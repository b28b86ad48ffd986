"""100 x one counter of the program's registry over another, in per cent
(unlabelled counters of ``deeplearning4j_tpu.obs.get_registry()``, in this
process); ``None`` where either is not registered or the denominator is 0."""


def read(ctx, num, den):
    try:
        from deeplearning4j_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    top, bottom = reg.get(num), reg.get(den)
    if top is None or bottom is None or not bottom.value():
        return None
    return 100.0 * top.value() / bottom.value()

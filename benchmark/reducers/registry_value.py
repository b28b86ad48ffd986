"""The value of one counter or gauge of the program's registry
(``deeplearning4j_tpu.obs.get_registry()``, in this process), scaled;
``labels`` picks one series of a labelled instrument. ``None`` where the
instrument is not registered: a tree without it reports nothing.

``run.py`` reads the metrics after the window and before the check, so the
value is the whole process's up to that moment: set-up and the window. A
counter that only set-up moves (compile phases: no cell compiles in its
window) then reads set-up's own total."""


def read(ctx, name, labels=None, scale=1.0):
    try:
        from deeplearning4j_tpu.obs import get_registry
    except ImportError:
        return None
    instrument = get_registry().get(name)
    if instrument is None:
        return None
    return instrument.value(**(labels or {})) * scale

"""Whole-step share of the chip's peak: the traced run's work per second,
over the part of its window before the profiler came on, times the
operations one unit of work needs (a function of ``flops.py``, recomputed
operations not counted) over chips times the peak."""

import flops


def read(ctx, work, steps, flops_fn, flops_args=(), dtype="bf16"):
    c = ctx["counters"]
    if not c.get("pre_trace_s") or not c.get(steps) or not ctx["peaks"]:
        return None
    per_step = c[work] / c[steps]
    rate = per_step * c["pre_trace_steps"] / c["pre_trace_s"]
    extra = [ctx["traffic"][a] for a in flops_args]
    per_unit = flops.resolve(flops_fn)(ctx["config"], *extra)
    peak = ctx["peaks"]["flops_per_s"][dtype] * ctx["chips"]
    return 100.0 * rate * per_unit / peak

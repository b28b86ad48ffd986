"""A percentile of one of the program's spans per batch of the window's
``fit()`` call, in ms.

The program records its own spans (``deeplearning4j_tpu.obs``, in this
process). The newest root span named ``fit`` is the window's call (set-up's
is older); its ``fit.iteration`` children that carry a ``batch``, in order,
are the iterations; ``first`` names a counter that cuts them to the first
``counters[first]`` (the iterations before the profiler came on: after it
has run, a host-fed loop stays slow). ``name`` is a span of the same trace
matched to those iterations by ``attrs["batch"]``, on whichever thread it
ran; a list of names reads their sum per batch (two spans between which
one piece of work moves from run to run). Prints the count, the median and the 90th percentile; ``None`` where
the program records no such spans.
"""

import math
import sys


def window_spans():
    """The spans of the newest ``fit`` root's trace, or ``(None, [])``."""
    try:
        from deeplearning4j_tpu.obs import get_tracer
    except ImportError:
        return None, []
    spans = get_tracer().spans()
    roots = [s for s in spans if s.name == "fit" and s.parent_id is None]
    if not roots:
        return None, []
    root = roots[-1]              # deposited in the order they finished
    return root, [s for s in spans if s.trace_id == root.trace_id]


def fit_iterations(ctx, first=None):
    """``(iterations, spans)``: the window's iterations in order, cut to
    the first ``counters[first]``, and every span of their trace."""
    root, spans = window_spans()
    if root is None:
        return [], []
    its = [s for s in spans if s.name == "fit.iteration"
           and s.parent_id == root.span_id and "batch" in s.attrs]
    its.sort(key=lambda s: s.attrs["batch"])
    if first is not None:
        its = its[: ctx["counters"].get(first) or 0]
    return its, spans


def percentile(values, q):
    values = sorted(values)
    return values[max(math.ceil(q / 100.0 * len(values)), 1) - 1]


def read(ctx, name, q=50, first=None):
    its, spans = fit_iterations(ctx, first)
    names = [name] if isinstance(name, str) else list(name)
    batches = {s.attrs["batch"] for s in its}
    per_batch = {}
    for s in spans:
        if s.name in names and s.attrs.get("batch") in batches:
            k = s.attrs["batch"]
            per_batch[k] = per_batch.get(k, 0.0) + 1e3 * s.time_s
    if len(per_batch) < 2:
        return None
    values = list(per_batch.values())
    print(f"span_ms: {'+'.join(names)} over {len(values)} of {len(its)} iterations, "
          f"median {percentile(values, 50):.3f} ms, p90 "
          f"{percentile(values, 90):.3f} ms", file=sys.stderr)
    return percentile(values, q)

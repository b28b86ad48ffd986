"""The share of the ``fit()`` loop that some span accounts for, in per
cent: 100 x (sum of the children of ``fit.iteration``) / (sum of
``fit.iteration``) over the iterations ``span_ms`` reads. The rest is the
iteration's self time: what the loop does outside any span."""

import sys

from reducers.span_ms import fit_iterations


def read(ctx, first=None):
    its, spans = fit_iterations(ctx, first)
    total = sum(s.time_s for s in its)
    if len(its) < 2 or total <= 0:
        return None
    ids = {s.span_id for s in its}
    covered = sum(s.time_s for s in spans if s.parent_id in ids)
    print(f"span_cover: {1e3 * covered / len(its):.3f} of "
          f"{1e3 * total / len(its):.3f} ms an iteration in child spans, "
          f"{len(its)} iterations", file=sys.stderr)
    return 100.0 * covered / total

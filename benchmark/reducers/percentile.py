"""A percentile of one of the run's lists (nearest rank on the sorted
values), scaled; ``first`` names a counter that cuts the list to its first
values (the iterations before the profiler came on); prints the count it
was taken from."""

import math
import sys


def read(ctx, key, q, scale=1.0, first=None):
    values = ctx["counters"].get(key) or ()
    if first is not None:            # only the first counters[first] values
        values = values[: ctx["counters"].get(first) or 0]
    values = sorted(values)
    if len(values) < 2:
        return None
    rank = max(math.ceil(q / 100.0 * len(values)), 1)
    print(f"percentile: p{q} of {len(values)} values of {key}, median "
          f"{values[len(values) // 2] * scale!r}", file=sys.stderr)
    return values[rank - 1] * scale

"""Device time of the events a regular expression finds by their scope, or
of all the OTHERS, over the stretch's busy time, in per cent.

As ``scope_time_share`` (whose ``scopes_of`` reads each event's ``tf_op``,
the name stack of ``jax.named_scope``s and JAX's own components the
operation sat in), but the scopes are ONE regular expression, matched at the
start of a path component of the ``tf_op``, inside any ``jvp(...)`` /
``transpose(...)`` wrapper: ``attn_qkv|attn_wo`` finds both projections, and
``[^/()]*\\.ConvolutionLayer(?:[/):]|$)`` every component that ENDS in
``.ConvolutionLayer`` (a ``ComputationGraph`` names a node's operations
``<node>.<Type>``). An event also belongs if its instruction's own name
starts with one of ``ops`` (XLA's ``ragged-dot`` kernels lose their scope).
With ``rest`` the share is that of the events that do NOT belong: what no
scope names. The two shares of one pattern add up to 100. Containers
(``while``) are left out, as everywhere.

A fused event carries ONE ``tf_op``, its root instruction's: a convolution
that XLA fused with the batch norm and the ReLU after it is counted where
the root sits, whole. Finds nothing to read (no trace, no device plane, no
event matches and ``rest`` is false) -> reports nothing, never 0."""

from __future__ import annotations

import re
import sys

import xplane
from reducers import scope_time_share


def split_seconds(ctx, pattern, ops=()):
    """(seconds matched, seconds not matched, events matched) of the
    window's device events, averaged over chips; ``None`` where there is no
    trace to read."""
    tr = ctx["trace"]
    if tr is None or not tr.device_ops:
        return None
    try:
        tf_ops = scope_time_share.scopes_of(
            xplane.find_xplane(scope_time_share.TRACE_DIR))
    except (FileNotFoundError, ValueError, IndexError):
        tf_ops = {}
    under = re.compile(r"(?:^|[/(])(?:%s)" % pattern)
    inside, outside, n = 0.0, 0.0, 0
    for s, e, name in tr.ops_in_window():
        if xplane._is_container(name):
            continue
        own = name.split(" = ", 1)[0].lstrip("%")
        took = min(e, tr.t1) - max(s, tr.t0)
        if any(own.startswith(p) for p in ops) \
                or under.search(tf_ops.get(name, "")):
            inside += took
            n += 1
        else:
            outside += took
    chips = max(len(tr.device_ops), 1)
    return inside / 1e9 / chips, outside / 1e9 / chips, n // chips


def read(ctx, pattern, ops=(), rest=False):
    found = split_seconds(ctx, pattern, ops)
    tr = ctx["trace"]
    if found is None or tr.busy_s <= 0:
        return None
    inside, outside, n = found
    seconds = outside if rest else inside
    if seconds <= 0 and not rest:
        return None
    print(f"scope_regex_share {pattern!r}{' (the rest)' if rest else ''}: "
          f"{n} events matched, {seconds:.6f} s of {tr.busy_s:.6f} s busy",
          file=sys.stderr)
    return 100.0 * seconds / tr.busy_s

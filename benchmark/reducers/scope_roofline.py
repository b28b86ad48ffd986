"""A named scope's share of its roofline in the traced stretch: as
``kernel_roofline``, but the events are found by the program's scope (see
``scope_time_share``) and ``flops_fn`` gives the operations and bytes of ONE
STEP, all layers and both passes (so layers of different kinds are counted
each at its own size); a name in ``shape`` is looked up in the
configuration, the traffic and the run's counters. The least time the chip could take is
max(operations / peak FLOP/s, bytes / peak bytes/s) x steps; the share is
that over the summed device time of every event under the scope. Work the
program does beyond what the algorithm needs (a forward recomputed in the
backward pass, elementwise passes between the products) is in the time and
not in the operations. Finds nothing (no event matches) -> reports nothing,
never 0."""

import sys

import flops
from reducers.scope_time_share import scope_seconds


def read(ctx, scopes, flops_fn, shape, ops=(), dtype="bf16"):
    steps = ctx["counters"].get("traced_steps")
    seconds, n_events = scope_seconds(ctx, scopes, ops)
    if not steps or seconds <= 0:
        return None
    look = {**ctx["config"], **ctx["traffic"], **ctx["counters"]}
    try:
        dims = [look[k] if isinstance(k, str) else k for k in shape]
    except KeyError as missing:     # a program without that counter
        print(f"scope_roofline {scopes}: no {missing} to read", file=sys.stderr)
        return None
    work, nbytes = flops.resolve(flops_fn)(*dims)
    t_flops = work * steps / ctx["peaks"]["flops_per_s"][dtype]
    t_bytes = nbytes * steps / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    print(f"scope_roofline {scopes}: {n_events} events, {seconds:.6f} s over "
          f"{steps} steps; least time {max(t_flops, t_bytes):.6f} s, "
          f"{bound}-bound (flops {t_flops:.6f} s, bytes {t_bytes:.6f} s)",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / seconds

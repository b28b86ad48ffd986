"""A kernel's share of its roofline in the traced stretch.

The least time the chip could take for the calls the algorithm needs (one
forward and one backward per layer and step: ``flops.py::<flops_fn>`` gives
operations and bytes for one such pair) is max(operations / peak FLOP/s,
bytes / peak bytes/s); the share is that over the summed device time of
every event of the kernel in the stretch. A call the program makes beyond
those (a forward recomputed in the backward pass) is in the time and not in
the operations. Finds nothing (no event matches) -> reports nothing."""

import sys

import flops


def read(ctx, pattern, flops_fn, shape, calls_per_step, dtype="bf16"):
    tr, steps = ctx["trace"], ctx["counters"].get("traced_steps")
    if tr is None or not steps:
        return None
    seconds, n_events = tr.event_time_s(pattern)
    if seconds <= 0:
        return None
    look = {**ctx["config"], **ctx["traffic"]}
    dims = [look[k] if isinstance(k, str) else k for k in shape]
    ops, nbytes = flops.resolve(flops_fn)(*dims)
    calls = steps * (look[calls_per_step] if isinstance(calls_per_step, str)
                     else calls_per_step)
    t_flops = ops * calls / ctx["peaks"]["flops_per_s"][dtype]
    t_bytes = nbytes * calls / ctx["peaks"]["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    print(f"kernel_roofline {pattern!r}: {n_events} events, {seconds:.6f} s "
          f"over {steps} steps ({n_events / calls:.2f} events per needed "
          f"forward+backward pair); least time {max(t_flops, t_bytes):.6f} s, "
          f"{bound}-bound (flops {t_flops:.6f} s, bytes {t_bytes:.6f} s)",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / seconds

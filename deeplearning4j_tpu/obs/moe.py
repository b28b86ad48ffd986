"""Expert-load counters of a routed expert layer that holds a share of the
experts (``zoo.transformer._moe_share``).

The train step computes, on the device and beside the loss, one float32
row per layer: assignments in all (tokens x top-k), assignments to the
experts held here, assignments dropped (always 0: the layer's buffer has a
row for every assignment), and the held experts' largest load over their
mean; where the router has a skip output, a fifth number: the tokens that
took it; and, beside the row, the rows the layer's bounded row movement
crossed (the way back's gradient fills only the rows routed here, a whole
pass at a time, of a buffer that has a row for every assignment). Whoever
fetches the loss fetches those small arrays with it and hands them here;
nothing in the step syncs for them.

- ``dl4j_moe_assignments_total``, ``dl4j_moe_local_assignments_total``,
  ``dl4j_moe_dropped_total``: summed over the layers of every recorded step;
- ``dl4j_moe_rows_moved_total``: rows the bounded movement crossed, summed
  likewise (registered only where the step tells them): over
  ``dl4j_moe_assignments_total`` the share of the buffer it moves;
- ``dl4j_moe_skipped_total``: tokens that took the router's skip, summed
  likewise (registered only by rows of five);
- ``dl4j_moe_load_max_over_mean``: the newest recorded step's worst layer
  (1.0 = the held experts are loaded evenly).
"""

from __future__ import annotations

import numpy as np


def record_expert_load(stats) -> dict:
    """Count one fetched step's ``(layers, 4)`` or ``(layers, 5)``
    expert-load array (or the train step's fourth output, which holds it
    under ``"load"`` and the rows moved under ``"moved"``) into the
    process-wide registry; returns what it read as a dict (``skipped`` only
    for rows of five, ``moved`` only from a step that tells it)."""
    from . import get_registry
    moved = None
    if isinstance(stats, dict):
        moved, stats = stats.get("moved"), stats["load"]
    stats = np.asarray(stats, np.float64)
    stats = stats.reshape(-1, stats.shape[-1] if stats.ndim > 1 else 4)
    total, local, dropped = (float(v) for v in stats[:, :3].sum(axis=0))
    worst = float(stats[:, 3].max())
    reg = get_registry()
    reg.counter("dl4j_moe_assignments_total",
                "token-to-expert assignments routed (tokens x top-k, summed "
                "over layers) in the recorded steps").inc(total)
    reg.counter("dl4j_moe_local_assignments_total",
                "assignments to the experts this program holds").inc(local)
    reg.counter("dl4j_moe_dropped_total",
                "assignments to held experts that were not computed").inc(dropped)
    reg.gauge("dl4j_moe_load_max_over_mean",
              "largest held expert's load over the held experts' mean, worst "
              "layer of the newest recorded step").set(worst)
    read = {"assignments": total, "local": local, "dropped": dropped,
            "max_over_mean": worst,
            "local_share": local / total if total else 0.0}
    if moved is not None:
        read["moved"] = float(np.sum(np.asarray(moved, np.float64)))
        reg.counter("dl4j_moe_rows_moved_total",
                    "rows the expert layer's bounded row movement crossed: "
                    "the rows routed here and the last pass's round-up"
                    ).inc(read["moved"])
    if stats.shape[1] > 4:
        read["skipped"] = float(stats[:, 4].sum())
        reg.counter("dl4j_moe_skipped_total",
                    "tokens that took the router's skip output and got "
                    "nothing from the expert layer").inc(read["skipped"])
    return read

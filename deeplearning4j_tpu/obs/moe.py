"""Expert-load counters of a routed expert layer that holds a share of the
experts (``zoo.transformer._moe_share``).

The train step computes, on the device and beside the loss, one float32
row per layer: assignments in all (tokens x top-k), assignments to the
experts held here, assignments dropped (always 0: the layer's buffer has a
row for every assignment), and the held experts' largest load over their
mean. Whoever fetches the loss fetches that small array with it and hands
it here; nothing in the step syncs for it.

- ``dl4j_moe_assignments_total``, ``dl4j_moe_local_assignments_total``,
  ``dl4j_moe_dropped_total``: summed over the layers of every recorded step;
- ``dl4j_moe_load_max_over_mean``: the newest recorded step's worst layer
  (1.0 = the held experts are loaded evenly).
"""

from __future__ import annotations

import numpy as np


def record_expert_load(stats) -> dict:
    """Count one fetched step's ``(layers, 4)`` expert-load array into the
    process-wide registry; returns what it read as a dict."""
    from . import get_registry
    stats = np.asarray(stats, np.float64).reshape(-1, 4)
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
    return {"assignments": total, "local": local, "dropped": dropped,
            "max_over_mean": worst,
            "local_share": local / total if total else 0.0}

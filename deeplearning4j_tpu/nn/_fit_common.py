"""The piece the two ``fit()`` loops share (MLN / CG): a batch is fetched
and its host-to-device copies are issued in one place, so that both loops
stage batch k+1 between the dispatch of step k and the fetch of its loss.
One copy — the spans' names, their ``batch`` and the counters must not
drift between the two networks."""

from __future__ import annotations

import jax

from ..obs import get_registry
from ..obs.spans import span


def fit_counters():
    """``(batches, staged_ahead)``: batches ``fit()`` dispatched, and those
    of them whose copy was issued while an earlier step was unsynced."""
    reg = get_registry()
    return (reg.counter("dl4j_fit_batches_total",
                        "batches fit() dispatched"),
            reg.counter("dl4j_fit_staged_ahead_total",
                        "batches copied to the device while an earlier "
                        "step was still unsynced"))


def stage_batch(batches, k, to_device):
    """Batch ``k`` of the call: ``fit.next`` (until the iterator hands it
    over) and ``fit.h2d`` (``to_device``: the ``jnp.asarray`` calls, which
    return before the bytes have moved; attrs ``bytes``), both carrying
    ``batch`` = k wherever they lie. Returns ``(ds, arrays)``, or ``None``
    where the source is exhausted.

    ``ds`` rides along so that the host arrays outlive the copy that reads
    them asynchronously. Holding it is all that takes today: no iterator
    writes into a batch it has handed over (``AsyncDataSetIterator._unpack``
    builds fresh arrays from the ring's bytes, and a batch sent by reference
    is the source's own), so nothing here aliases a reused buffer.
    """
    with span("fit.next", attrs={"batch": k}):
        ds = next(batches, None)
    if ds is None:
        return None
    with span("fit.h2d", attrs={"batch": k}) as h2d:
        arrays = to_device(ds)
        h2d.set_attr("bytes", sum(
            a.nbytes for a in jax.tree_util.tree_leaves(arrays)))
    return ds, arrays

"""What the training drivers share: reading the program's side of the
comparison out of its optimizer state and parameters."""

from __future__ import annotations

import jax

from reference.common import delta_norms, leaf_norms

_leaf_norms = jax.jit(leaf_norms)
_delta_norms = jax.jit(delta_norms)


def first_gradient_norms(opt_state, b1: float) -> dict:
    """Per-leaf norm of the first step's gradient as the optimizer got it,
    worked out from Adam's first moment after one step: mu = (1 - b1) g."""
    for part in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")):
        if hasattr(part, "mu"):
            return {k: float(v) / (1 - b1)
                    for k, v in _leaf_norms(part.mu).items()}
    raise ValueError("no Adam first moment in the optimizer's state")


def change_norms(params, start) -> dict:
    """Per-leaf norm of the parameters' change from ``start``."""
    return {k: float(v) for k, v in _delta_norms(params, start).items()}


class State:
    """What set-up hands to the window and to the check."""

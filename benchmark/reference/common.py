"""What the references share: the seed's PRNG key and per-leaf norms."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """--seed may be a little over 2**31: folded into 32 bits."""
    return jax.random.PRNGKey(np.uint32(seed % (2 ** 32)))


def leaf_norms(tree) -> dict:
    """{leaf path: l2 norm} of a tree of arrays."""
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(x)))
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def delta_norms(tree, start) -> dict:
    """Per-leaf norm of ``tree - start``."""
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, tree, start))

"""Ring attention — sequence/context parallelism for long sequences.

Reference counterpart: DL4J has no long-context story; this is the TPU-native
capability the goal spec demands (sequence parallel over the 'sp' mesh axis).

Design (Liu et al. ring attention, blockwise online softmax): queries stay
resident per device; key/value blocks rotate around the 'sp' ring via
``lax.ppermute`` (ICI neighbor exchange), each hop overlapping the local
blockwise attention. Partials are merged in (out, lse) form — numerically
stable log-sum-exp weighting — so the result is EXACT: identical to full
attention, with O(T/n) memory per device.

r4 rework:
- No bias tensors: the only hop that needs masking is the diagonal one
  (own k/v), and there the q/k blocks are ALIGNED, so plain causal
  attention applies. Earlier hops are unmasked; later hops are fully
  masked and are SKIPPED via ``lax.cond`` (an all-zero partial), halving
  the causal ring's compute instead of exp(-1e30)-ing it away.
- The local block attention can run the pallas flash kernel
  (``use_flash="auto"``): ``flash_attention_lse`` streams the block
  through VMEM and returns the lse the merge needs, custom-VJP included,
  so the per-shard score matrix never hits HBM — the composition the
  long-context regime exists for.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _xla_attn_lse(q, k, v, causal):
    """(B,T,H,D) attention returning (out f32, lse (B,H,T) f32)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.maximum(jnp.max(s, axis=-1), -1e30)                 # (B,H,Tq)
    p = jnp.exp(s - m[..., None])
    den = jnp.sum(p, axis=-1)                                   # (B,H,Tq)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    den_safe = jnp.maximum(den, 1e-30)
    out = out.astype(jnp.float32) / den_safe.transpose(0, 2, 1)[..., None]
    return out, m + jnp.log(den_safe)


def _flash_attn_lse(q, k, v, causal, interpret):
    """Flash-kernel local attention in ring layout (B,T,H,D)."""
    from ..kernels.flash_attention import (GLUE_SCOPE, _tuned_blocks,
                                           flash_attention_lse)
    b, t, h, d = q.shape
    bq, bk = _tuned_blocks(b, h, t, d, q.dtype, causal, interpret)
    with jax.named_scope(GLUE_SCOPE):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = flash_attention_lse(q, k, v, None, causal, bq, bk, interpret)
    with jax.named_scope(GLUE_SCOPE):
        return out.transpose(0, 2, 1, 3).astype(jnp.float32), lse


def _merge(acc, new):
    """Merge two (out, lse) online-softmax partials."""
    out_a, lse_a = acc
    out_n, lse_n = new
    lse = jnp.logaddexp(lse_a, lse_n)                            # (B,H,Tq)
    ca = jnp.exp(lse_a - lse).transpose(0, 2, 1)[..., None]      # (B,Tq,H,1)
    cn = jnp.exp(lse_n - lse).transpose(0, 2, 1)[..., None]
    return out_a * ca + out_n * cn, lse


def _use_flash(use_flash, t_local):
    if use_flash == "auto":
        return jax.default_backend() == "tpu" and t_local >= 1024
    return bool(use_flash)


def ring_attention_sharded(q, k, v, axis_name: str = "sp",
                           causal: bool = True, use_flash="auto",
                           interpret=None):
    """Runs INSIDE shard_map: q/k/v are the local sequence shard
    (B, T_local, H, D). Exact causal attention across the full sequence."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    t_local = q.shape[1]
    flash = _use_flash(use_flash, t_local)

    def attn(q_, k_, v_, causal_):
        if flash:   # the kernels and the layout around them name themselves
            return _flash_attn_lse(q_, k_, v_, causal_, interpret)
        with jax.named_scope("attn_core"):
            return _xla_attn_lse(q_, k_, v_, causal_)

    # hop 0: own k/v — the diagonal block is ALIGNED, plain causal applies
    acc = attn(q, k, v, causal)
    kv = (k, v)
    perm = [(i, (i + 1) % n) for i in range(n)]
    zero = (jnp.zeros_like(acc[0]),
            jnp.full_like(acc[1], -jnp.inf))
    for hop in range(1, n):
        kv = jax.tree_util.tree_map(lambda x: lax.ppermute(x, axis_name, perm), kv)
        src = (idx - hop) % n   # whose k/v we now hold
        if causal:
            # src < idx: full (unmasked) block; src > idx: entirely above
            # the diagonal — skip the matmuls, contribute a zero partial
            new = lax.cond(src < idx,
                           lambda ops: attn(q, ops[0], ops[1], False),
                           lambda ops: zero, kv)
        else:
            new = attn(q, kv[0], kv[1], False)
        with jax.named_scope("attn_core"):
            acc = _merge(acc, new)
    out, _ = acc
    return out.astype(q.dtype)


def ring_attention_inner(q, k, v, causal: bool = True, axis_name: str = "sp",
                         use_flash="auto", interpret=None):
    """Mesh-aware dispatch: ring when 'sp' is an in-scope mapped axis."""
    try:
        lax.axis_index(axis_name)  # raises NameError outside shard_map('sp')
        in_ring = True
    except NameError:
        in_ring = False
    if in_ring:
        return ring_attention_sharded(q, k, v, axis_name, causal, use_flash,
                                      interpret)
    with jax.named_scope("attn_core"):
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal)


def ring_attention(mesh: Mesh, q, k, v, causal: bool = True,
                   use_flash="auto", interpret=None):
    """Host-callable wrapper: shard q/k/v over ('dp', 'sp') and run the ring.

    q/k/v: (B, T, H, D) global arrays. Returns global (B, T, H, D).
    ``use_flash``: True / False / "auto" — run the pallas flash kernel for
    the per-shard local attention (auto: on TPU when the local shard is
    long enough to engage it).
    """
    spec = P("dp" if "dp" in mesh.axis_names else None, "sp", None, None)
    fn = shard_map(
        partial(ring_attention_sharded, axis_name="sp", causal=causal,
                use_flash=use_flash, interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)

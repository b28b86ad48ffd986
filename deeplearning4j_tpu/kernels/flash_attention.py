"""Flash attention — pallas TPU kernel (FlashAttention-2 schedule).

Replaces the reference's cuDNN/libnd4j fused-attention path
(``org.deeplearning4j.nn.layers.recurrent/attention``, libnd4j
``multiHeadDotProductAttention``) with a TPU-native kernel: online-softmax
tiling keeps the (T, T) score matrix out of HBM, MXU matmuls accumulate in
f32, and the backward pass recomputes probabilities per tile (two passes:
dQ over query tiles, dK/dV over key tiles) instead of materialising them.

VMEM discipline: K/V (and in the backward passes Q/dO/lse/delta) STREAM
through the kernel one block per grid step — the KV/Q block index is the
fastest grid dimension and the online-softmax state lives in VMEM scratch
that persists across it (TPU grids iterate sequentially). Peak VMEM is
O(block_q·d + block_k·d), independent of sequence length, so the kernel
works exactly in the long-context regime flash attention exists for.

Shapes: q is (B, H, T, D), k and v are (B, Hkv, T, D) with H a multiple of
Hkv (query head i reads K/V head i // (H // Hkv): grouped-query attention;
Hkv == H is the plain case); output (B, H, T, D). ``causal`` applies a
lower-triangular mask, ``window`` (with ``causal``) keeps only the keys with
i - j < window: key blocks that lie wholly outside the band, on either side,
are skipped via pl.when and their fetches clamped away, in all three kernels.
Falls back to interpreter mode off-TPU so the same code path is
unit-testable on the CPU mesh.

Names on the device: each kernel sits in a ``jax.named_scope`` of its own
name (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, with ``_win`` under
a window); what XLA runs around them (the transposes between the callers'
(B, T, H, D) and the kernels' (B, H, T, D), the backward's row sums of dO·O,
the 8-lane copies of lse and delta) sits in ``attn_core``, so that a profile
tells the kernels' time from the layout's.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ._common import interpret_default as _interpret_default
from ._common import pltpu

NEG_INF = -1e30
#: the scope of what XLA runs around the kernels (see the module docstring)
GLUE_SCOPE = "attn_core"


def _block_sizes(t: int, d: int, block_q: int, block_k: int):
    bq = min(block_q, t)
    bk = min(block_k, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _live(qi, kj, bq, bk, causal, window):
    """Does query block ``qi`` see any key of key block ``kj``? (The dkv
    kernel asks the same of its grid, which streams the query blocks.)"""
    if not causal:
        return kj >= 0
    live = kj * bk <= qi * bq + bq - 1          # not wholly above the diagonal
    if window is not None:                      # not wholly left of the band
        live = live & (kj * bk + bk - 1 > qi * bq - window)
    return live


def _masked(s, qi, kj, bq, bk, causal, window):
    if not causal:
        return s
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    dead = k_idx > q_idx
    if window is not None:
        dead = dead | (q_idx - k_idx >= window)
    return jnp.where(dead, NEG_INF, s)


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, window):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip key blocks that lie entirely above the diagonal (or, with
    # a window, entirely left of the band)
    live = _live(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _compute():
        # operands stay bf16: the v5e MXU multiplies bf16 natively with
        # f32 accumulation (preferred_element_type); casting to f32 first
        # runs the MXU at a fraction of peak and doubles VMEM traffic
        q = q_ref[0]                                        # (bq, d)
        k = k_ref[0]                                        # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * scale                   # (bq, bk) f32
        # a row whose keys in this block are all masked adds exp(0) terms
        # while its running max is still NEG_INF; the first block with a
        # key it sees (the diagonal at the latest) wipes them: corr = 0
        s = _masked(s, qi, kj, bq, bk, causal, window)
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse broadcast over a small lane dim so the block is TPU-tileable
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(l_safe))[:, None], lse_ref.shape[1:])


def _causal_kv_map(bq, bk, causal, window=None, group=1):
    """KV-block index map. For causal grids, dead steps (key block entirely
    above the diagonal, or entirely left of the window's band) CLAMP to the
    nearest live key block: Pallas skips the HBM->VMEM fetch when successive
    steps reference the same block, so the part of the rectangular grid that
    pl.when skips stops costing bandwidth too. (Compute for dead steps is
    already skipped; without the clamp their DMAs still ran — measured ~2x
    wasted attention traffic at long T.) Row ``bh`` of the flattened
    (B*H) queries reads row ``bh // group`` of the flattened (B*Hkv) keys."""
    row = (lambda bh: bh) if group == 1 else (lambda bh: bh // group)
    if not causal:
        return lambda bh, i, j: (row(bh), j, 0)
    if window is None:
        return lambda bh, i, j: (row(bh),
                                 jnp.minimum(j, (i * bq + bq - 1) // bk), 0)
    return lambda bh, i, j: (row(bh), jnp.clip(
        j, jnp.maximum(i * bq - window + 1, 0) // bk,
        (i * bq + bq - 1) // bk), 0)


def _suffix(window):
    """The window kernels carry names of their own, so that a trace tells
    them from the full causal ones."""
    return "" if window is None else "_win"


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret, window=None):
    b, h, t, d = q.shape
    hkv = k.shape[1]
    bq, bk = _block_sizes(t, d, block_q, block_k)
    with jax.named_scope(GLUE_SCOPE):
        qf = q.reshape(b * h, t, d)
        kf = k.reshape(b * hkv, t, d)
        vf = v.reshape(b * hkv, t, d)
    kv_map = _causal_kv_map(bq, bk, causal, window, h // hkv)
    grid = (b * h, t // bq, t // bk)      # kv block = fastest dim (streamed)
    name = "flash_fwd" + _suffix(window)
    with jax.named_scope(name):
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              window=window),
            grid=grid,
            in_specs=[pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bk, d), kv_map)],
            out_specs=[pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                       pl.BlockSpec((1, bq, 8), lambda bh, i, j: (bh, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
                       jax.ShapeDtypeStruct((b * h, t, 8), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, 8), jnp.float32),
                            pltpu.VMEM((bq, 8), jnp.float32)],
            interpret=interpret,
            name=name,
        )(qf, kf, vf)
    with jax.named_scope(GLUE_SCOPE):
        return out.reshape(b, h, t, d), lse[:, :, 0].reshape(b, h, t)


# --------------------------------------------------------------- backward --

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, scale, causal, window):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = _live(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _masked(s, qi, kj, bq, bk, causal, window)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal,
                    window, group):
    kj = pl.program_id(1)
    # the streamed dimension runs over the query blocks of every query head
    # of this K/V head's group in turn: dK and dV sum over the group
    step = pl.program_id(2)
    n_steps = pl.num_programs(2)
    qi = step if group == 1 else step % (n_steps // group)
    bk = k_ref.shape[1]
    bq = q_ref.shape[1]

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: only query blocks at or below this key block contribute (and,
    # with a window, none wholly beyond the band)
    live = _live(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * scale                    # (bq, bk)
        s = _masked(s, qi, kj, bq, bk, causal, window)
        p = jnp.exp(s - lse[:, None])
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(step == n_steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ------------------------------------------------------------- public api --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_pallas(q, k, v, scale: Optional[float] = None,
                            causal: bool = False, block_q: int = 128,
                            block_k: int = 128,
                            interpret: Optional[bool] = None,
                            window: Optional[int] = None):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                        window)
    return out


def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Fused scaled-dot-product attention. q: (B, H, T, D), k/v:
    (B, Hkv, T, D) with H % Hkv == 0 → (B, H, T, D). ``window`` (needs
    ``causal``): query i sees keys j with 0 <= i - j < window."""
    if window is not None and not causal:
        raise ValueError("a window is a band under the diagonal: it needs "
                         "causal=True")
    if q.shape[1] % k.shape[1] or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads cannot share "
                         f"{k.shape[1]} K/V heads")
    return _flash_attention_pallas(q, k, v, scale, causal, block_q, block_k,
                                   interpret, window)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               window=None):
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    window)
    # The two residuals only the kernel can rebuild carry names, so that a
    # jax.checkpoint policy can keep them (zoo/transformer.py's "save_attn"
    # does) and the backward pass need not run this kernel a second time.
    # Under no policy, or one that looks at no name, a name is the identity.
    # The output is named as (B, T, H, D), which the ntc callers hold
    # anyway as the lane-dense (B, T, H*D) matrix their output projection
    # reads (the transposes around it cancel). A copy saved in the kernel's
    # own (B, H, T, D) layout is padded from D = 64 to 128 lanes, twice the
    # bytes (v5e, compiled at b16 T1024 H16: +0.83 GB over 24 layers).
    with jax.named_scope(GLUE_SCOPE):
        out_t = checkpoint_name(out.transpose(0, 2, 1, 3), "attn_out")
        lse = checkpoint_name(lse, "attn_lse")
        return out_t.transpose(0, 2, 1, 3), (q, k, v, out_t, lse)


def _rowsum_do_o(g, out_t):
    """rowsum(dO * O), (B, H, T) f32, from O as `_flash_fwd` saved it."""
    with jax.named_scope(GLUE_SCOPE):
        return jnp.sum(
            g.astype(jnp.float32)
            * out_t.transpose(0, 2, 1, 3).astype(jnp.float32), axis=-1)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out_t, lse = res
    return _flash_bwd_impl(scale, causal, block_q, block_k, interpret,
                           q, k, v, g, lse, _rowsum_do_o(g, out_t), window)


def _flash_bwd_impl(scale, causal, block_q, block_k, interpret,
                    q, k, v, g, lse, delta, window=None):
    """Shared backward. ``delta`` is rowsum(dO·O) for the plain kernel; the
    lse-returning variant passes rowsum(dO·O) − dLSE instead — the ONLY
    difference an lse cotangent makes (ds = p·(dp − delta + dlse), so it
    folds into delta; dv is dlse-independent)."""
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, t, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    bq, bk = _block_sizes(t, d, block_q, block_k)
    nq = t // bq
    flat = lambda x: x.reshape(-1, t, x.shape[-1])
    with jax.named_scope(GLUE_SCOPE):
        qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(g)
        lsef = jnp.broadcast_to(lse.reshape(b * h, t)[:, :, None],
                                (b * h, t, 8))
        deltaf = jnp.broadcast_to(delta.reshape(b * h, t)[:, :, None],
                                  (b * h, t, 8))

    kv_map = _causal_kv_map(bq, bk, causal, window, group)
    # dkv grid streams q blocks (of each query head of the group in turn);
    # dead steps (q block entirely above the diagonal, or entirely beyond
    # the band) clamp to the nearest live q block — same no-refetch trick as
    # _causal_kv_map, mirrored
    if group == 1:
        q_of = lambda bh, i: (bh, i)
    else:
        q_of = lambda bh, i: (bh * group + i // nq, i % nq)
    if not causal:
        q_blk = lambda j, i: i
    elif window is None:
        q_blk = lambda j, i: jnp.maximum(i, (j * bk) // bq)
    else:
        q_blk = lambda j, i: jnp.clip(
            i, (j * bk) // bq,
            jnp.minimum((j * bk + bk + window - 2) // bq, nq - 1))

    def q_map(bh, j, i):
        row, blk = q_of(bh, i)
        return (row, q_blk(j, blk), 0)

    sfx = _suffix(window)
    with jax.named_scope("flash_bwd_dq" + sfx):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              window=window),
            grid=(b * h, t // bq, t // bk),   # kv block streamed (fastest dim)
            in_specs=[pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bk, d), kv_map),
                      pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                      pl.BlockSpec((1, bq, 8), lambda bh, i, j: (bh, i, 0)),
                      pl.BlockSpec((1, bq, 8), lambda bh, i, j: (bh, i, 0))],
            out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq" + sfx,
        )(qf, kf, vf, dof, lsef, deltaf)

    with jax.named_scope("flash_bwd_dkv" + sfx):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              window=window, group=group),
            # q block streamed (fastest dim), group * nq steps a key block
            grid=(b * hkv, t // bk, group * nq),
            in_specs=[pl.BlockSpec((1, bq, d), q_map),
                      pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                      pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                      pl.BlockSpec((1, bq, d), q_map),
                      # lse/delta stream with the q block — clamp them too, or
                      # dead causal steps keep fetching these (1, bq, 8) blocks
                      pl.BlockSpec((1, bq, 8), q_map),
                      pl.BlockSpec((1, bq, 8), q_map)],
            out_specs=[pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                       pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0))],
            out_shape=[jax.ShapeDtypeStruct((b * hkv, t, d), q.dtype),
                       jax.ShapeDtypeStruct((b * hkv, t, d), q.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dkv" + sfx,
        )(qf, kf, vf, dof, lsef, deltaf)

    with jax.named_scope(GLUE_SCOPE):
        return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash_attention_pallas.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------- lse-returning api --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_lse_pallas(q, k, v, scale: Optional[float] = None,
                                causal: bool = False, block_q: int = 128,
                                block_k: int = 128,
                                interpret: Optional[bool] = None):
    (out, lse), _ = _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k,
                                   interpret)
    return out, lse


def flash_attention_lse(q, k, v, scale: Optional[float] = None,
                        causal: bool = False, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp, ``lse`` (B, H, T) f32 — the quantity ring attention needs
    to merge partial attention results across sequence shards. The custom
    VJP propagates BOTH cotangents (dLSE folds into the delta term; see
    `_flash_bwd_impl`)."""
    return _flash_attention_lse_pallas(q, k, v, scale, causal, block_q,
                                       block_k, interpret)


def _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k, interpret):
    out, res = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return (out, res[4]), res


def _flash_bwd_lse(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out_t, lse = res
    g_out, g_lse = g
    delta = _rowsum_do_o(g_out, out_t)
    if g_lse is not None and jnp.issubdtype(
            getattr(g_lse, "dtype", jnp.float32), jnp.floating):
        delta = delta - g_lse.astype(jnp.float32)
    return _flash_bwd_impl(scale, causal, block_q, block_k, interpret,
                           q, k, v, g_out, lse, delta)


_flash_attention_lse_pallas.defvjp(_flash_fwd_lse, _flash_bwd_lse)


def _tuned_blocks(b, h, t, d, dtype, causal, interpret, window=None,
                  group=1) -> tuple:
    """Autotuned (block_q, block_k) for this attention shape — timed on the
    real chip once per (shape, window, group), cached to disk
    (kernels/autotune.py). Off-TPU (or with tuning disabled) falls back to
    the measured v5e sweet spot (min(512,T), min(1024,T)) rather than
    re-timing."""
    import os

    if interpret or jax.default_backend() != "tpu" \
            or os.environ.get("DL4J_TPU_AUTOTUNE", "1") != "1":
        return _block_sizes(t, d, 512, 1024)
    from .autotune import autotune

    def make_run(cand):
        bq, bk = cand
        if t % bq or t % bk:
            return None
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, h, t, d), dtype)
        kv = q[:, ::group]

        # Time the TRAIN path (fwd + both bwd passes): block-size choice is
        # dominated by the backward kernels, and a fwd-only race mispicks
        # (the flash4 tuner's 128×128 regression).
        def loss(q_, k_, v_):
            return jnp.sum(_flash_attention_pallas(
                q_, k_, v_, None, causal, bq, bk, False, window
            ).astype(jnp.float32))

        grad_fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        def run():
            return grad_fn(q, kv, kv)[0]
        return run

    chip = jax.devices()[0].device_kind.replace(" ", "_")
    # "flash5" (r5): tune on the GRAD path with large-block candidates.
    # The flash4 tuner timed the forward kernel only and picked 128×128,
    # but the 128-vs-1024 block gap lives in the two backward passes: the
    # diag_t4096 phase-F fwd+bwd sweep (2026-08-01, v5e) measured t4096/b4
    # 34.0 ms at 128×128 vs 6.1 ms at 1024×1024, and t1024/b16 9.9 ms vs
    # 2.1 ms at 512×1024 — the grid is (B·H)(T/bq)(T/bk) SEQUENTIAL steps,
    # and per-step grid+DMA overhead (~1 µs) dominates small blocks.
    # Candidates ≥2048 are not raced: none has been timed on a chip, and
    # 1024×1024 (s block 4 MB f32 + kv 256 KB) already sits well inside
    # VMEM at d=64.
    key = f"flash5:{chip}:{b}x{h}x{t}x{d}:{jnp.dtype(dtype).name}:{causal}"
    if window is not None or group != 1:       # a race of their own
        key += f":w{window}:g{group}"
    return autotune(
        key,
        [(512, 1024), (1024, 1024), (1024, 512), (512, 512),
         (256, 512), (256, 256), (128, 128)],
        make_run)


def flash_attention_ntc(q, k, v, causal=False, interpret=None, window=None):
    """(B, T, H, D)-layout adapter around :func:`flash_attention` — the
    layout the nn layers and the transformer use; k and v may hold fewer
    heads than q (grouped-query attention). Block sizes are autotuned per
    (shape, window, group) on the real chip."""
    b, t, h, d = q.shape
    bq, bk = _tuned_blocks(b, h, t, d, q.dtype, causal, interpret, window,
                           h // k.shape[2])
    with jax.named_scope(GLUE_SCOPE):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = flash_attention(q, k, v, None, causal, bq, bk, interpret, window)
    with jax.named_scope(GLUE_SCOPE):
        return out.transpose(0, 2, 1, 3)


def mha_reference(q, k, v, scale=None, causal=False, window=None):
    """Plain-XLA oracle used by tests and as a fallback; k and v may hold
    fewer heads than q, ``window`` as in :func:`flash_attention`."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[1] // k.shape[1]
    if group != 1:
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((t, t), bool), -window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)

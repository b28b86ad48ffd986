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

Layouts. The kernels read and write whatever layout their caller holds, by
their ``BlockSpec`` index maps alone: an array is (rows, T, heads·D), a
kernel row is one head (or, at D < 128, the ``128 // D`` heads that fill one
128-lane tile, each kept to its own lanes by zeroed lanes: the contraction
stays exact and the MXU passes a head are those of D alone). Two callers:

- :func:`flash_attention_ntc`, the layout the transformer and the nn layers
  use: q (B, T, H·D), k and v (B, T, Hkv·D), as the projections make them,
  out, dq, dk and dv the same; where D is a multiple of 128, or divides 128
  with H a multiple of 128 // D and no grouping, no transpose runs round the
  kernels; any other shape transposes into the second layout;
- :func:`flash_attention` / :func:`flash_attention_lse` (and ring attention):
  (B, H, T, D), one head a row (the reshapes to and from (B·H, T, D) are
  free).

k and v may hold fewer heads than q (query head i reads K/V head
i // (H // Hkv): grouped-query attention). The log-sum-exp and the
backward's delta, rowsum(dO·O), travel lane-dense as (B·H, 1, T) f32;
delta is formed in the dq kernel, which hands it to the dkv kernel, and the
dkv kernel works in the transposed orientation (scores as (keys, queries)),
so that both broadcast along rows. ``causal`` applies a lower-triangular
mask, ``window`` (with ``causal``) keeps only the keys with i - j < window:
key blocks that lie wholly outside the band, on either side, are skipped
via pl.when and their fetches clamped away, in all three kernels. On the
chip a block of queries is a multiple of 128 or the whole sequence (the
log-sum-exp's block has it on its lanes). Falls back to interpreter mode
off-TPU so the same code path is unit-testable on the CPU mesh.

Names on the device: each kernel sits in a ``jax.named_scope`` of its own
name (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``, with ``_win`` under
a window); what XLA runs around them sits in ``attn_core``: on the direct
layouts nothing, on the transposed one the transposes between (B, T, H, D)
and (B, H, T, D). ``dl4j_flash_layout_total{layout}`` counts the traced
calls of :func:`flash_attention_ntc` by the layout taken: ``ntc``,
``ntc_pairs`` (heads packed into a tile) or ``transposed``.
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
LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _block_sizes(t: int, d: int, block_q: int, block_k: int):
    bq = min(block_q, t)
    bk = min(block_k, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _heads_a_block(d: int, h: int, hkv: int) -> Optional[int]:
    """How many heads one kernel row carries side by side on its lanes, for
    arrays of (rows, T, h·d) queries and (rows, T, hkv·d) keys: 1 where a
    head fills whole 128-lane tiles or is the array's whole lane dimension,
    ``128 // d`` where smaller heads fill one tile exactly and K/V pair as
    the queries do; None where no block can be cut from this layout."""
    if d % LANES == 0 or h == hkv == 1:
        return 1
    p = LANES // d
    if LANES % d == 0 and h == hkv and h % p == 0:
        return p
    return None


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _split_heads(x, d):
    """The heads of a (rows, n·d) block, one array each with the other
    heads' lanes zeroed: a product over all n·d lanes then reads one head's
    d, and the zeros add nothing. n = 1: the block itself."""
    n = x.shape[-1] // d
    if n == 1:
        return [x]
    head = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // d
    return [jnp.where(head == h, x, jnp.zeros_like(x)) for h in range(n)]


def _join_heads(parts, d):
    """One (rows, n·d) array whose head-h lanes are ``parts[h]``'s."""
    out = parts[-1]
    if len(parts) > 1:
        head = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1) // d
        for h in range(len(parts) - 2, -1, -1):
            out = jnp.where(head == h, parts[h], out)
    return out


def _live(qi, kj, bq, bk, causal, window):
    """Does query block ``qi`` see any key of key block ``kj``? (The dkv
    kernel asks the same of its grid, which streams the query blocks.)"""
    if not causal:
        return kj >= 0
    live = kj * bk <= qi * bq + bq - 1          # not wholly above the diagonal
    if window is not None:                      # not wholly left of the band
        live = live & (kj * bk + bk - 1 > qi * bq - window)
    return live


def _masked(s, qi, kj, bq, bk, causal, window, keys_on_rows=False):
    if not causal:
        return s
    q_axis, k_axis = (1, 0) if keys_on_rows else (0, 1)
    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_idx = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis)
    dead = k_idx > q_idx
    if window is not None:
        dead = dead | (q_idx - k_idx >= window)
    return jnp.where(dead, NEG_INF, s)


def _at(heads, row_of=lambda r: r):
    """Block coordinates (array row, lane block) of kernel row ``r`` in an
    array that holds ``heads`` kernel rows side by side on its lanes."""
    def place(r):
        row = row_of(r)
        return row // heads, row % heads
    return place


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, window, d):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq, width = q_ref.shape[1], q_ref.shape[2]
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
        k = k_ref[0]                                        # (bk, width)
        v = v_ref[0]
        corr, pv = [], []
        for h, q in enumerate(_split_heads(q_ref[0], d)):   # (bq, width)
            s = _dot(q, k, _NT) * scale                     # (bq, bk) f32
            # a row whose keys in this block are all masked adds exp(0)
            # terms while its running max is still NEG_INF; the first block
            # with a key it sees (the diagonal at the latest) wipes them
            s = _masked(s, qi, kj, bq, bk, causal, window)
            m = m_ref[h][:, 0]
            l = l_ref[h][:, 0]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            c = jnp.exp(m - m_new)
            l_new = l * c + jnp.sum(p, axis=-1)
            corr.append(jnp.broadcast_to(c[:, None], (bq, width)))
            # p @ v over every lane; only head h's lanes are kept below
            pv.append(_dot(p.astype(v.dtype), v, _NN))
            m_ref[h] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])
        acc_ref[...] = (acc_ref[...] * _join_heads(corr, d)
                        + _join_heads(pv, d))

    @pl.when(kj == nk - 1)
    def _finalize():
        inv = []
        for h in range(width // d):
            l = l_ref[h][:, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            inv.append(jnp.broadcast_to(l_safe[:, None], (bq, width)))
            # the block's log-sum-exp, written along the lanes
            lse_ref[h] = (m_ref[h][:, 0] + jnp.log(l_safe)).reshape(1, bq)
        o_ref[0] = (acc_ref[...] / _join_heads(inv, d)).astype(o_ref.dtype)


def _causal_kv_map(bq, bk, causal, window=None, group=1, heads=1):
    """KV-block index map. For causal grids, dead steps (key block entirely
    above the diagonal, or entirely left of the window's band) CLAMP to the
    nearest live key block: Pallas skips the HBM->VMEM fetch when successive
    steps reference the same block, so the part of the rectangular grid that
    pl.when skips stops costing bandwidth too. (Compute for dead steps is
    already skipped; without the clamp their DMAs still ran — measured ~2x
    wasted attention traffic at long T.) Kernel row ``bh`` of the queries
    reads kernel row ``bh // group`` of the keys, which sits at
    :func:`_at` of an array holding ``heads`` kernel rows on its lanes."""
    at = _at(heads, lambda bh: bh // group)
    if not causal:
        blk = lambda i, j: j
    elif window is None:
        blk = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    else:
        blk = lambda i, j: jnp.clip(
            j, jnp.maximum(i * bq - window + 1, 0) // bk,
            (i * bq + bq - 1) // bk)

    def kv_map(bh, i, j):
        row, lane = at(bh)
        return row, blk(i, j), lane
    return kv_map


def _suffix(window):
    """The window kernels carry names of their own, so that a trace tells
    them from the full causal ones."""
    return "" if window is None else "_win"


class _Grid:
    """What the three kernels need to know of their operands' layout: q
    (Nq, T, hq·D), k and v (Nkv, T, hkv·D) with ``heads = (hq, hkv)``."""

    def __init__(self, q, k, heads, block_q, block_k):
        hq, hkv = heads
        self.t, self.d = q.shape[1], q.shape[2] // hq
        self.p = _heads_a_block(self.d, hq, hkv)
        self.width = self.p * self.d                 # a block's lanes
        self.rows = q.shape[0] * hq // self.p        # kernel rows of q
        self.rows_kv = k.shape[0] * hkv // self.p
        self.group = self.rows // self.rows_kv
        self.bq, self.bk = _block_sizes(self.t, self.d, block_q, block_k)
        self.nq = self.t // self.bq
        self.q_at = _at(hq // self.p)
        self.kv_heads = hkv // self.p

    def q_spec(self):
        at = self.q_at
        return pl.BlockSpec((1, self.bq, self.width),
                            lambda bh, i, j: (at(bh)[0], i, at(bh)[1]))

    def row_spec(self):
        """lse and delta: (p, 1, bq) of the (Nq·hq, 1, T) f32 rows."""
        return pl.BlockSpec((self.p, 1, self.bq), lambda bh, i, j: (bh, 0, i))

    def kv_spec(self, causal, window):
        return pl.BlockSpec((1, self.bk, self.width), _causal_kv_map(
            self.bq, self.bk, causal, window, self.group, self.kv_heads))

    def lse_shape(self):
        return jax.ShapeDtypeStruct((self.rows * self.p, 1, self.t),
                                    jnp.float32)


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret, window, heads):
    g = _Grid(q, k, heads, block_q, block_k)
    grid = (g.rows, g.nq, g.t // g.bk)    # kv block = fastest dim (streamed)
    name = "flash_fwd" + _suffix(window)
    with jax.named_scope(name):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              window=window, d=g.d),
            grid=grid,
            in_specs=[g.q_spec(), g.kv_spec(causal, window),
                      g.kv_spec(causal, window)],
            out_specs=[g.q_spec(), g.row_spec()],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), g.lse_shape()],
            scratch_shapes=[pltpu.VMEM((g.bq, g.width), jnp.float32),
                            pltpu.VMEM((g.p, g.bq, 8), jnp.float32),
                            pltpu.VMEM((g.p, g.bq, 8), jnp.float32)],
            interpret=interpret,
            name=name,
        )(q, k, v)


# --------------------------------------------------------------- backward --

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *refs,
                   scale, causal, window, d, with_dlse):
    dlse_ref = refs[0] if with_dlse else None
    dq_ref, delta_ref, dq_acc_ref, lse_c_ref, delta_c_ref = refs[with_dlse:]
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        # once a block: lse as columns, and delta = rowsum(dO·O) of each
        # head (less the lse's cotangent), as columns for this kernel's
        # steps and along the lanes for dkv's
        prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
        for h, x in enumerate(_split_heads(prod, d)):
            delta = jnp.sum(x, axis=-1)
            if with_dlse:
                delta = delta - dlse_ref[h, 0]
            delta_ref[h] = delta.reshape(1, bq)
            delta_c_ref[h] = jnp.broadcast_to(delta[:, None], (bq, 8))
            lse_c_ref[h] = jnp.broadcast_to(lse_ref[h, 0][:, None], (bq, 8))

    live = _live(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        dos = _split_heads(do_ref[0], d)
        parts = []
        for h, q in enumerate(_split_heads(q_ref[0], d)):
            s = _dot(q, k, _NT) * scale
            s = _masked(s, qi, kj, bq, bk, causal, window)
            p = jnp.exp(s - lse_c_ref[h][:, 0][:, None])
            dp = _dot(dos[h], v, _NT)
            ds = (p * (dp - delta_c_ref[h][:, 0][:, None]) * scale
                  ).astype(k.dtype)
            parts.append(_dot(ds, k, _NN))       # head h's lanes are kept
        dq_acc_ref[...] += _join_heads(parts, d)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal,
                    window, group, d):
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
        # the transposed orientation: scores as (keys, queries), so that lse
        # and delta, rows of the queries, broadcast along the rows, and dK
        # and dV are plain products
        k = k_ref[0]
        v = v_ref[0]
        dos = _split_heads(do_ref[0], d)
        dk, dv = [], []
        for h, q in enumerate(_split_heads(q_ref[0], d)):
            st = _dot(k, q, _NT) * scale                        # (bk, bq)
            st = _masked(st, qi, kj, bq, bk, causal, window, keys_on_rows=True)
            pt = jnp.exp(st - lse_ref[h])
            # dos[h] and q hold head h's lanes only: so do these products
            dv.append(_dot(pt.astype(dos[h].dtype), dos[h], _NN))
            dpt = _dot(v, dos[h], _NT)
            dst = (pt * (dpt - delta_ref[h]) * scale).astype(q.dtype)
            dk.append(_dot(dst, q, _NN))
        dv_acc_ref[...] += sum(dv[1:], dv[0])
        dk_acc_ref[...] += sum(dk[1:], dk[0])

    @pl.when(step == n_steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_impl(scale, causal, block_q, block_k, interpret, window,
                    heads, q, k, v, out, g_out, lse, dlse=None):
    """Shared backward. ``dlse``, the lse-returning variant's cotangent of
    the log-sum-exp, is the ONLY difference it makes: ds = p·(dp − delta +
    dlse), so the dq kernel folds it into delta; dv is dlse-independent."""
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1] // heads[0])
    gr = _Grid(q, k, heads, block_q, block_k)
    bq, bk, nq, group = gr.bq, gr.bk, gr.nq, gr.group
    with_dlse = dlse is not None
    sfx = _suffix(window)
    with jax.named_scope("flash_bwd_dq" + sfx):
        dq, delta = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              window=window, d=gr.d, with_dlse=with_dlse),
            grid=(gr.rows, nq, gr.t // bk),   # kv block streamed (fastest dim)
            in_specs=[gr.q_spec(), gr.kv_spec(causal, window),
                      gr.kv_spec(causal, window), gr.q_spec(), gr.q_spec(),
                      gr.row_spec()] + [gr.row_spec()] * with_dlse,
            out_specs=[gr.q_spec(), gr.row_spec()],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       gr.lse_shape()],
            scratch_shapes=[pltpu.VMEM((bq, gr.width), jnp.float32),
                            pltpu.VMEM((gr.p, bq, 8), jnp.float32),
                            pltpu.VMEM((gr.p, bq, 8), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq" + sfx,
        )(q, k, v, g_out, out, lse, *([dlse] if with_dlse else []))

    # dkv grid streams q blocks (of each query head of the group in turn);
    # dead steps (q block entirely above the diagonal, or entirely beyond
    # the band) clamp to the nearest live q block — same no-refetch trick as
    # _causal_kv_map, mirrored
    if not causal:
        q_blk = lambda j, i: i
    elif window is None:
        q_blk = lambda j, i: jnp.maximum(i, (j * bk) // bq)
    else:
        q_blk = lambda j, i: jnp.clip(
            i, (j * bk) // bq,
            jnp.minimum((j * bk + bk + window - 2) // bq, nq - 1))

    def q_row(bh, j, i):           # kernel row and block of step i
        if group == 1:
            return bh, q_blk(j, i)
        return bh * group + i // nq, q_blk(j, i % nq)

    def q_map(bh, j, i):
        row, blk = q_row(bh, j, i)
        at = gr.q_at(row)
        return at[0], blk, at[1]

    def rows_map(bh, j, i):
        # lse/delta stream with the q block — clamped too, or dead causal
        # steps keep fetching them
        row, blk = q_row(bh, j, i)
        return row, 0, blk

    kv_at = _at(gr.kv_heads)
    kv_spec = pl.BlockSpec((1, bk, gr.width),
                           lambda bh, j, i: (kv_at(bh)[0], j, kv_at(bh)[1]))
    q_spec = pl.BlockSpec((1, bq, gr.width), q_map)
    rows_spec = pl.BlockSpec((gr.p, 1, bq), rows_map)
    with jax.named_scope("flash_bwd_dkv" + sfx):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              window=window, group=group, d=gr.d),
            # q block streamed (fastest dim), group * nq steps a key block
            grid=(gr.rows_kv, gr.t // bk, group * nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, rows_spec, rows_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, gr.width), jnp.float32),
                            pltpu.VMEM((bk, gr.width), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dkv" + sfx,
        )(q, k, v, g_out, lse, delta)
    return dq, dk, dv


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window,
               heads):
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1] // heads[0])
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    window, heads)
    # The two residuals only the kernel can rebuild carry names, so that a
    # jax.checkpoint policy can keep them (zoo/transformer.py's "save_attn"
    # does) and the backward pass need not run this kernel a second time.
    # Under no policy, or one that looks at no name, a name is the identity.
    # Both are the kernel's own arrays: the output in the caller's layout
    # (for the ntc callers the lane-dense (B, T, H·D) matrix their output
    # projection reads), the log-sum-exp as (B·H, 1, T) rows.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, lse, (q, k, v, out, lse)


# ------------------------------------------------------------- public api --

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window,
           heads):
    """The kernels on operands in their layout: q (Nq, T, hq·D), k and v
    (Nkv, T, hkv·D), ``heads = (hq, hkv)``; the output is q's shape."""
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                      window, heads)[0]


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                   window, heads):
    out, _, res = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                             interpret, window, heads)
    return out, res


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, window, heads,
                   res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(scale, causal, block_q, block_k, interpret, window,
                           heads, q, k, v, out, g, lse)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, interpret, heads):
    """:func:`_flash` that also returns the (Nq·hq, 1, T) log-sum-exp."""
    out, lse, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                             interpret, None, heads)
    return out, lse


def _flash_lse_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                       heads):
    out, lse, res = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                               interpret, None, heads)
    return (out, lse), res


def _flash_lse_vjp_bwd(scale, causal, block_q, block_k, interpret, heads, res,
                       g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd_impl(scale, causal, block_q, block_k, interpret, None,
                           heads, q, k, v, out, g_out, lse,
                           g_lse.astype(jnp.float32))


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _check(causal, window, h, hkv, k, v):
    if window is not None and not causal:
        raise ValueError("a window is a band under the diagonal: it needs "
                         "causal=True")
    if h % hkv or k.shape != v.shape:
        raise ValueError(f"{h} query heads cannot share {hkv} K/V heads")


def _rows(x):
    """(B, H, T, D) -> (B·H, T, D), one head a kernel row (free)."""
    with jax.named_scope(GLUE_SCOPE):
        return x.reshape(-1, *x.shape[2:])


def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Fused scaled-dot-product attention. q: (B, H, T, D), k/v:
    (B, Hkv, T, D) with H % Hkv == 0 → (B, H, T, D). ``window`` (needs
    ``causal``): query i sees keys j with 0 <= i - j < window."""
    _check(causal, window, q.shape[1], k.shape[1], k, v)
    out = _flash(_rows(q), _rows(k), _rows(v), scale, causal, block_q,
                 block_k, interpret, window, (1, 1))
    with jax.named_scope(GLUE_SCOPE):
        return out.reshape(q.shape)


def flash_attention_lse(q, k, v, scale: Optional[float] = None,
                        causal: bool = False, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp, ``lse`` (B, H, T) f32 — the quantity ring attention needs
    to merge partial attention results across sequence shards. The custom
    VJP propagates BOTH cotangents (dLSE folds into the delta term; see
    `_flash_bwd_impl`)."""
    _check(True, None, q.shape[1], k.shape[1], k, v)
    out, lse = _flash_lse(_rows(q), _rows(k), _rows(v), scale, causal,
                          block_q, block_k, interpret, (1, 1))
    with jax.named_scope(GLUE_SCOPE):
        return out.reshape(q.shape), lse.reshape(q.shape[:3])


def _tuned_blocks(b, h, t, d, dtype, causal, interpret, window=None,
                  group=1, layout="transposed") -> tuple:
    """Autotuned (block_q, block_k) for this attention shape — timed on the
    real chip once per (shape, window, group, layout), cached to disk
    (kernels/autotune.py). Off-TPU (or with tuning disabled) falls back to
    the measured v5e sweet spot (min(512,T), min(1024,T)) rather than
    re-timing. ``layout`` is :func:`flash_attention_ntc`'s: the direct
    layouts race the kernels on (B, T, H·D) operands, "transposed" on
    (B, H, T, D) ones."""
    import os

    if interpret or jax.default_backend() != "tpu" \
            or os.environ.get("DL4J_TPU_AUTOTUNE", "1") != "1":
        return _block_sizes(t, d, 512, 1024)
    from .autotune import autotune
    hkv = h // group
    if layout == "transposed":
        shapes, heads = ((b * h, t, d), (b * hkv, t, d)), (1, 1)
    else:
        shapes, heads = ((b, t, h * d), (b, t, hkv * d)), (h, hkv)

    def make_run(cand):
        bq, bk = cand
        if t % bq or t % bk:
            return None
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, shapes[0], dtype)
        kv = jax.random.normal(key, shapes[1], dtype)

        # Time the TRAIN path (fwd + both bwd passes): block-size choice is
        # dominated by the backward kernels, and a fwd-only race mispicks
        # (the flash4 tuner's 128×128 regression).
        def loss(q_, k_, v_):
            return jnp.sum(_flash(q_, k_, v_, None, causal, bq, bk, False,
                                  window, heads).astype(jnp.float32))

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
    # VMEM at d=64. A direct layout (PR 38) is a kernel of its own, with
    # a race of its own: "flash_ntc", "flash_ntc_pairs".
    kind = "flash5" if layout == "transposed" else "flash_" + layout
    key = f"{kind}:{chip}:{b}x{h}x{t}x{d}:{jnp.dtype(dtype).name}:{causal}"
    if window is not None or group != 1:       # a race of their own
        key += f":w{window}:g{group}"
    return autotune(
        key,
        [(512, 1024), (1024, 1024), (1024, 512), (512, 512),
         (256, 512), (256, 256), (128, 128)],
        make_run)


def ntc_layout(d: int, h: int, hkv: int) -> str:
    """The layout :func:`flash_attention_ntc` runs the kernels in for heads
    of ``d`` with ``h`` query and ``hkv`` K/V heads: "ntc" (a head a block),
    "ntc_pairs" (``128 // d`` heads a block) or "transposed"."""
    p = _heads_a_block(d, h, hkv)
    return "transposed" if p is None else "ntc" if p == 1 else "ntc_pairs"


def _layout_counter():
    from ..obs import get_registry
    return get_registry().counter(
        "dl4j_flash_layout_total",
        "traced flash_attention_ntc calls by the layout the kernels took: "
        "ntc, ntc_pairs (heads packed into a 128-lane tile) or transposed",
        labelnames=("layout",))


def flash_attention_ntc(q, k, v, n_heads, causal=False, interpret=None,
                        window=None):
    """Attention in the projections' own layout — the one the nn layers and
    the transformer use: q (B, T, H·D) with ``n_heads`` = H, k and v
    (B, T, Hkv·D) with H a multiple of Hkv (grouped-query attention) →
    (B, T, H·D). The kernels read and write these arrays directly where
    :func:`_heads_a_block` can cut them (D a multiple of 128; D dividing 128
    with H a multiple of 128 // D and Hkv == H), else the operands are
    transposed into :func:`flash_attention`'s layout and back. Block sizes
    are autotuned per (shape, window, group, layout) on the real chip."""
    b, t, width = q.shape
    d = width // n_heads
    hkv = k.shape[-1] // d
    _check(causal, window, n_heads, hkv, k, v)
    layout = ntc_layout(d, n_heads, hkv)
    _layout_counter().inc(layout=layout)
    bq, bk = _tuned_blocks(b, n_heads, t, d, q.dtype, causal, interpret,
                           window, n_heads // hkv, layout)
    if layout != "transposed":
        return _flash(q, k, v, None, causal, bq, bk, interpret, window,
                      (n_heads, hkv))
    with jax.named_scope(GLUE_SCOPE):
        q, k, v = (x.reshape(b, t, -1, d).transpose(0, 2, 1, 3)
                   for x in (q, k, v))
    out = flash_attention(q, k, v, None, causal, bq, bk, interpret, window)
    with jax.named_scope(GLUE_SCOPE):
        return out.transpose(0, 2, 1, 3).reshape(b, t, width)


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

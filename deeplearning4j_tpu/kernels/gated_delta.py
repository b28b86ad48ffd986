"""The gated delta rule — chunk-fused Pallas TPU kernels, forward and backward.

The rule of a gated DeltaNet layer (arXiv:2412.06464): per value head, from
a zero (dk, dv) state, ``S <- exp(g_t) S; S <- S + k_t beta_t (v_t -
S^T k_t)^T; o_t = S^T q_t``, q and k L2-normed per head (q then over
sqrt(dk)), value head h reading key head h // (Hv / Hk).

Both kernels walk the chunks of 64 positions IN ORDER (the backward in
reverse) with every value head's float32 state (dk, dv) in VMEM scratch,
and do all of a chunk's work there, the WY form of the paper's §3:

- the cumulative log decay of the chunk, the decays as exp of masked
  differences of it (never exp(G) and exp(-G) apart, which overflow);
- ``a = tril(beta kk^T * decay, -1)`` and the inverse of the unit lower
  triangular ``I + a`` in float32 (:func:`_unit_lower_inverse`), a key
  head's value heads as one block-diagonal problem (:func:`_inverses`): a
  chain of small dependent products, most of the kernels' time;
- the corrected values and keys ``u = inv (v beta)``, ``w = inv (k beta
  e^cum)``, the chunk's new values ``u - w S``, the output ``e^cum q S +
  (q k^T * decay) new`` and the state ``e^last S + k_end^T new``.

The forward writes the output and each chunk's STARTING state, (B, N, Hv,
dk, dv) float32, which the backward reads to rebuild the chunk (norms,
decays, inverse, u, w, new) before it forms the gradients, carrying dS
backwards in VMEM. Nothing of a chunk's (64, 64) or (64, d) intermediates
goes through HBM.

Layouts. q, k and v are read straight from the caller's (B, T, 2 Hk dk + Hv
dv) array (the convolution's output, [q | k | v] on the lanes) and dq, dk,
dv are written into one array of that shape; g and beta (and their
gradients) are (B, T, Hv) float32; the output is (B, T, Hv dv) float32. A
grid step is one chunk of one sequence and all its heads: a loop over key
heads, and within one over the key head's value heads, slices the heads'
lanes (on the chip dk and dv are multiples of 128). A sequence that is no
multiple of 64 is padded with g = 0 and beta = 0, which decay nothing and
write nothing.

Arithmetic: the products take their operands in the dtype q, k and v come
in (bf16 in training, one MXU pass as XLA's default precision gives the
float32 products of the rule it replaced) and accumulate in float32; the
state, the cumulative sums (a product at full float32 precision), every
exp, the masks, the norms and the inverse are float32, the inverse as
accurate as a float32 forward substitution (:func:`_unit_lower_inverse`).

Set-up: each kernel is the lowering of a primitive of its own, emitted once
a module as a private function (``inline=False``) that every layer, the
recomputation under remat and the backward call, so its body is traced and
lowered once however many layers run the rule. A private function's body
starts from no scope, so the lowering opens the scopes the caller names
(``gated_delta_rule``'s ``scopes``), and every kernel event carries them
wherever it is called from. Off the chip the kernels run in interpreter
mode.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.extend import core as jex_core
from jax.interpreters import mlir

from ._common import interpret_default as _interpret_default
from ._common import pltpu

#: positions a chunk takes
CHUNK = 64
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_EPS = 1e-6
_ONE_PASS = jnp.bfloat16        # operands of a product that one MXU pass takes
_VMEM_LIMIT = 64 * 1024 * 1024
f32 = jnp.float32


def _dot(a, b, dims, dtype):
    """A product with ``dtype`` operands accumulated in float32."""
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           preferred_element_type=f32)


def _exact(a, b, dims=_NN):
    """A float32 product at full float32 precision."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=f32)


def _three_pass(a, b):
    """A float32 product to about 2^-16 of its size: both operands split
    into bf16 high and low parts, the low parts' product left out."""
    (ah, al), (bh, bl) = (
        (x.astype(_ONE_PASS), (x - x.astype(_ONE_PASS).astype(f32))
         .astype(_ONE_PASS)) for x in (a, b))
    return _dot(ah, bh, _NN, _ONE_PASS) + (_dot(ah, bl, _NN, _ONE_PASS)
                                           + _dot(al, bh, _NN, _ONE_PASS))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _lower(n, strict=False):
    """(n, n) mask of i >= j (i > j where ``strict``)."""
    i, j = _iota((n, n), 0), _iota((n, n), 1)
    return i > j if strict else i >= j


def _column(x, h):
    """Column ``h`` (traced) of x (C, H) as (C, 1)."""
    return jnp.sum(jnp.where(_iota(x.shape, 1) == h, x, 0.0), axis=1,
                   keepdims=True)


def _as_row(col):
    """(C, 1) -> (1, C), by the diagonal: no transpose."""
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _as_col(row):
    """(1, C) -> (C, 1), by the diagonal."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _lanes(start, size, align):
    return pl.ds(pl.multiple_of(start, align), size)


def _unit_lower_inverse(a, size):
    """(I + a)^-1 in float32 for a strictly lower triangular float32 ``a``
    that is block diagonal in (size, size) blocks, ``size`` a power of two.

    A first inverse by doubling the diagonal blocks already inverted (with
    X the inverse of the (s, s) blocks, that of the (2s, 2s) blocks is X - X
    a' X, a' the lower-left (s, s) quarter of each (2s, 2s) block of a: the
    recursive form of forward substitution over the rows), its products one
    bf16 pass each; then two Newton steps, X <- X + X E with the residual E
    = I - (I + a) X formed at full float32 precision: each squares the
    relative error (about 2^-7 after the doubling, 2^-24 after both steps),
    and X E, a product of that error, needs one pass. 2 log2(size) - 2 bf16
    products and two float32 ones, where doubling at float32 takes 2
    log2(size) - 2 float32 products of six passes each."""
    n = a.shape[0]
    i, j = _iota((n, n), 0), _iota((n, n), 1)
    eye = jnp.where(i == j, 1.0, 0.0)
    # the (2, 2) blocks' inverse, I - a' (X = I before it), needs no product
    x = eye - jnp.where(i // 2 == j // 2, a, 0.0)
    s = 2
    while s < size:
        quarter = (i // (2 * s) == j // (2 * s)) & (i // s != j // s)
        x = x - _dot(x, _dot(jnp.where(quarter, a, 0.0), x, _NN, _ONE_PASS),
                     _NN, _ONE_PASS)
        s *= 2
    x = x + _dot(x, eye - x - _three_pass(a, x), _NN, _ONE_PASS)
    return x + _dot(x, eye - x - _exact(a, x), _NN, _ONE_PASS)


def _unit_norm(x):
    """x / |x| per row, and the 1 / |x| it took."""
    r = lax.rsqrt(_rowsum(x * x) + _EPS)
    return x * r, r


def _inverses(kk, heads):
    """(I + a)^-1 of each of a key head's value heads in one chunk, from
    the key head's kk (C, C) and each value head's (cumulative log decay,
    beta), (C, 1) each: the heads as ONE block-diagonal problem of
    len(heads) C rows, so that each product of :func:`_unit_lower_inverse`
    is one MXU pass for all of them (its products are a chain of small
    dependent ones, and their latency, not their work, is the cost)."""
    n, m = kk.shape[0], len(heads) * kk.shape[0]
    cum = jnp.concatenate([c for c, _ in heads], axis=0)         # (m, 1)
    beta = jnp.concatenate([b for _, b in heads], axis=0)
    kks = jnp.concatenate([jnp.concatenate([kk] * len(heads), axis=1)]
                          * len(heads), axis=0)
    i, j = _iota((m, m), 0), _iota((m, m), 1)
    strict = (i > j) & (i // n == j // n)
    a = jnp.where(strict, beta * kks * jnp.exp(
        jnp.where(strict, cum - _as_row(cum), -jnp.inf)), 0.0)
    inv = _unit_lower_inverse(a, n)
    return [inv[r * n:(r + 1) * n, r * n:(r + 1) * n]
            for r in range(len(heads))]


def _packs(heads, n):
    """Value heads of a key head that share one problem of
    :func:`_inverses`: as many as fill the MXU's 128 rows."""
    size = max(1, min(heads, 128 // n))
    while heads % size:
        size -= 1
    return size


class _Chunk:
    """What the forward and the backward both form of one value head in one
    chunk, from the key head's normed q and k (C, dk) and their products
    kk and qk (C, C), the head's cumulative log decay and beta (C, 1), its
    values (C, dv) and its (I + a)^-1 (:func:`_inverses`)."""

    def __init__(self, kn, kk, qk, cum, beta, v, inv, dtype):
        n = cum.shape[0]
        lower = _lower(n)
        self.decay = jnp.exp(jnp.where(lower, cum - _as_row(cum), -jnp.inf))
        self.inv = inv
        self.ecum = jnp.exp(cum)
        self.last = cum[n - 1:, :]                          # (1, 1)
        self.ecl = jnp.exp(self.last - cum)                 # exp(last - cum)
        self.vb = v * beta
        self.xk = kn * (beta * self.ecum)
        self.u = _dot(self.inv, self.vb, _NN, dtype)
        self.w = _dot(self.inv, self.xk, _NN, dtype)
        self.p = qk * self.decay
        self.kend = kn * self.ecl


def _qk_of(x_ref, j, hk, dk):
    """Key head j's q and k (C, dk) float32 from the [q | k | v] block."""
    q = x_ref[0, :, _lanes(j * dk, dk, dk)].astype(f32)
    k = x_ref[0, :, _lanes(hk * dk + j * dk, dk, dk)].astype(f32)
    return q, k


def _v_lanes(h, hk, dk, dv):
    return _lanes(2 * hk * dk + h * dv, dv, math.gcd(2 * hk * dk, dv))


def _value_heads(j, hk, hv, cums, betas, kk, body, carry):
    """``body(h, cum, beta, inv, carry)`` over key head j's value heads h,
    unrolled, with their cumulative log decays, betas (columns of ``cums``
    and ``betas``) and inverses, formed a pack at a time (:func:`_packs`)."""
    per = hv // hk
    size = _packs(per, kk.shape[0])
    for first in range(0, per, size):
        heads = [j * per + first + r for r in range(size)]
        cols = [(_column(cums, h), _column(betas, h)) for h in heads]
        for h, (cum, beta), inv in zip(heads, cols, _inverses(kk, cols)):
            carry = body(h, cum, beta, inv, carry)
    return carry


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, st_ref, s_ref, *, hk, dk, dv):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        s_ref[...] = jnp.zeros_like(s_ref)

    dtype = x_ref.dtype
    n, hv = g_ref.shape[1], g_ref.shape[2]
    cums = _exact(jnp.where(_lower(n), 1.0, 0.0), g_ref[0])     # (C, Hv)
    betas = b_ref[0]
    scale = 1.0 / math.sqrt(dk)

    def key_head(j, carry):
        q, k = _qk_of(x_ref, j, hk, dk)
        qn, kn = _unit_norm(q)[0] * scale, _unit_norm(k)[0]
        kk, qk = _dot(kn, kn, _NT, dtype), _dot(qn, kn, _NT, dtype)

        def value_head(h, cum, beta, inv, carry):
            lanes = _v_lanes(h, hk, dk, dv)
            c = _Chunk(kn, kk, qk, cum, beta, x_ref[0, :, lanes].astype(f32),
                       inv, dtype)
            s = s_ref[h]
            st_ref[0, 0, h] = s
            new = c.u - _dot(c.w, s, _NN, dtype)
            o_ref[0, :, _lanes(h * dv, dv, dv)] = (
                c.ecum * _dot(qn, s, _NN, dtype) + _dot(c.p, new, _NN, dtype))
            s_ref[h] = jnp.exp(c.last) * s + _dot(c.kend, new, _TN, dtype)
            return carry

        return _value_heads(j, hk, hv, cums, betas, kk, value_head, carry)

    lax.fori_loop(0, hk, key_head, 0)


def _bwd_kernel(x_ref, g_ref, b_ref, st_ref, do_ref, dx_ref, dg_ref, db_ref,
                ds_ref, *, hk, dk, dv):
    @pl.when(pl.program_id(1) == 0)
    def _zero():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dtype = x_ref.dtype
    n, hv = g_ref.shape[1], g_ref.shape[2]
    lower = jnp.where(_lower(n), 1.0, 0.0)
    cums = _exact(lower, g_ref[0])
    betas = b_ref[0]
    scale = 1.0 / math.sqrt(dk)
    lane = _iota((n, hv), 1)
    at_last = _iota((n, 1), 0) == n - 1

    def key_head(j, sums):
        q, k = _qk_of(x_ref, j, hk, dk)
        qu, rq = _unit_norm(q)
        kn, rk = _unit_norm(k)
        qn = qu * scale
        kk, qk = _dot(kn, kn, _NT, dtype), _dot(qn, kn, _NT, dtype)

        def value_head(h, cum, beta, inv, carry):
            dqn, dkn, dcums, dbetas = carry
            lanes = _v_lanes(h, hk, dk, dv)
            v = x_ref[0, :, lanes].astype(f32)
            c = _Chunk(kn, kk, qk, cum, beta, v, inv, dtype)
            s, ds = st_ref[0, 0, h], ds_ref[h]
            do = do_ref[0, :, _lanes(h * dv, dv, dv)]
            el = jnp.exp(c.last)
            new = c.u - _dot(c.w, s, _NN, dtype)
            # through the output and the state after the chunk
            dnew = _dot(c.p, do, _TN, dtype) + _dot(c.kend, ds, _NN, dtype)
            ds_ref[h] = el * ds + _dot(qn * c.ecum, do, _TN, dtype) \
                - _dot(c.w, dnew, _TN, dtype)
            dp = _dot(do, new, _NT, dtype)
            dqk = dp * c.decay
            dqn = dqn + c.ecum * _dot(do, s, _NT, dtype) \
                + _dot(dqk, kn, _NN, dtype)
            dkend = _dot(new, ds, _NT, dtype)
            dkn = dkn + _dot(dqk, qn, _TN, dtype) + dkend * c.ecl
            t = _rowsum(dkend * c.kend)
            dlast = jnp.sum(t, keepdims=True) \
                + el * jnp.sum(ds * s, keepdims=True)
            dcum = c.ecum * _rowsum(do * _dot(qn, s, _NN, dtype)) - t
            # through u = inv (v beta) and w = inv (k beta e^cum)
            dw = -_dot(dnew, s, _NT, dtype)
            dvb = _dot(c.inv, dnew, _TN, dtype)
            dxk = _dot(c.inv, dw, _TN, dtype)
            dx_ref[0, :, lanes] = (dvb * beta).astype(dx_ref.dtype)
            s2 = _rowsum(dxk * kn)
            dbeta = _rowsum(v * dvb) + s2 * c.ecum
            dcum = dcum + s2 * beta * c.ecum
            dkn = dkn + dxk * (beta * c.ecum)
            # through the inverse: d a = -inv^T d inv inv^T, masked
            da = jnp.where(_lower(n, strict=True),
                           -(_dot(dvb, c.u, _NT, dtype)
                             + _dot(dxk, c.w, _NT, dtype)), 0.0)
            dkk = da * beta * c.decay
            dbeta = dbeta + _rowsum(da * kk * c.decay)
            dkn = dkn + _dot(dkk, kn, _NN, dtype) + _dot(dkk, kn, _TN, dtype)
            # through the decays, exp(cum_i - cum_j)
            t2 = (dp * qk + da * beta * kk) * c.decay
            dcum = dcum + _rowsum(t2) - _as_col(jnp.sum(t2, axis=0,
                                                        keepdims=True))
            dcum = dcum + jnp.where(at_last, dlast, 0.0)
            dcums = jnp.where(lane == h, dcum, dcums)
            dbetas = jnp.where(lane == h, dbeta, dbetas)
            return dqn, dkn, dcums, dbetas

        zeros = jnp.zeros((n, dk), f32)
        dqn, dkn, dcums, dbetas = _value_heads(
            j, hk, hv, cums, betas, kk, value_head, (zeros, zeros, *sums))
        # through the norms
        dq = scale * rq * (dqn - qu * _rowsum(qu * dqn))
        dk_ = rk * (dkn - kn * _rowsum(kn * dkn))
        dx_ref[0, :, _lanes(j * dk, dk, dk)] = dq.astype(dx_ref.dtype)
        dx_ref[0, :, _lanes(hk * dk + j * dk, dk, dk)] = \
            dk_.astype(dx_ref.dtype)
        return dcums, dbetas

    zeros = jnp.zeros((n, hv), f32)
    dcums, dbetas = lax.fori_loop(0, hk, key_head, (zeros, zeros))
    # cum = L g: dg = L^T dcum
    dg_ref[0] = _exact(lower, dcums, _TN)
    db_ref[0] = dbetas


@contextlib.contextmanager
def _scoped(names):
    """``jax.named_scope`` of each of ``names``, outermost first."""
    with contextlib.ExitStack() as stack:
        for name in names:
            stack.enter_context(jax.named_scope(name))
        yield


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward(x, g, beta, *, hk, dk, dv, interpret, scopes):
    """(o (B, T, Hv dv) f32, states (B, N, Hv, dk, dv) f32)."""
    b, t, width = x.shape
    hv, n = g.shape[-1], t // CHUNK

    def rows(w):
        return pl.BlockSpec((1, CHUNK, w), lambda i, c: (i, c, 0))

    with _scoped(scopes):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, hk=hk, dk=dk, dv=dv),
            grid=(b, n),
            in_specs=[rows(width), rows(hv), rows(hv)],
            out_specs=[rows(hv * dv), pl.BlockSpec(
                (1, 1, hv, dk, dv), lambda i, c: (i, c, 0, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, t, hv * dv), f32),
                       jax.ShapeDtypeStruct((b, n, hv, dk, dv), f32)],
            scratch_shapes=[pltpu.VMEM((hv, dk, dv), f32)],
            compiler_params=_params(), interpret=interpret, name="gdn_fwd",
        )(x, g, beta)


def _backward(x, g, beta, states, do, *, hk, dk, dv, interpret, scopes):
    """(dx like x, dg, dbeta (B, T, Hv) f32), the chunks in reverse."""
    b, t, width = x.shape
    hv, n = g.shape[-1], t // CHUNK

    def rows(w):
        return pl.BlockSpec((1, CHUNK, w), lambda i, c: (i, n - 1 - c, 0))

    with _scoped(scopes):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, hk=hk, dk=dk, dv=dv),
            grid=(b, n),
            in_specs=[rows(width), rows(hv), rows(hv), pl.BlockSpec(
                (1, 1, hv, dk, dv), lambda i, c: (i, n - 1 - c, 0, 0, 0)),
                rows(hv * dv)],
            out_specs=[rows(width), rows(hv), rows(hv)],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(g.shape, f32),
                       jax.ShapeDtypeStruct(g.shape, f32)],
            scratch_shapes=[pltpu.VMEM((hv, dk, dv), f32)],
            compiler_params=_params(), interpret=interpret, name="gdn_bwd",
        )(x, g, beta, states, do)


def _primitive(name, fn, abstract):
    """A primitive lowered to ``fn`` once a module (see the docstring)."""
    p = jex_core.Primitive(name)
    p.multiple_results = True
    p.def_impl(jax.jit(fn, static_argnames=("hk", "dk", "dv", "interpret",
                                            "scopes")))
    p.def_abstract_eval(abstract)
    mlir.register_lowering(p, mlir.lower_fun(fn, multiple_results=True),
                           inline=False)
    return p


def _fwd_shapes(x, g, beta, *, hk, dk, dv, **_):
    b, t, _ = x.shape
    hv = g.shape[-1]
    return (jax.core.ShapedArray((b, t, hv * dv), f32),
            jax.core.ShapedArray((b, t // CHUNK, hv, dk, dv), f32))


def _bwd_shapes(x, g, beta, states, do, **_):
    return (jax.core.ShapedArray(x.shape, x.dtype),
            jax.core.ShapedArray(g.shape, f32),
            jax.core.ShapedArray(g.shape, f32))


_fwd_p = _primitive("gdn_fwd", _forward, _fwd_shapes)
_bwd_p = _primitive("gdn_bwd", _backward, _bwd_shapes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _rule(x, g, beta, hk, dk, dv, interpret, scopes):
    return _fwd_p.bind(x, g, beta, hk=hk, dk=dk, dv=dv, interpret=interpret,
                       scopes=scopes)[0]


def _rule_fwd(x, g, beta, hk, dk, dv, interpret, scopes):
    o, states = _fwd_p.bind(x, g, beta, hk=hk, dk=dk, dv=dv,
                            interpret=interpret, scopes=scopes)
    # Named, as the flash kernels name theirs, so that a jax.checkpoint
    # policy can keep them (zoo/transformer.py's "save_attn" does) and the
    # backward pass need not run the forward kernel a second time: the
    # output, which the layer's gated norm reads back, and the chunk-start
    # states, which only this kernel makes.
    o = checkpoint_name(o, "attn_out")
    states = checkpoint_name(states, "gdn_states")
    return o, (x, g, beta, states)


def _rule_bwd(hk, dk, dv, interpret, scopes, res, do):
    return tuple(_bwd_p.bind(*res, do, hk=hk, dk=dk, dv=dv,
                             interpret=interpret, scopes=scopes))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(qkv, g, beta, key_heads: int, key_size: int,
                     value_size: int, scopes: tuple[str, ...] = (),
                     interpret=None):
    """The gated delta rule over whole sequences from zero states.

    qkv (B, T, 2 Hk dk + Hv dv): [q | k | v] on the last axis, head-major
    (as a gated DeltaNet's convolution makes them), in the compute dtype; g
    (log decay, <= 0) and beta (B, T, Hv), float32; Hv a multiple of
    ``key_heads``. ``scopes``: the ``jax.named_scope``s, outermost first,
    that the kernels' events carry (the caller's own: see the module
    docstring). Returns o (B, T, Hv dv) float32; differentiable in all three
    operands."""
    b, t, _ = qkv.shape
    pad = (-t) % CHUNK
    if interpret is None:
        interpret = _interpret_default()
    g, beta = g.astype(f32), beta.astype(f32)
    if pad:
        qkv, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                        for a in (qkv, g, beta))
    o = _rule(qkv, g, beta, key_heads, key_size, value_size, interpret,
              tuple(scopes))
    return o[:, :t] if pad else o

"""Fused batchnorm-activation — pallas TPU kernel (SURVEY §7 R2 kernel).

Reference counterpart: libnd4j's fused ``batchnorm`` + activation epilogue
(cuDNN ``cudnnBatchNormalizationForwardInference`` followed by the fused
activation the reference's conv helpers request). At inference the whole
BN collapses to a per-channel affine y = act(x * scale + shift) with

    scale = gamma / sqrt(var + eps),   shift = beta - mean * scale

precomputed once; the kernel then makes ONE bandwidth-bound pass over x:
rows stream through VMEM in blocks, the (1, C) scale/shift vectors stay
resident, and the activation is applied in-register before the row block
is written back — no (B·H·W, C) intermediate ever round-trips to HBM.

Backward (rarely needed at inference, but required for frozen-BN
fine-tuning) is recompute-based via the jnp reference, like the other
kernels in this package.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ._common import interpret_default

_VMEM_BUDGET = 8 << 20  # row blocks stay comfortably inside VMEM


def plan_blocks(n: int, c: int, itemsize: int, buffers: int = 2):
    """Row-block size for an (N, C) pass, or None when no clean block fits
    VMEM (callers fall back to the XLA path). `buffers` is how many
    (block, C) tensors the kernel keeps resident per grid step (in + out =
    2 for the forward passes; the backward dx pass streams x, g AND dx =
    3). A non-divisible N is only acceptable when the WHOLE array is one
    small block."""
    for cand in (1024, 512, 256, 128, 8):
        if n % cand == 0 \
                and buffers * cand * c * max(itemsize, 4) <= _VMEM_BUDGET:
            return cand
    if buffers * n * c * max(itemsize, 4) <= _VMEM_BUDGET:
        return n
    return None

_ACTS = {
    "identity": lambda x: x,
    "relu": jax.nn.relu,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "swish": jax.nn.swish,
    "leakyrelu": lambda x: jax.nn.leaky_relu(x, 0.01),
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "softplus": jax.nn.softplus,
}


def supported_activation(name) -> bool:
    return isinstance(name, str) and name in _ACTS


_interpret_default = interpret_default


def bn_act_reference(x2d, scale, shift, activation: str):
    """jnp oracle AND recompute target: act(x * scale + shift), (N, C)."""
    return _ACTS[activation](x2d * scale[None, :] + shift[None, :])


def _kernel(x_ref, scale_ref, shift_ref, o_ref, *, activation):
    y = (x_ref[...].astype(jnp.float32) * scale_ref[...]
         + shift_ref[...])
    o_ref[...] = _ACTS[activation](y).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_bn_act(x2d, scale, shift, activation: str = "identity",
                 interpret=None):
    """(N, C) rows × per-channel affine + activation, one HBM pass."""
    out, _ = _fwd(x2d, scale, shift, activation, interpret)
    return out


def _fwd(x2d, scale, shift, activation, interpret):
    res = (x2d, scale, shift)
    if interpret is None:
        interpret = _interpret_default()
    n, c = x2d.shape
    bn = plan_blocks(n, c, x2d.dtype.itemsize)
    if bn is None:                       # no VMEM-safe blocking: XLA path
        return bn_act_reference(x2d, scale, shift, activation
                                ).astype(x2d.dtype), res
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), x2d.dtype),
        interpret=interpret,
    )(x2d, scale.reshape(1, c).astype(jnp.float32),
      shift.reshape(1, c).astype(jnp.float32))
    return out, res


def _bwd(activation, interpret, res, g):
    x2d, scale, shift = res
    # cast like the primal does: without it the recompute emits f32 for
    # bf16 x (promotion with the f32 scale/shift) and the VJP then
    # rejects the incoming bf16 cotangent
    _, vjp_fn = jax.vjp(
        lambda x, sc, sh: bn_act_reference(x, sc, sh, activation
                                           ).astype(x2d.dtype),
        x2d, scale, shift)
    return vjp_fn(g)


fused_bn_act.defvjp(_fwd, _bwd)


# ------------------------------------------------------------------ training
# Training-path BN+activation (the cuDNN BatchNormalizationForwardTraining /
# Backward regime, reference org.deeplearning4j.nn.layers.normalization.
# BatchNormalization via its cuDNN helper): batch statistics computed from x
# with the one-pass shifted-moment trick, then ONE normalize+activation
# sweep; the custom VJP implements the standard BN backward (two fused
# sweeps: reductions, then dx) instead of letting autodiff save the
# pre-activation tensor as a residual.

_ACT_GRADS = {
    # act'(z) computed straight from the PRE-activation z, so the backward
    # never needs the activation output as a residual
    "identity": lambda z: jnp.ones_like(z),
    "relu": lambda z: (z > 0).astype(z.dtype),
    "relu6": lambda z: ((z > 0) & (z < 6.0)).astype(z.dtype),
    "sigmoid": lambda z: jax.nn.sigmoid(z) * (1 - jax.nn.sigmoid(z)),
    "tanh": lambda z: 1.0 - jnp.square(jnp.tanh(z)),
    "leakyrelu": lambda z: jnp.where(z > 0, 1.0, 0.01).astype(z.dtype),
    "softplus": lambda z: jax.nn.sigmoid(z),
}


def supported_train_activation(name) -> bool:
    return isinstance(name, str) and name in _ACT_GRADS


def _train_stats(x2d, center):
    """One-pass shifted batch moments (same numerics as the jnp train path):
    mean = c + E[x-c], var = E[(x-c)^2] - E[x-c]^2, clamped at 0."""
    n, c = x2d.shape
    xf = x2d.astype(jnp.float32)
    d = xf - center[None, :]
    s1 = jnp.sum(d, axis=0)
    s2 = jnp.sum(d * d, axis=0)
    mean = center + s1 / n
    var = jnp.maximum(s2 / n - jnp.square(s1 / n), 0.0)
    return mean, var


def _stats_kernel(x_ref, c_ref, s_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    d = x_ref[...].astype(jnp.float32) - c_ref[...]
    s_ref[0:1, :] += jnp.sum(d, axis=0, keepdims=True)
    s_ref[1:2, :] += jnp.sum(d * d, axis=0, keepdims=True)


def _bn_bwd_reduce_kernel(x_ref, g_ref, scale_ref, shift_ref, minv_ref,
                          r_ref, *, activation):
    """Accumulate dbeta = sum(dz) and dgamma = sum(dz * xhat) over row
    blocks; z and xhat are recomputed in-register from x."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        r_ref[...] = jnp.zeros_like(r_ref)

    xf = x_ref[...].astype(jnp.float32)
    z = xf * scale_ref[...] + shift_ref[...]
    dz = g_ref[...].astype(jnp.float32) * _ACT_GRADS[activation](z)
    # xhat = (x - mean) * inv = (z - beta_hat) / gamma ... recompute from
    # x directly with (mean, inv) folded into minv rows: [mean; inv]
    xhat = (xf - minv_ref[0:1, :]) * minv_ref[1:2, :]
    r_ref[0:1, :] += jnp.sum(dz, axis=0, keepdims=True)
    r_ref[1:2, :] += jnp.sum(dz * xhat, axis=0, keepdims=True)


def _bn_bwd_dx_kernel(x_ref, g_ref, scale_ref, shift_ref, minv_ref,
                      corr_ref, dx_ref, *, activation):
    """dx = scale * (dz - dbeta/N - xhat * dgamma/N); corr rows hold
    [dbeta/N ; dgamma/N]."""
    xf = x_ref[...].astype(jnp.float32)
    z = xf * scale_ref[...] + shift_ref[...]
    dz = g_ref[...].astype(jnp.float32) * _ACT_GRADS[activation](z)
    xhat = (xf - minv_ref[0:1, :]) * minv_ref[1:2, :]
    dx = scale_ref[...] * (dz - corr_ref[0:1, :] - xhat * corr_ref[1:2, :])
    dx_ref[...] = dx.astype(dx_ref.dtype)


def bn_act_train_reference(x2d, gamma, beta, center, eps, activation):
    """jnp oracle: batch-stats BN + activation, one-pass shifted moments."""
    mean, var = _train_stats(x2d, center)
    inv = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    y = _ACTS[activation](x2d.astype(jnp.float32) * scale + shift)
    return y.astype(x2d.dtype), mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_bn_act_train(x2d, gamma, beta, center, eps: float = 1e-5,
                       activation: str = "identity", interpret=None):
    """(N, C) training BN: batch stats -> act(x*scale+shift).

    Returns ``(y, mean, var)`` — mean/var are the BATCH statistics (f32),
    for the caller's running-average update; their output cotangents are
    treated as zero (they feed stop-gradient EMA state, never the loss).
    ``center`` is the f32 per-channel shift for the one-pass moments
    (callers pass the running mean; in exact arithmetic the moments are
    independent of it, so its cotangent is zero).
    """
    (y, mean, var), _ = _train_fwd(x2d, gamma, beta, center, eps, activation,
                                   interpret)
    # enforce the VJP contract in the primal too: the stats outputs are
    # EMA-only, so differentiating through them must not silently drop terms
    return y, lax.stop_gradient(mean), lax.stop_gradient(var)


def _train_fwd(x2d, gamma, beta, center, eps, activation, interpret):
    n, c = x2d.shape
    if interpret is None:
        interpret = _interpret_default()
    bn = plan_blocks(n, c, x2d.dtype.itemsize)
    if bn is None:
        y, mean, var = bn_act_train_reference(x2d, gamma, beta, center, eps,
                                              activation)
        inv = lax.rsqrt(var + eps)
        return (y, mean, var), (x2d, gamma, beta, mean, inv)
    s = pl.pallas_call(
        _stats_kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((2, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, c), jnp.float32),
        interpret=interpret,
    )(x2d, center.reshape(1, c).astype(jnp.float32))
    mean = center + s[0] / n
    var = jnp.maximum(s[1] / n - jnp.square(s[0] / n), 0.0)
    inv = lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    y = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), x2d.dtype),
        interpret=interpret,
    )(x2d, scale.reshape(1, c), shift.reshape(1, c))
    return (y, mean, var), (x2d, gamma, beta, mean, inv)


def _train_bwd(eps, activation, interpret, res, cotangents):
    g = cotangents[0]  # (dy, dmean, dvar) — stats cotangents are EMA-only
    x2d, gamma, beta, mean, inv = res
    dcenter = jnp.zeros_like(mean)
    n, c = x2d.shape
    if interpret is None:
        interpret = _interpret_default()
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    # 3 resident row blocks in the dx pass (x, g, dx)
    bn = plan_blocks(n, c, x2d.dtype.itemsize, buffers=3)
    if bn is None:
        xf = x2d.astype(jnp.float32)
        z = xf * scale[None, :] + shift[None, :]
        dz = g.astype(jnp.float32) * _ACT_GRADS[activation](z)
        xhat = (xf - mean[None, :]) * inv[None, :]
        dbeta = jnp.sum(dz, axis=0)
        dgamma = jnp.sum(dz * xhat, axis=0)
        dx = scale[None, :] * (dz - dbeta[None, :] / n
                               - xhat * dgamma[None, :] / n)
        return (dx.astype(x2d.dtype), dgamma.astype(gamma.dtype),
                dbeta.astype(beta.dtype), dcenter)
    minv = jnp.stack([mean, inv]).astype(jnp.float32)          # (2, C)
    common = [pl.BlockSpec((bn, c), lambda i: (i, 0)),         # x
              pl.BlockSpec((bn, c), lambda i: (i, 0)),         # g
              pl.BlockSpec((1, c), lambda i: (0, 0)),          # scale
              pl.BlockSpec((1, c), lambda i: (0, 0)),          # shift
              pl.BlockSpec((2, c), lambda i: (0, 0))]          # [mean; inv]
    r = pl.pallas_call(
        functools.partial(_bn_bwd_reduce_kernel, activation=activation),
        grid=(n // bn,),
        in_specs=common,
        out_specs=pl.BlockSpec((2, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, c), jnp.float32),
        interpret=interpret,
    )(x2d, g, scale.reshape(1, c), shift.reshape(1, c), minv)
    dbeta, dgamma = r[0], r[1]
    dx = pl.pallas_call(
        functools.partial(_bn_bwd_dx_kernel, activation=activation),
        grid=(n // bn,),
        in_specs=common + [pl.BlockSpec((2, c), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), x2d.dtype),
        interpret=interpret,
    )(x2d, g, scale.reshape(1, c), shift.reshape(1, c), minv,
      (r / n).astype(jnp.float32))
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dcenter)


fused_bn_act_train.defvjp(_train_fwd, _train_bwd)

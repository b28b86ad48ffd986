"""Normalization layers — BatchNorm, LayerNorm, RMSNorm, LRN.

Reference parity: ``org.deeplearning4j.nn.conf.layers.BatchNormalization``
(cuDNN BatchNormalizationHelper path → fused XLA here),
``LocalResponseNormalization``. LayerNorm/RMSNorm are the reference's
SameDiff ops surfaced as layers (transformer path).

BatchNorm keeps running mean/var in layer `state` (the functional analogue of
the reference's mutable global stats arrays) — threaded through train steps
and used verbatim at inference.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .base import Ctx, Layer


@dataclass
class BatchNormalization(Layer):
    """Normalizes the trailing (channel) axis — works for FF (B,C) and
    conv NHWC (B,H,W,C) inputs alike."""

    n_out: Optional[int] = None  # channels; inferred
    decay: float = 0.9           # DL4J's `decay` for running stats EMA
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False
    use_log_std: bool = False
    # DL4J BatchNormalization inherits activation from FeedForwardLayer;
    # at inference the whole BN+act collapses into the fused pallas
    # scale-shift-act kernel ("auto": on TPU only; True: everywhere —
    # compiled on a TPU, pallas interpret mode off it)
    activation: Any = "identity"
    fused: Any = "auto"

    def _fuse_ok(self, supported) -> bool:
        """Shared fused/auto/backend gating; `supported` is the kernel's
        activation predicate (inference and training support differ)."""
        if self.fused is False or not supported(self.activation):
            return False
        if self.fused is True:
            return True
        # "auto" fuses only when there IS an activation to fuse — plain
        # identity BN gains nothing over XLA's own fusion, so don't route
        # every existing BN through the kernel by default
        return self.activation != "identity" \
            and jax.default_backend() == "tpu"

    def _can_fuse(self) -> bool:
        from ...kernels.fused_ops import supported_activation
        return self._fuse_ok(supported_activation)

    def _can_fuse_train(self) -> bool:
        # OPT-IN ONLY (fused=True), never "auto": on-chip measurement
        # (scripts/diag_resnet_out.json, r4) showed the pallas training
        # BN regresses ResNet-50 b128 from MFU 0.35 to 0.22 — the kernel
        # materializes its input/output at HBM and blocks XLA from fusing
        # the BN+act chain into the producing convolution's epilogue.
        # The XLA path with one-pass shifted stats is the fast default.
        if self.fused is not True:
            return False
        from ...kernels.fused_ops import supported_train_activation
        return self._fuse_ok(supported_train_activation)

    def init(self, key, input_shape):
        c = self.n_out or input_shape[-1]
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.full((c,), self.gamma_init, self.dtype),
                      "beta": jnp.full((c,), self.beta_init, self.dtype)}
        state = {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)}
        return params, state, input_shape

    def apply(self, params, state, x, ctx: Ctx):
        axes = tuple(range(x.ndim - 1))
        if ctx.train:
            c = lax.stop_gradient(state["mean"])
            if self._can_fuse_train():
                # fused pallas training BN (kernels/fused_ops.py): shifted
                # one-pass stats sweep + normalize-act sweep, custom-VJP
                # backward with fused reductions — the cuDNN
                # BatchNormalizationForwardTraining/Backward regime
                from ...kernels.fused_ops import fused_bn_act_train
                ch = x.shape[-1]
                gamma = (jnp.ones((ch,), jnp.float32)
                         if self.lock_gamma_beta else params["gamma"])
                beta = (jnp.zeros((ch,), jnp.float32)
                        if self.lock_gamma_beta else params["beta"])
                y, mean, var = fused_bn_act_train(
                    x.reshape(-1, ch), gamma, beta, c, self.eps,
                    self.activation)
                new_state = {
                    "mean": self.decay * state["mean"]
                            + (1 - self.decay) * lax.stop_gradient(mean),
                    "var": self.decay * state["var"]
                           + (1 - self.decay) * lax.stop_gradient(var),
                }
                return y.reshape(x.shape), new_state
            # One-pass stats: jnp.var's two-pass form costs an extra full
            # HBM sweep of the activation per BN; the fused single sweep
            # measured +8.6% whole-model ResNet-50 throughput on v5e.
            # Shift by the RUNNING mean c (per-channel f32 state) before
            # squaring — var = E[(x−c)²] − (E[x]−c)² — so the subtraction
            # cancels (std² + drift²) − drift², not the catastrophic
            # E[x²] − mean² of the naive form: once c tracks the channel
            # mean this is as accurate as two-pass even for large-offset
            # channels. The clamp guards first-batch roundoff while c is
            # still cold.
            xf = x.astype(jnp.float32)
            d = xf - c
            dmean = jnp.mean(d, axis=axes)
            d2mean = jnp.mean(d * d, axis=axes)
            mean = c + dmean
            var = jnp.maximum(d2mean - dmean * dmean, 0.0)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
            if self._can_fuse():
                # inference BN+act folds to act(x*scale + shift): one
                # bandwidth-bound pallas pass (kernels/fused_ops.py)
                from ...kernels.fused_ops import fused_bn_act
                inv = lax.rsqrt(var + self.eps)
                scale, shift = inv, -mean * inv
                if not self.lock_gamma_beta:
                    g32 = params["gamma"].astype(jnp.float32)
                    scale = inv * g32
                    shift = params["beta"].astype(jnp.float32) - mean * scale
                c = x.shape[-1]
                y = fused_bn_act(x.reshape(-1, c), scale, shift,
                                 self.activation)
                return y.reshape(x.shape), new_state
        # normalize as one fused multiply-add: fold mean/gamma/beta into
        # per-channel scale/shift vectors (C-sized math) instead of two
        # full-tensor passes
        inv = lax.rsqrt(var + self.eps)
        if not self.lock_gamma_beta:
            scale = inv * params["gamma"].astype(jnp.float32)
            shift = params["beta"].astype(jnp.float32) - mean * scale
        else:
            scale, shift = inv, -mean * inv
        y = x.astype(jnp.float32) * scale + shift
        if self.activation != "identity":
            from .. import activations as _a
            y = _a.get(self.activation)(y)
        return y.astype(x.dtype), new_state


@dataclass
class LayerNormalization(Layer):
    """LayerNorm over the channel axis (SameDiff standardize + gain/bias)."""

    eps: float = 1e-5
    use_bias: bool = True

    def init(self, key, input_shape):
        c = input_shape[-1]
        params = {"gamma": jnp.ones((c,), self.dtype)}
        if self.use_bias:
            params["beta"] = jnp.zeros((c,), self.dtype)
        return params, {}, input_shape

    def apply(self, params, state, x, ctx: Ctx):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        y = y * params["gamma"].astype(jnp.float32)
        if self.use_bias:
            y = y + params["beta"].astype(jnp.float32)
        return y.astype(x.dtype), state


@dataclass
class RMSNorm(Layer):
    """RMS normalization (no mean subtraction) — transformer staple."""

    eps: float = 1e-6

    def init(self, key, input_shape):
        return {"gamma": jnp.ones((input_shape[-1],), self.dtype)}, {}, input_shape

    def apply(self, params, state, x, ctx: Ctx):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + self.eps) * params["gamma"].astype(jnp.float32)
        return y.astype(x.dtype), state


@dataclass
class LocalResponseNormalization(Layer):
    """LRN across channels (AlexNet-era). NHWC; pure elementwise+window — XLA fuses."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def init(self, key, input_shape):
        return {}, {}, input_shape

    def apply(self, params, state, x, ctx: Ctx):
        xf = x.astype(jnp.float32)
        sq = jnp.square(xf)
        half = self.n // 2
        # sum over a window of channels via padded cumulative trick
        pad = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
        win = sum(lax.slice_in_dim(pad, i, i + x.shape[-1], axis=x.ndim - 1)
                  for i in range(self.n))
        y = xf / jnp.power(self.k + self.alpha * win, self.beta)
        return y.astype(x.dtype), state

    def has_params(self):
        return False

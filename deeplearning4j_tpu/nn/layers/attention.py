"""Attention layers — SelfAttention (MHA), LearnedSelfAttention,
RecurrentAttention.

Reference parity: ``org.deeplearning4j.nn.conf.layers.{SelfAttentionLayer,
LearnedSelfAttentionLayer, RecurrentAttentionLayer}`` (built on SameDiff
MultiHeadDotProductAttention). TPU-first: the core is
``jax.nn.dot_product_attention`` which XLA lowers to a fused (flash-style)
kernel; a Pallas flash-attention path plugs in via `impl="pallas"` (see
`deeplearning4j_tpu.kernels.flash_attention`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .base import Ctx, Layer, apply_time_mask


def _mha_params(layer, key, n_in, n_out, n_heads, head_dim):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    proj = n_heads * head_dim
    return {
        "Wq": layer._make_weight(k1, (n_in, proj), n_in, proj),
        "Wk": layer._make_weight(k2, (n_in, proj), n_in, proj),
        "Wv": layer._make_weight(k3, (n_in, proj), n_in, proj),
        "Wo": layer._make_weight(k4, (proj, n_out), proj, n_out),
    }


def multi_head_attention(params, q_in, kv_in, n_heads, head_dim, mask=None,
                         is_causal=False, impl=None, dtype=None, v_in=None):
    """q_in (B,Tq,C), kv_in (B,Tk,C) → (B,Tq,nOut). mask: (B,Tk) key mask.
    ``v_in`` (B,Tk,Cv) lets values come from a different input than keys
    (AttentionVertex's 3-input form); defaults to kv_in."""
    dt = dtype or q_in.dtype
    b, tq, _ = q_in.shape
    tk = kv_in.shape[1]
    v_src = kv_in if v_in is None else v_in
    q = q_in @ params["Wq"].astype(dt)
    k = kv_in @ params["Wk"].astype(dt)
    v = v_src @ params["Wv"].astype(dt)
    # pallas kernel needs self-attention (Tq == Tk), no key mask, and real TPU
    # hardware ("pallas_interpret" forces interpreter mode for tests/debug);
    # it takes the projections as they are, (B, T, H·D)
    use_pallas = (impl == "pallas_interpret"
                  or (impl == "pallas" and jax.default_backend() == "tpu"))
    if use_pallas and mask is None and tq == tk:
        from ...kernels.flash_attention import flash_attention_ntc
        out = flash_attention_ntc(
            q, k, v, n_heads, causal=is_causal,
            interpret=True if impl == "pallas_interpret" else None)
    else:
        q = q.reshape(b, tq, n_heads, head_dim)
        k = k.reshape(b, tk, n_heads, head_dim)
        v = v.reshape(b, tk, n_heads, head_dim)
        kw = {}
        if mask is not None:
            kw["key_value_seq_lengths"] = None
            amask = mask[:, None, None, :].astype(bool)  # (B,1,1,Tk) -> broadcast (B,H,Tq,Tk)
            kw["mask"] = jnp.broadcast_to(amask, (b, n_heads, tq, tk))
        out = jax.nn.dot_product_attention(q, k, v, is_causal=is_causal, **kw)
        out = out.reshape(b, tq, n_heads * head_dim)
    return out @ params["Wo"].astype(dt)


@dataclass
class SelfAttentionLayer(Layer):
    """Multi-head self attention over (B,T,C) [NTC]."""

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None
    project_input: bool = True
    is_causal: bool = False
    impl: Optional[str] = None  # None → XLA fused; "pallas" → our kernel

    def _head_dim(self, n_in):
        return self.head_size or (self.n_out or n_in) // self.n_heads

    def init(self, key, input_shape):
        t, c = input_shape
        c = self.n_in or c
        n_out = self.n_out or c
        params = _mha_params(self, key, c, n_out, self.n_heads, self._head_dim(c))
        return params, {}, (t, n_out)

    def apply(self, params, state, x, ctx: Ctx):
        x = self._cast_in(x)
        y = multi_head_attention(params, x, x, self.n_heads, self._head_dim(x.shape[-1]),
                                 mask=ctx.mask, is_causal=self.is_causal, impl=self.impl)
        return apply_time_mask(y, ctx.mask), state


@dataclass
class LearnedSelfAttentionLayer(Layer):
    """Attention with nQueries learned query vectors → fixed-size output
    (B, nQueries, nOut) regardless of sequence length."""

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None
    n_queries: int = 1
    impl: Optional[str] = None

    def init(self, key, input_shape):
        t, c = input_shape
        c = self.n_in or c
        n_out = self.n_out or c
        kq, kp = jax.random.split(key)
        hd = self.head_size or n_out // self.n_heads
        params = _mha_params(self, kp, c, n_out, self.n_heads, hd)
        params["Q"] = self._make_weight(kq, (self.n_queries, c), c, c)
        return params, {}, (self.n_queries, n_out)

    def apply(self, params, state, x, ctx: Ctx):
        x = self._cast_in(x)
        b = x.shape[0]
        q = jnp.broadcast_to(params["Q"].astype(x.dtype), (b,) + params["Q"].shape)
        hd = self.head_size or (self.n_out or x.shape[-1]) // self.n_heads
        y = multi_head_attention(params, q, x, self.n_heads, hd, mask=ctx.mask, impl=self.impl)
        return y, state


@dataclass
class AttentionVertex(Layer):
    """Multi-head dot-product attention as a ComputationGraph vertex
    (reference ``org.deeplearning4j.nn.conf.graph.AttentionVertex``).

    Inputs (all NTC): 1 → self-attention (q = k = v); 2 → (queries,
    keys-and-values); 3 → (queries, keys, values). With
    ``project_input=False`` (requires ``n_heads == 1``) raw scaled
    dot-product attention runs without projections, like the reference.
    """

    multi_input = True

    n_out: int = 0
    n_heads: int = 1
    head_size: Optional[int] = None
    project_input: bool = True
    n_in_queries: Optional[int] = None
    n_in_keys: Optional[int] = None
    n_in_values: Optional[int] = None

    @staticmethod
    def _norm_shapes(input_shapes):
        if input_shapes and not isinstance(input_shapes[0], (tuple, list)):
            input_shapes = [input_shapes]
        if len(input_shapes) == 1:
            input_shapes = input_shapes * 3
        elif len(input_shapes) == 2:
            input_shapes = [input_shapes[0], input_shapes[1], input_shapes[1]]
        elif len(input_shapes) != 3:
            raise ValueError(
                f"AttentionVertex takes 1-3 inputs, got {len(input_shapes)}")
        return input_shapes

    def init(self, key, input_shapes):
        (tq, cq), (_, ck), (_, cv) = self._norm_shapes(input_shapes)
        cq = self.n_in_queries or cq
        ck = self.n_in_keys or ck
        cv = self.n_in_values or cv
        if not self.project_input:
            if self.n_heads != 1:
                raise ValueError(
                    "AttentionVertex(project_input=False) requires "
                    f"n_heads == 1, got {self.n_heads}")
            if cq != ck:
                raise ValueError(
                    "AttentionVertex(project_input=False): query size "
                    f"{cq} must equal key size {ck}")
            if self.n_out and self.n_out != cv:
                raise ValueError(
                    "AttentionVertex(project_input=False) outputs the value "
                    f"width {cv}; n_out={self.n_out} needs project_input="
                    "True (there is no projection to change the width)")
            return {}, {}, (tq, self.n_out or cv)
        n_out = self.n_out or cv
        hd = self.head_size or n_out // self.n_heads
        proj = self.n_heads * hd
        k1, k2, k3, k4 = jax.random.split(key, 4)
        params = {
            "Wq": self._make_weight(k1, (cq, proj), cq, proj),
            "Wk": self._make_weight(k2, (ck, proj), ck, proj),
            "Wv": self._make_weight(k3, (cv, proj), cv, proj),
            "Wo": self._make_weight(k4, (proj, n_out), proj, n_out),
        }
        return params, {}, (tq, n_out)

    def apply(self, params, state, xs, ctx: Ctx):
        if not isinstance(xs, (list, tuple)):
            xs = [xs]
        xs = [self._cast_in(x) for x in xs]
        if len(xs) == 1:
            q_in = k_in = v_src = xs[0]
        elif len(xs) == 2:
            q_in, k_in = xs
            v_src = k_in
        else:
            q_in, k_in, v_src = xs
        mask = ctx.mask
        if mask is not None and (mask.ndim != 2
                                 or mask.shape[1] != k_in.shape[1]):
            mask = None  # feature mask doesn't span the key axis
        if not self.project_input:
            scale = 1.0 / jnp.sqrt(jnp.asarray(q_in.shape[-1], q_in.dtype))
            scores = jnp.einsum("bqc,bkc->bqk", q_in, k_in) * scale
            if mask is not None:
                scores = jnp.where(mask[:, None, :] > 0, scores,
                                   jnp.finfo(scores.dtype).min)
            y = jax.nn.softmax(scores, axis=-1) @ v_src
            return y, state
        n_out = self.n_out or v_src.shape[-1]
        hd = self.head_size or n_out // self.n_heads
        y = multi_head_attention(params, q_in, k_in, self.n_heads, hd,
                                 mask=mask, v_in=v_src)
        return y, state


@dataclass
class RecurrentAttentionLayer(Layer):
    """SimpleRnn cell whose input at each step is augmented with attention
    over the full input sequence (reference RecurrentAttentionLayer)."""

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    activation: Any = "tanh"

    def init(self, key, input_shape):
        t, c = input_shape
        c = self.n_in or c
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hd = self.n_out // self.n_heads
        params = _mha_params(self, k1, c, self.n_out, self.n_heads, max(hd, 1))
        params["W"] = self._make_weight(k2, (c, self.n_out), c, self.n_out)
        params["RW"] = self._make_weight(k3, (self.n_out, self.n_out), self.n_out, self.n_out)
        params["Wa"] = self._make_weight(k4, (self.n_out, self.n_out), self.n_out, self.n_out)
        params["b"] = self._make_bias((self.n_out,))
        return params, {}, (t, self.n_out)

    def apply(self, params, state, x, ctx: Ctx):
        x = self._cast_in(x)
        act = self.activation_fn()
        hd = max(self.n_out // self.n_heads, 1)
        # attention context per step computed from x (keys/values static per seq)
        attn = multi_head_attention(params, x, x, self.n_heads, hd, mask=ctx.mask)
        w, rw, wa, b = (params[k].astype(x.dtype) for k in ("W", "RW", "Wa", "b"))
        xw = x @ w + b
        aw = attn @ wa
        h0 = jnp.zeros((x.shape[0], self.n_out), x.dtype)

        def step(h, inp):
            xt, at, mt = inp
            h_new = act(xt + at + h @ rw)
            if mt is not None:
                h_new = jnp.where(mt[:, None] > 0, h_new, h)
            return h_new, h_new

        xs, ats = xw.swapaxes(0, 1), aw.swapaxes(0, 1)
        if ctx.mask is None:
            _, hs = jax.lax.scan(lambda h, i: step(h, (i[0], i[1], None)), h0, (xs, ats))
        else:
            _, hs = jax.lax.scan(step, h0, (xs, ats, ctx.mask.swapaxes(0, 1)))
        y = hs.swapaxes(0, 1)
        return apply_time_mask(y, ctx.mask), state

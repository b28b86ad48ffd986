"""The control's precision: every product of the reference computed in
scaled float8, the nearest precision under the bf16 the configurations state
(Micikevicius et al., "FP8 Formats for Deep Learning", 2022: e4m3 for the
forward operands, e5m2 for the gradients flowing back).

``product(op)`` wraps a bilinear ``op(a, b)`` (a matmul, an einsum, a
convolution): forward ``op(q(a), q(b))``; backward the two transposed
products of ``op`` with the incoming gradient rounded as well. ``EXACT`` is
the identity wrapper the reference itself uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _round(a, dtype, top):
    scale = jnp.max(jnp.abs(a)) / top + 1e-30
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


def e4m3(a):
    return _round(a, jnp.float8_e4m3fn, 448.0)


def e5m2(a):
    return _round(a, jnp.float8_e5m2, 57344.0)


def EXACT(op):
    return op


def FP8(op):
    @jax.custom_vjp
    def f(a, b):
        return op(e4m3(a), e4m3(b))

    def fwd(a, b):
        qa, qb = e4m3(a), e4m3(b)
        return op(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(op, *res)
        return vjp(e5m2(g))

    f.defvjp(fwd, bwd)
    return f

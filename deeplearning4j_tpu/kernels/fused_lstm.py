"""Fused whole-sequence LSTM — pallas TPU kernel (SURVEY §7 R2 kernel).

Replaces the reference's cuDNN RNN helper (libnd4j ``lstmLayer``/cuDNN
``cudnnRNNForward``) for the training/inference forward pass. The
TPU-native design runs the ENTIRE time loop inside one pallas kernel:

- the grid iterates t = 0..T-1 sequentially; the recurrent weights
  (H, 4H), peephole vectors, and the (B, H) h/c state live in VMEM the
  whole time (constant-index blocks are kept resident across grid steps),
  so HBM traffic per step is just the (B, 4H) input-projection block in
  and the (B, H) hidden block out — XLA's `lax.scan` loop re-reads the
  recurrent weights from HBM every iteration;
- the input projection x@W+b for ALL steps is computed OUTSIDE as one
  (B·T, 4H) MXU matmul (hoisted, as in the scan path);
- gate math matches nn.layers.recurrent.LSTM._cell exactly: gate order
  [i, f, o, g], sigmoid gates, tanh candidate/output, optional Graves
  peepholes (pI/pF on c_{t-1}, pO on c_t), f32 accumulation.

Backward is recompute-based (flash-attention-style): the custom VJP
replays the pure-jnp reference scan under jax.vjp, so no per-step gate
activations are saved — O(B·H) residual memory instead of O(B·T·4H),
which is what lets long sequences train at all.

Falls back to interpreter mode off-TPU so the same code path is
unit-testable on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import interpret_default as _interpret_default
from ._common import pltpu

_VMEM_BUDGET = 12 << 20  # leave headroom of the ~16 MiB per-core VMEM


def fits_vmem(b: int, h: int, itemsize: int) -> bool:
    """Whether the whole-sequence kernel's resident set fits VMEM: the
    (H, 4H) weights + (B, 4H) x-block (double-buffered) + two f32 (B, H)
    state scratches + in/out state blocks. Callers fall back to the
    lax.scan path when this is False — a model that trained fine there
    must never start failing to compile because of an 'auto' kernel."""
    resident = (h * 4 * h * itemsize          # RW, constant block
                + 2 * b * 4 * h * itemsize    # streamed x-proj, dbl-buffered
                + 2 * b * h * 4               # h/c f32 scratch
                + 4 * b * h * itemsize        # h0/c0 in + out block (dbl)
                + 3 * h * 4)                  # peepholes
    return resident <= _VMEM_BUDGET


# ------------------------------------------------------------ reference ----
def lstm_seq_reference(xproj, rw, peep, h0, c0):
    """Pure-jnp oracle AND the recompute target for the backward pass.

    xproj (B, T, 4H) = x@W + b; rw (H, 4H); peep (3, H) [pI, pF, pO]
    (zeros for a plain LSTM); h0/c0 (B, H). Returns hs (B, T, H).
    """
    h = h0.shape[-1]

    def step(carry, xt):
        h_prev, c_prev = carry
        # gate math in f32 regardless of the (possibly bf16) carry dtype —
        # matches the kernel's f32 scratch state
        z = (xt + h_prev @ rw).astype(jnp.float32)
        c32 = c_prev.astype(jnp.float32)
        zi, zf, zo, zg = (z[:, :h], z[:, h:2 * h],
                          z[:, 2 * h:3 * h], z[:, 3 * h:])
        zi = zi + c32 * peep[0]
        zf = zf + c32 * peep[1]
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = jnp.tanh(zg)
        c_new = f * c32 + i * g
        zo = zo + c_new * peep[2]
        o = jax.nn.sigmoid(zo)
        h_new = o * jnp.tanh(c_new)
        return (h_new.astype(h_prev.dtype), c_new.astype(c_prev.dtype)), \
            h_new.astype(h_prev.dtype)

    _, hs = jax.lax.scan(step, (h0, c0), xproj.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


# --------------------------------------------------------------- kernel ----
def _lstm_kernel(xproj_ref, rw_ref, peep_ref, h0_ref, c0_ref,
                 out_ref, h_s, c_s):
    t = pl.program_id(0)
    hdim = h_s.shape[-1]

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)
        c_s[...] = c0_ref[...].astype(jnp.float32)

    h_prev = h_s[...]
    c_prev = c_s[...]
    # matmul in the weights' dtype (bf16 runs at full MXU rate), f32 accum;
    # the h/c state itself stays f32 in scratch across all steps
    z = xproj_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h_prev.astype(rw_ref.dtype), rw_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    peep = peep_ref[...].astype(jnp.float32)       # (3, H) resident
    zi = z[:, :hdim] + c_prev * peep[0:1, :]
    zf = z[:, hdim:2 * hdim] + c_prev * peep[1:2, :]
    zo = z[:, 2 * hdim:3 * hdim]
    zg = z[:, 3 * hdim:]
    i = jax.nn.sigmoid(zi)
    f = jax.nn.sigmoid(zf)
    g = jnp.tanh(zg)
    c_new = f * c_prev + i * g
    o = jax.nn.sigmoid(zo + c_new * peep[2:3, :])
    h_new = o * jnp.tanh(c_new)
    h_s[...] = h_new
    c_s[...] = c_new
    out_ref[0] = h_new.astype(out_ref.dtype)


def _lstm_pallas(xproj, rw, peep, h0, c0, interpret):
    b, t, g4 = xproj.shape
    h = g4 // 4
    # time-major so every streamed block is a FULL (B, 4H) slice — pallas
    # TPU requires the last two block dims be (8, 128)-aligned or whole
    hs = pl.pallas_call(
        _lstm_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, g4), lambda i: (i, 0, 0)),  # streamed x-proj
            pl.BlockSpec((h, g4), lambda i: (0, 0)),        # resident weights
            pl.BlockSpec((3, h), lambda i: (0, 0)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
            pl.BlockSpec((b, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, b, h), xproj.dtype),
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32),
                        pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret,
    )(xproj.swapaxes(0, 1), rw, peep, h0, c0)
    return hs.swapaxes(0, 1)


# ------------------------------------------------------------ public -------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_lstm_seq(xproj, rw, peep, h0, c0, interpret=None):
    """Whole-sequence LSTM: (B, T, 4H) projections → (B, T, H) hiddens."""
    out, _ = _fwd(xproj, rw, peep, h0, c0, interpret)
    return out


def _fwd(xproj, rw, peep, h0, c0, interpret):
    if interpret is None:
        interpret = _interpret_default()
    out = _lstm_pallas(xproj, rw, peep, h0, c0, interpret)
    return out, (xproj, rw, peep, h0, c0)


def _bwd(interpret, res, g):
    xproj, rw, peep, h0, c0 = res
    # recompute-backward: replay the jnp scan under vjp (no stored gates)
    _, vjp_fn = jax.vjp(lstm_seq_reference, xproj, rw, peep, h0, c0)
    return vjp_fn(g)


fused_lstm_seq.defvjp(_fwd, _bwd)

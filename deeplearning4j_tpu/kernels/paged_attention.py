"""Paged-attention decode kernel — pallas TPU (ISSUE 17 tentpole).

The paged decode path (ISSUE 14) was XLA gather-attention: every decode
step materializes each slot's WHOLE mapped KV (``kl[table]`` — a
(B, S, H, Dh) gather) in HBM before one un-fused softmax-matvec reads
it once. Correct, but the slowest possible per-step kernel: the 3.0×
concurrency win of PR 14 and the 8.8× prefix-sharing win of PR 16 both
sit on it. This module is the cuDNN move (arXiv 1410.0759) — one fused
primitive instead of composed ops:

- **block-parallel over a slot's mapped pages**: grid ``(B, P)`` with
  the logical-page index fastest (TPU grids iterate sequentially, so
  the online-softmax state lives in VMEM scratch across a slot's
  pages, exactly the FlashAttention-2 schedule
  ``flash_attention.py`` already proves);
- **no materialized gather**: the per-slot page-table row rides in as
  a scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``) and the
  K/V BlockSpec index maps read THROUGH it — each grid step DMAs one
  (page_len, H, Dh) page straight from the pool, HBM traffic is the
  mapped bytes once, with no (B, S, H, Dh) intermediate;
- **sentinel pages and partial-fill tails masked in-kernel**: an
  unmapped (sentinel ``n_pages``) entry or a page past the slot's
  cursor is dead — ``pl.when`` skips its compute, the index map clamps
  its DMA onto the last live page (the `_causal_kv_map` no-refetch
  trick), and the tail rows of the last live page mask to ``NEG_INF``
  before the running max.

Dispatch is fidelity-gated promotion (:func:`decide`), not faith: per
shape-bucket the kernel RACES the XLA gather path on probe caches of
the live geometry; promotion requires the FidelityProbe
(``paged_kernel_vs_xla``) to hold ``kl_max`` under
:data:`PROMOTION_MAX_KL` AND bit-identical greedy tokens, plus a
measured speed win. Losers fall back silently. The verdict persists as
a unified-harness cost record (``paged_decode:...`` key) stamped with
:func:`kernel_sha` — editing this kernel auto-invalidates every stale
verdict and re-races (``kernels/autotune.py``).

Off-TPU the kernel runs in pallas interpret mode (the CPU CI oracle).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import interpret_default as _interpret_default
from ._common import pltpu
from . import autotune

NEG_INF = -1e30

#: promotion fidelity budget: max per-position KL(ref ‖ kernel), nats —
#: the same bound `scripts/fidelity_report.py --max-kl` gates captures
#: with. Greedy tokens must additionally match bitwise.
PROMOTION_MAX_KL = 1e-3

#: env knob for the dispatch mode when the engine doesn't pin one:
#: auto (race on TPU, gather elsewhere) | race | on | off
_MODE_ENV = "DL4J_PAGED_KERNEL"


# ------------------------------------------------------------ kernel --

def _decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_len, n_pages):
    """One grid step = one (slot b, logical page j). Scratch carries
    the slot's online-softmax state (m/l running stats + f32 acc)
    across its pages; init at j==0, emit at the last page."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    # dead page: unmapped (sentinel) or entirely past the cursor —
    # compute skipped AND (via the clamped index map) no fresh DMA
    live = (table_ref[b, j] < n_pages) & (j * page_len <= pos)

    @pl.when(live)
    def _compute():
        # One query row per head: the products are matrix-VECTOR, so
        # they run on the VPU in f32 with the head axis kept on the
        # sublanes of every operand (k/v arrive (PL, H, Dh)). Mosaic
        # accepts no batched dot whose batch axis sits at a different
        # position in each operand, and an M=1 MXU pass would buy
        # nothing over the multiply-reduce.
        q = q_ref[0].astype(jnp.float32)                  # (H, Dh)
        k = k_ref[0].astype(jnp.float32)                  # (PL, H, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.sum(k * q[None], axis=-1,
                    keepdims=True) * scale                # (PL, H, 1)
        # partial-fill tail: rows past the slot's cursor mask out
        t_idx = j * page_len + jax.lax.broadcasted_iota(jnp.int32,
                                                        s.shape, 0)
        s = jnp.where(t_idx > pos, NEG_INF, s)
        m = m_ref[...]                                    # (H, 1)
        l = l_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])                      # (PL, H, 1)
        corr = jnp.exp(m - m_new)
        acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * v, axis=0)
        m_ref[...] = m_new
        l_ref[...] = l * corr + jnp.sum(p, axis=0)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[...]
        # a slot with zero live rows (nothing mapped) emits zeros —
        # garbage-by-contract the scheduler never reads, same as the
        # gather path's clamped-garbage rows
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, table, pos,
                    interpret: Optional[bool] = None):
    """Fused single-token attention over a block-paged KV pool.

    q (B, H, Dh); k_pages/v_pages (n_pages, page_len, H, Dh) — ONE
    layer's pool; table (B, P) int32 per-slot page-table rows (sentinel
    ``n_pages`` = unmapped); pos (B,) int32 per-slot cursors (position
    ``pos[b]`` is the row just written — valid rows are
    ``<= pos[b]``, the `_cached_attention` mask contract). Returns
    (B, H, Dh) in q's dtype.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, dh = q.shape
    npg, plen = k_pages.shape[0], k_pages.shape[1]
    per_slot = table.shape[1]
    scale = 1.0 / math.sqrt(dh)

    def kv_map(b_, j_, tbl, ps):
        # dead steps clamp onto the slot's LAST live page so pallas
        # skips the HBM->VMEM fetch (same-block no-refetch rule); the
        # sentinel additionally clamps in-bounds for the DMA engine
        jl = jnp.minimum(j_, jnp.maximum(ps[b_], 0) // plen)
        return (jnp.minimum(tbl[b_, jl], npg - 1), 0, 0, 0)

    q_map = lambda b_, j_, tbl, ps: (b_, 0, 0)      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, per_slot),                  # page index fastest
        in_specs=[
            pl.BlockSpec((1, h, dh), q_map),
            pl.BlockSpec((1, plen, h, dh), kv_map),
            pl.BlockSpec((1, plen, h, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, dh), q_map),
        scratch_shapes=[pltpu.VMEM((h, dh), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_len=plen,
                          n_pages=npg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        interpret=interpret,
    )(table, pos, q, k_pages, v_pages)


def paged_attention_reference(q, k_pages, v_pages, table, pos):
    """The XLA gather oracle — byte-for-byte the math the engine's
    PR 14 paged decode ran: materialize each slot's fixed-width table
    row (sentinel entries CLAMP to the last pool page — garbage the pos
    mask never exposes), f32 softmax over the masked scores."""
    b, h, dh = q.shape
    plen = k_pages.shape[1]
    per_slot = table.shape[1]
    kg = k_pages[table].reshape(b, per_slot * plen, h, dh)
    vg = v_pages[table].reshape(b, per_slot * plen, h, dh)
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bhd,bshd->bhs",
                        (q.astype(jnp.float32) * scale),
                        kg.astype(jnp.float32))
    s = kg.shape[1]
    mask = jnp.arange(s)[None, :] <= pos[:, None]          # (B, S)
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, vg.astype(jnp.float32))
    return out.astype(q.dtype)


def kernel_sha() -> str:
    """Source fingerprint of the pallas kernel — the ``sha=`` every
    ``paged_decode:*`` cost record is stamped with. Editing the kernel
    (or its dispatch wrapper) changes this, which auto-invalidates
    stale promotion verdicts on next lookup (tested in
    tests/test_paged_attention.py)."""
    return autotune.source_sha(_decode_kernel, paged_attention)


# --------------------------------------------------------- promotion --

def bucket_key(cfg, cache, backend: Optional[str] = None) -> str:
    """The shape-bucket cost-record key for one engine geometry:
    kernel kind + model shape + pool geometry + dtype + backend."""
    if backend is None:
        backend = jax.default_backend()
    npg, plen = cache["k"].shape[1], cache["k"].shape[2]
    slots, per_slot = cache["pages"].shape
    dt = jnp.dtype(cache["k"].dtype).name
    return (f"paged_decode:L{cfg.n_layers}H{cfg.n_heads}D{cfg.head_dim}"
            f":PL{plen}:P{per_slot}:NP{npg}:S{slots}:{dt}:{backend}")


def _probe_cache(cfg, cache) -> Tuple[Dict, object]:
    """A probe cache with the LIVE cache's exact abstract shapes —
    random k/v content, every slot mapped to ~3/4 of its page-table
    width (partial-fill tail included) with contiguous distinct pages,
    cursors mid-page. Racing on it compiles/times the very signatures
    the live decode sweep will run (the race pre-warms the bucket).
    Returns (cache pytree, probe tokens)."""
    import numpy as np
    rng = np.random.default_rng(0)
    kshape = cache["k"].shape
    dt = cache["k"].dtype
    npg, plen = kshape[1], kshape[2]
    slots, per_slot = cache["pages"].shape
    table = np.full((slots, per_slot), npg, np.int32)
    nxt = 0
    pos = np.zeros((slots,), np.int32)
    for s in range(slots):
        want = max(1, (3 * per_slot) // 4)
        got = min(want, npg - nxt)
        if got < 1:                       # pool exhausted: leave empty
            continue
        table[s, :got] = np.arange(nxt, nxt + got)
        nxt += got
        # cursor mid-way into the last mapped page (partial fill)
        pos[s] = (got - 1) * plen + plen // 2
    probe = {
        "k": jnp.asarray(rng.standard_normal(kshape), dt),
        "v": jnp.asarray(rng.standard_normal(kshape), dt),
        "pos": jnp.asarray(pos),
        "pages": jnp.asarray(table),
    }
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots,)),
                       jnp.int32)
    return probe, toks


def _fid_compact(rep: Dict) -> Dict:
    keep = ("max_abs_err", "mean_abs_err", "kl_mean", "kl_max",
            "topk_agreement", "greedy_match_frac", "greedy_prefix_len",
            "positions")
    return {k: rep[k] for k in keep if k in rep}


def race(engine, cache, *, max_kl: float = PROMOTION_MAX_KL) -> Dict:
    """Race the pallas kernel against the XLA gather path on probe
    caches of ``cache``'s geometry; gate on fidelity; persist the
    verdict as a sha-stamped cost record; bump
    ``dl4j_autotune_promotions_total{kernel,verdict}``.

    Returns the record meta: ``{choice, verdict, gather_s, kernel_s,
    speedup, fidelity}``. The verdict vocabulary:

    - ``promoted`` — fidelity holds and the kernel measured faster;
    - ``fallback_slower`` — fidelity holds, gather measured faster;
    - ``fallback_fidelity`` — kl_max or greedy equivalence failed
      (the kernel is silently never dispatched for this bucket).
    """
    import numpy as np
    from ..obs import get_registry
    from ..obs.fidelity import FidelityProbe

    cfg = engine.cfg
    key = bucket_key(cfg, cache)
    sha = kernel_sha()

    # fidelity first: one step from IDENTICAL probe content through
    # both paths, compared in token space and KL
    probe_a, toks = _probe_cache(cfg, cache)
    probe_b = {k: jnp.array(v) for k, v in probe_a.items()}  # own buffers
    ref_logits, _ = engine._decode_paged(engine.params, probe_a, toks)
    cand_logits, _ = engine._decode_paged_kernel(engine.params, probe_b,
                                                 toks)
    fid = FidelityProbe("paged_kernel_vs_xla").compare(
        np.asarray(ref_logits, np.float32),
        np.asarray(cand_logits, np.float32))
    fidelity_ok = (fid["kl_max"] <= max_kl
                   and fid["greedy_match_frac"] == 1.0)

    # time BOTH arms regardless of the fidelity outcome — the A/B
    # numbers belong in the cost record and the bench ledger either
    # way; fidelity gates only the PROMOTION, never the measurement
    timings: Dict[str, float] = {}
    for name, fn in (("gather", engine._decode_paged),
                     ("kernel", engine._decode_paged_kernel)):
        state = {}
        state["cache"], state["toks"] = _probe_cache(cfg, cache)

        def run():
            logits, state["cache"] = fn(engine.params, state["cache"],
                                        state["toks"])
            return logits

        timings[name] = autotune._time_once(run)
    if fidelity_ok:
        chosen = ("kernel" if timings["kernel"] < timings["gather"]
                  else "gather")
        verdict = "promoted" if chosen == "kernel" else "fallback_slower"
    else:
        chosen, verdict = "gather", "fallback_fidelity"

    meta = {
        "verdict": verdict,
        "gather_s": timings.get("gather"),
        "kernel_s": timings.get("kernel"),
        "speedup": (round(timings["gather"] / timings["kernel"], 3)
                    if len(timings) == 2 and timings["kernel"] > 0
                    else None),
        "max_kl": max_kl,
        "fidelity": _fid_compact(fid),
        "backend": jax.default_backend(),
    }
    autotune.put(key, (chosen,), meta=meta, sha=sha)
    get_registry().counter(
        "dl4j_autotune_promotions_total",
        "Fidelity-gated kernel-vs-XLA promotion races, by verdict",
        labelnames=("kernel", "verdict")).inc(
            kernel="paged_decode", verdict=verdict)
    return dict(meta, choice=chosen, key=key)


def decide(engine, cache, mode: Optional[str] = None) -> str:
    """The dispatch decision for one engine × cache geometry:
    ``"kernel"`` or ``"gather"``. Resolution order:

    - ``mode`` (or the engine's pinned mode, or ``$DL4J_PAGED_KERNEL``):
      ``off`` → gather, ``on`` → kernel (no race — bench/debug);
    - ``auto`` (default): off-TPU the gather path wins untimed (the
      interpret-mode kernel exists for CI oracles, not speed); on TPU,
      fall through to the race;
    - ``race``: race regardless of backend (CPU tests/bench A/B).

    Raced verdicts are persistent sha-stamped cost records — a second
    process on the same chip generation gets the verdict for free, and
    an edited kernel invalidates + re-races (``kernels/autotune.py``).
    """
    if mode is None:
        mode = getattr(engine, "paged_kernel_mode", None) \
            or os.environ.get(_MODE_ENV, "auto")
    mode = str(mode).lower()
    if mode in ("off", "0", "gather"):
        return "gather"
    if mode in ("on", "1", "kernel"):
        return "kernel"
    if mode == "auto" and jax.default_backend() != "tpu":
        return "gather"
    # race (or auto-on-TPU): serve the cached verdict when its sha
    # still matches the kernel source, else measure
    rec = autotune.lookup(bucket_key(engine.cfg, cache), sha=kernel_sha())
    if rec is not None and rec["choice"]:
        return str(rec["choice"][0])
    return str(race(engine, cache)["choice"])

"""Generation engine for the zoo Transformer-LM: jitted KV-cache prefill
and single-token decode, plus greedy/temperature/top-k sampling.

Two device entry points, both compiled once per shape and reused for the
life of the engine:

- ``prefill`` — runs the prompt through the ordinary block stack (the
  SAME ``apply_blocks`` the training forward uses, ``return_kv=True``),
  writes every layer's k/v into the cache, and returns ONLY the last
  valid position's logits (``(B, V)`` — never the ``(B, T, V)`` tensor a
  generation step doesn't need; at T=4096/V=32k that tensor alone is
  0.5 GB f32).
- ``decode_step`` — one token per slot: embed at each slot's own
  position cursor, scan the stacked blocks with the cache riding the
  scan's xs/ys (layer l's k/v slab is consumed and re-emitted in place),
  attend causally against the cache under a per-slot length mask. The
  cache argument is DONATED, so the decode loop never holds two copies
  of the K/V HBM.

Correctness is anchored the ``rnn_time_step`` way (tests/test_serving.py):
prefill+decode logits must match the full forward at every position
within fp tolerance — the cache is an optimization, never a different
model.

Single-chip inference path: MoE (`n_experts`) and ring attention are
training-parallelism features with no single-token analogue here and are
rejected at construction. Prefill inherits the model's own attention
gating (`flash_engages`), so a TPU prefill at flash-sized T runs the
pallas kernel exactly like the training forward.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..zoo import transformer as tfm
from . import kvcache

# prompt lengths are padded up to one of these before the jitted
# per-slot prefill runs, so mixed-length traffic compiles a handful of
# kernels instead of one per distinct prompt length (clipped to the
# engine's max_len; max_len itself is always a bucket)
DEFAULT_PREFILL_BUCKETS = (32, 128, 512, 1024, 2048, 4096, 8192)

_NEG_INF = -1e30  # mask value: finite, softmax-safe in f32


def sample_tokens(key, logits, temperature, top_k):
    """Vectorized next-token sampling: (B, V) f32 logits, per-slot
    ``temperature`` (B,) and ``top_k`` (B,) — a slot with
    ``temperature <= 0`` decodes greedily (argmax, key unused), one with
    ``top_k > 0`` samples only among its k highest logits. Per-slot
    knobs make one jitted sampler serve a mixed-request decode sweep.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    temperature = jnp.asarray(temperature, jnp.float32).reshape(-1)
    top_k = jnp.asarray(top_k, jnp.int32).reshape(-1)
    # top-k filter: threshold at each row's k-th largest logit
    desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kk = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    thresh = jnp.take_along_axis(desc, (kk - 1)[:, None], axis=-1)
    filtered = jnp.where(logits >= thresh, logits, _NEG_INF)
    scaled = filtered / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def sample_tokens_masked(key, logits, temperature, top_k, mask):
    """CONSTRAINED-decoding sampler (ISSUE 20): masked logits through
    the SAME ``sample_tokens`` body. ``mask`` is (B, V) bool — a
    disallowed token's logit drops to the mask floor BEFORE the top-k
    threshold and the greedy argmax, so every sampled (or greedy)
    token lies inside the mask. An all-true mask is the identity:
    bit-identical to ``sample_tokens`` on the same operands."""
    masked = jnp.where(mask, logits.astype(jnp.float32), _NEG_INF)
    return sample_tokens(key, masked, temperature, top_k)


def _wload(blk, name, dt):
    """One layer weight in compute dtype. A quantized block stack
    (ISSUE 19, ``serving.quant.quantized_params``) stores int8 values
    plus a per-output-channel scale under ``name + "_scale"``; the
    dequant happens here, on the fly, so storage is int8 and the
    matvec math stays bf16 — identical call sites either way."""
    w = blk[name]
    s = blk.get(name + "_scale")
    if s is None:
        return w.astype(dt)
    return (w.astype(jnp.float32) * s.astype(jnp.float32)).astype(dt)


def _cached_attention(cfg, q, k, v, pos):
    """Single-token attention against the cache: q (B, H, Dh) vs
    k/v (B, S, H, Dh), each slot masked to its own length (positions
    ``<= pos[b]`` — pos is the index the current token was just written
    at). Scores accumulate f32 regardless of cache dtype; out-of-range
    cache rows never contribute."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhd,bshd->bhs",
                        (q.astype(jnp.float32) * scale),
                        k.astype(jnp.float32))
    s = k.shape[1]
    mask = jnp.arange(s)[None, :] <= pos[:, None]          # (B, S)
    scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out.astype(cfg.dtype)


class GenerationEngine:
    """Prefill/decode engine bound to one (cfg, params) pair.

    The engine owns the jitted functions; callers own the cache pytree
    (``init_cache``) and thread it through ``prefill`` / ``decode_step``
    — the functional style every other step in this codebase uses, so
    the cache composes with donation and with schedulers that interleave
    prefill and decode on one pool.
    """

    def __init__(self, cfg, params, *, max_len: Optional[int] = None,
                 prefill_buckets=DEFAULT_PREFILL_BUCKETS,
                 prefill_chunk: Optional[int] = None,
                 paged_kernel: Optional[str] = None,
                 quant_kv: Optional[str] = None,
                 quant_weights: Optional[str] = None):
        if "gated_deltanet" in getattr(cfg, "layer_mixers", ()):
            raise NotImplementedError(
                "GenerationEngine cannot decode layer_mixers "
                "'gated_deltanet': such a layer carries a (key x value) "
                "state per head and its convolution's last tokens from one "
                "step to the next, which needs a state per slot beside the "
                "KV pages, and a period whose layers hold trees of different "
                "shapes needs a cache of each kind (ROADMAP Reach B8)")
        if (getattr(cfg, "attention", "mha") != "mha"
                or getattr(cfg, "router", "linear") != "linear"
                or getattr(cfg, "scaled_residuals", False)
                or getattr(cfg, "rotary_share", 1.0) != 1.0
                or getattr(cfg, "norm_eps", 1e-6) != 1e-6
                or getattr(cfg, "dense_layers", 0)
                or getattr(cfg, "shared_experts", 0)
                or getattr(cfg, "predict_ahead", 0)
                or getattr(cfg, "qk_norm", False)
                or getattr(cfg, "attn_output_gate", False)
                or getattr(cfg, "norm_zero_centred", False)
                or getattr(cfg, "shared_expert_gate", False)):
            raise NotImplementedError(
                "GenerationEngine cannot decode this block: compressed "
                "convolutional attention (attention='cca') needs the "
                "convolutions' last tokens and the shifted value beside the "
                "KV pages, the mlp router its state from layer to layer, and "
                "the decode step knows no scaled residuals, no partial "
                "rotary and one norm_eps (ROADMAP Reach B9); latent "
                "attention (attention='mla') needs a cache of the latent and "
                "the shared rotary key with the up-projections absorbed into "
                "the query and the output, and the decode step knows no "
                "sigmoid router, no shared expert, no leading dense layers "
                "(two groups of blocks) and no prediction module to draft "
                "with (ROADMAP Reach B10); nor q and k norms, an output "
                "gate, zero-centred norms or a gated shared expert")
        if getattr(cfg, "n_experts", 0):
            raise NotImplementedError(
                "GenerationEngine is dense-only: MoE expert dispatch has "
                "no single-token decode path yet (train MoE via the GSPMD "
                "path; see ROADMAP)")
        if (getattr(cfg, "layer_positions", ()) or getattr(cfg, "layer_windows", ())
                or getattr(cfg, "kv_heads", cfg.n_heads) != cfg.n_heads
                or getattr(cfg, "mlp", "gelu") != "gelu"):
            raise NotImplementedError(
                "GenerationEngine serves the GPT-2-style block only: the KV "
                "pool's row holds one K/V head per query head, the decode "
                "step adds the learned position table and knows no window "
                "(ROADMAP Reach B2, B5)")
        if cfg.use_ring_attention:
            raise NotImplementedError(
                "ring attention is a sequence-parallel TRAINING path; the "
                "decode step attends one token against a local cache — "
                "construct the engine with use_ring_attention=False")
        self.cfg = cfg
        self.params = params
        self.max_len = int(cfg.max_seq if max_len is None else max_len)
        if self.max_len > cfg.max_seq:
            raise ValueError(
                f"max_len {self.max_len} exceeds cfg.max_seq="
                f"{cfg.max_seq}: no position rows past the table")
        self.prefill_buckets = tuple(sorted(
            {min(b, self.max_len) for b in prefill_buckets} | {self.max_len}))
        # chunked prefill (ISSUE 14): one chunk never exceeds this many
        # prompt tokens; chunks pad to the bucket subset at or below it
        # (≤ 1 compile per chunk bucket — the retrace contract)
        self.chunk_len = int(min(
            kvcache.DEFAULT_PREFILL_CHUNK if prefill_chunk is None
            else prefill_chunk, self.max_len))
        if self.chunk_len < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.chunk_buckets = tuple(sorted(
            {min(b, self.chunk_len) for b in self.prefill_buckets}
            | {self.chunk_len}))
        # jit once; cache (argnum 1 after params) donated on every path.
        # Each entry point is wrapped in a CompileSentinel (ISSUE 12):
        # compiles are counted/timed per abstract signature, and after
        # mark_warm() any further compile is a warned retrace — the
        # zero-recompile-after-warmup contract the regression tests pin.
        # The sentinel is transparent (.lower etc. delegate), so floor
        # probes keep working on eng._decode unchanged.
        from ..obs.compiles import CompileSentinel
        self._decode = CompileSentinel(
            "decode_step", jax.jit(self._decode_raw, donate_argnums=(1,)))
        self._prefill = CompileSentinel(
            "prefill", jax.jit(self._prefill_raw, donate_argnums=(1,)))
        self._prefill_slot = CompileSentinel(
            "prefill_slot", jax.jit(self._prefill_slot_raw,
                                    donate_argnums=(1,)))
        self._sample = CompileSentinel("sample_tokens",
                                       jax.jit(sample_tokens))
        # paged entry points (ISSUE 14): same donation discipline — the
        # page pool is updated in place for the life of the cache
        self._decode_paged = CompileSentinel(
            "decode_paged", jax.jit(self._decode_paged_raw,
                                    donate_argnums=(1,)))
        # pallas paged-attention variant (ISSUE 17): same signature and
        # donation, attention fused in-kernel instead of gathered.
        # Which one decode_step dispatches is a per-geometry verdict
        # from the fidelity-gated promotion race (_paged_kernel_choice)
        self._decode_paged_kernel = CompileSentinel(
            "decode_paged_kernel",
            jax.jit(functools.partial(self._decode_paged_raw,
                                      use_kernel=True),
                    donate_argnums=(1,)))
        # paged_kernel pins the dispatch mode (off|on|auto|race); None
        # defers to $DL4J_PAGED_KERNEL, default "auto" (race on TPU,
        # gather elsewhere — see kernels.paged_attention.decide)
        self.paged_kernel_mode = paged_kernel
        self._paged_plan = {}            # geometry key -> kernel|gather
        # quantization plane (ISSUE 19): per-mode dispatch verdicts —
        # quant_kv/quant_weights pin the mode (off|on|auto|race); None
        # defers to $DL4J_QUANT_KV / $DL4J_QUANT_W, default "auto"
        # (race on TPU, bf16 elsewhere — serving.quant.decide_*). The
        # int8 block stack is built lazily on the first decode that
        # wants it, never at construction.
        self.quant_kv_mode = quant_kv
        self.quant_weights_mode = quant_weights
        self._wchoice: Optional[str] = None   # "int8" | "bf16"
        self._qparams = None
        self._prefill_chunk = CompileSentinel(
            "prefill_chunk", jax.jit(self._prefill_chunk_raw,
                                     donate_argnums=(1,)))
        # speculative-decode verify (ISSUE 19): the SAME chunked-prefill
        # body, but the head runs over EVERY row — the draft's k
        # proposals are judged from one dispatch's (C, V) logits
        self._verify_chunk = CompileSentinel(
            "verify_chunk",
            jax.jit(functools.partial(self._prefill_chunk_raw,
                                      all_logits=True),
                    donate_argnums=(1,)))
        self._copy_page = CompileSentinel(
            "copy_page", jax.jit(self._copy_page_raw,
                                 donate_argnums=(0,)))
        # multi-workload request plane (ISSUE 20): the EMBED hidden-row
        # chunk and the CONSTRAINED masked sampler — same bodies as
        # their unmasked/logit siblings, pre-warmed by the scheduler so
        # a new workload never retraces mid-serve
        self._embed_chunk = CompileSentinel(
            "embed_chunk",
            jax.jit(functools.partial(self._prefill_chunk_raw,
                                      return_hidden=True),
                    donate_argnums=(1,)))
        self._sample_masked = CompileSentinel(
            "sample_tokens_masked", jax.jit(sample_tokens_masked))
        self.sentinels = {s.name: s for s in (
            self._decode, self._prefill, self._prefill_slot, self._sample,
            self._decode_paged, self._decode_paged_kernel,
            self._prefill_chunk, self._verify_chunk, self._copy_page,
            self._embed_chunk, self._sample_masked)}

    # ------------------------------------------------------------ cache
    def init_cache(self, n_slots: int):
        return kvcache.init_cache(self.cfg, n_slots, self.max_len)

    def init_paged_cache(self, n_slots: int, n_pages: int,
                         page_len: int = kvcache.DEFAULT_PAGE_LEN,
                         quantized: Optional[bool] = None):
        """Allocate the paged pool. ``quantized=None`` lets the
        fidelity-gated quant_kv promotion decide per geometry (ISSUE
        19, ``serving.quant.decide_kv``) — off everywhere the race
        does not run or win, so callers that never opt in keep the
        bf16 pool byte-for-byte."""
        if quantized is None:
            from . import quant
            quantized = quant.decide_kv(self, n_slots, n_pages,
                                        page_len) == "int8"
        return kvcache.init_paged_cache(self.cfg, n_slots, n_pages,
                                        page_len, self.max_len,
                                        quantized=bool(quantized))

    def refresh(self, params):
        """Swap in new params (e.g. after more training). Compiled fns
        are shape-keyed, so no retrace as long as shapes match. The
        quantized block stack (ISSUE 19) is derived state: drop it so
        the next decode re-quantizes the fresh values."""
        self.params = params
        self._qparams = None
        return self

    def _decode_params(self):
        """Params the decode matvecs run with: the int8 block stack
        when the quant_w promotion picked it (ISSUE 19), else the full
        ones. Resolved lazily ONCE per engine — the race itself needs
        the jitted decode, so this cannot happen at construction."""
        if self._wchoice is None:
            from . import quant
            self._wchoice = quant.decide_weights(self)
        if self._wchoice == "int8":
            if self._qparams is None:
                from . import quant
                self._qparams = quant.quantized_params(self.params)
            return self._qparams
        return self.params

    # -------------------------------------------------- compile plane
    def mark_warm(self):
        """Declare warmup over on every sentinel: the decode sweep and
        the bucketed prefills seen so far are the working set; any
        compile after this is a warned retrace (ISSUE 12)."""
        for s in self.sentinels.values():
            s.mark_warm()
        return self

    def compile_report(self):
        """{entry point: {compiles, signatures, retraces_after_warm}} —
        what the retrace regression tests and ``/debug/memory`` read."""
        return {name: s.report() for name, s in self.sentinels.items()}

    # ----------------------------------------------------- device fns
    def _prefill_trunk(self, params, tokens):
        """Shared prompt pass: embedded tokens through the block stack
        with per-layer k/v capture. Returns (hidden, k, v)."""
        cfg = self.cfg
        x = tfm.embed(params, cfg, tokens)
        x, _, (ks, vs) = tfm.apply_blocks(params["blocks"], cfg, x,
                                          return_kv=True)
        return x, ks, vs

    def _prefill_raw(self, params, cache, tokens, lengths):
        """Whole-pool prefill: tokens (B, T) — B must equal the cache's
        slot count — lengths (B,) valid-prefix lengths (padding rows
        beyond a row's length leave garbage k/v that the pos mask never
        exposes). Returns (last-position logits (B, V) f32, cache)."""
        x, ks, vs = self._prefill_trunk(params, tokens)
        k_cache = lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        v_cache = lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
        b, t = tokens.shape
        last = jnp.clip(lengths - 1, 0, t - 1)
        x_last = x[jnp.arange(b), last]
        logits = tfm.head_logits_rows(params, self.cfg, x_last)
        return logits, {"k": k_cache, "v": v_cache,
                        "pos": lengths.astype(jnp.int32)}

    def _prefill_slot_raw(self, params, cache, tokens, length, slot):
        """Admit ONE request into slot ``slot`` of a live pool: tokens
        (1, T_bucket) padded prompt, ``length`` its true length. Only
        this slot's cache rows and cursor change — in-flight neighbours
        are untouched, which is what lets admission interleave with
        decode on the same cache."""
        x, ks, vs = self._prefill_trunk(params, tokens)
        k_cache = lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, slot, 0, 0, 0))
        v_cache = lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, slot, 0, 0, 0))
        t = tokens.shape[1]
        x_last = x[0, jnp.clip(length - 1, 0, t - 1)]
        logits = tfm.head_logits_rows(params, self.cfg, x_last[None])[0]
        pos = cache["pos"].at[slot].set(length.astype(jnp.int32))
        return logits, {"k": k_cache, "v": v_cache, "pos": pos}

    def _decode_raw(self, params, cache, tokens):
        """One decode step for the whole pool: tokens (B,) int32 → next
        logits (B, V) f32 + advanced cache. Each slot writes its token's
        k/v at its own cursor and attends to its own prefix; a slot past
        capacity drops the write (scatter OOB is a no-op) and its output
        is garbage the scheduler must mask — capacity accounting is the
        scheduler's admission-time job, not a per-step branch here."""
        cfg = self.cfg
        pos = cache["pos"]
        b = tokens.shape[0]
        x = self._embed_rows(params, tokens, pos)
        x, kv = self._blocks_with_cache(
            params, cache, x,
            write=lambda kl, rows: kl.at[jnp.arange(b), pos].set(
                rows.astype(kl.dtype)),
            attend=lambda q, kl, vl: _cached_attention(cfg, q, kl, vl,
                                                       pos))
        logits = tfm.head_logits_rows(params, cfg, x)
        return logits, dict(kv, pos=pos + 1)

    def _embed_rows(self, params, tokens, pos):
        """Embed one token row per sequence at its own position —
        the shared prologue of every cached entry point."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        x = x * math.sqrt(cfg.d_model)
        pos_rows = jnp.take(params["pos_embed"],
                            jnp.clip(pos, 0, cfg.max_seq - 1), axis=0)
        return x + pos_rows.astype(cfg.dtype)

    def _blocks_with_cache(self, params, cache, x, *, write, attend):
        """The ONE transformer block body every cached entry point
        (dense decode, paged decode, chunked prefill) runs — they
        differ ONLY in how k/v rows land in the layer cache
        (``write(layer_cache, rows) -> layer_cache``) and how the
        rows' queries see the cache (``attend(q, kl, vl) ->
        (rows, H, Dh)``). Keeping the norm/qkv/residual/MLP math in
        one place is what makes the paged-vs-dense bitwise-equivalence
        contract a structural property, not a maintenance promise.

        A quantized pool (ISSUE 19) threads its per-row scale arrays
        through the same scan: each layer's cache then travels as a
        ``(rows, scales)`` pair through ``write``/``attend``, and the
        closures own the quantize-on-append / dequantize-on-gather.
        Raw compute-dtype rows go INTO ``write`` on every path — the
        storage cast lives in the closure beside the scatter it feeds.
        Returns (block-stack output rows, cache k/v update dict)."""
        cfg = self.cfg
        n = x.shape[0]
        h_, dh = cfg.n_heads, cfg.head_dim
        quant = kvcache.is_quantized(cache)

        def block(x, xs):
            if quant:
                blk, kl, vl, ks, vs = xs
                kc, vc = (kl, ks), (vl, vs)
            else:
                blk, kc, vc = xs
            hh = tfm._rmsnorm(x, blk["ln1"])
            qkv = hh @ _wload(blk, "wqkv", hh.dtype)           # (n, 3h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(n, h_, dh)
            kc = write(kc, k.reshape(n, h_, dh))
            vc = write(vc, v.reshape(n, h_, dh))
            a = attend(q, kc, vc).reshape(n, h_ * dh)
            x = x + a @ _wload(blk, "wo", hh.dtype)
            h2 = tfm._rmsnorm(x, blk["ln2"])
            m = jax.nn.gelu(h2 @ _wload(blk, "w_in", h2.dtype)) \
                @ _wload(blk, "w_out", h2.dtype)
            if quant:
                return x + m, (kc[0], vc[0], kc[1], vc[1])
            return x + m, (kc, vc)

        if quant:
            x, (k_new, v_new, ks_new, vs_new) = lax.scan(
                block, x, (params["blocks"], cache["k"], cache["v"],
                           cache["k_scale"], cache["v_scale"]))
            return x, {"k": k_new, "v": v_new,
                       "k_scale": ks_new, "v_scale": vs_new}
        x, (k_new, v_new) = lax.scan(block, x,
                                     (params["blocks"], cache["k"],
                                      cache["v"]))
        return x, {"k": k_new, "v": v_new}

    def _decode_paged_raw(self, params, cache, tokens, use_kernel=False):
        """One decode step over a block-paged pool (ISSUE 14): same
        contract as ``_decode_raw`` — tokens (B,) → (logits (B, V) f32,
        advanced cache) — but each slot's k/v rows live in the pages its
        table maps. The write scatters the token's k/v into
        (page, offset); attention gathers the slot's fixed-width table
        row (pads to the pool sentinel, so the gather SHAPE never
        changes — page-table growth is data, not a retrace). A slot
        whose write position falls on an unmapped/sentinel entry drops
        the write (scatter OOB is a no-op — same contract as the dense
        path's past-capacity drop); keeping every position mapped is
        the scheduler's page-accounting job.

        ``use_kernel=True`` (the ``decode_paged_kernel`` entry point,
        ISSUE 17) swaps ONLY the attend closure for the fused pallas
        paged-attention kernel — page-table indirection via scalar
        prefetch, no materialized gather; writes, block math and logits
        are byte-identical to the gather path by construction
        (``_blocks_with_cache`` is shared)."""
        cfg = self.cfg
        pos = cache["pos"]
        table = cache["pages"]                       # (B, P) int32
        b = tokens.shape[0]
        h_, dh = cfg.n_heads, cfg.head_dim
        npg, plen = cache["k"].shape[1], cache["k"].shape[2]
        per_slot = table.shape[1]
        # write coordinates: logical page -> pool page via the table;
        # past-capacity or unmapped -> sentinel npg (scatter drops)
        lp = pos // plen                              # (B,)
        ent = table[jnp.arange(b), jnp.clip(lp, 0, per_slot - 1)]
        ent = jnp.where(lp < per_slot, ent, npg)
        off = pos % plen
        x = self._embed_rows(params, tokens, pos)
        quant = kvcache.is_quantized(cache)

        if use_kernel:
            if quant:
                raise NotImplementedError(
                    "the pallas paged-attention kernel reads bf16 pages; "
                    "a quantized pool decodes via the gather path "
                    "(decode_step routes it there automatically)")
            from ..kernels.paged_attention import paged_attention as _pa

            def attend(q, kl, vl):
                return _pa(q, kl, vl, table, pos)
        elif quant:
            from . import quant as quantmod

            def attend(q, kc, vc):
                # dequantize at gather: int8 pages × per-row-per-head
                # scales → f32 rows, same clamp-the-sentinel contract
                kl, ks = kc
                vl, vs = vc
                s = per_slot * plen
                kg = kl[table].reshape(b, s, h_, dh).astype(jnp.float32) \
                    * ks[table].reshape(b, s, h_)[..., None]
                vg = vl[table].reshape(b, s, h_, dh).astype(jnp.float32) \
                    * vs[table].reshape(b, s, h_)[..., None]
                return _cached_attention(cfg, q, kg, vg, pos)
        else:
            def attend(q, kl, vl):
                # gather each slot's pages: sentinel entries clamp to
                # the last pool page — garbage the pos mask never
                # exposes
                kg = kl[table].reshape(b, per_slot * plen, h_, dh)
                vg = vl[table].reshape(b, per_slot * plen, h_, dh)
                return _cached_attention(cfg, q, kg, vg, pos)

        if quant:
            def write(kc, rows):
                # quantize at append (ISSUE 19): the scale scatters to
                # the same (page, offset) the int8 row does
                arr, sc = kc
                qr, s = quantmod.quantize_rows(rows)
                return (arr.at[ent, off].set(qr),
                        sc.at[ent, off].set(s))
        else:
            def write(kl, rows):
                return kl.at[ent, off].set(rows.astype(kl.dtype))

        x, kv = self._blocks_with_cache(params, cache, x,
                                        write=write, attend=attend)
        logits = tfm.head_logits_rows(params, cfg, x)
        return logits, dict(kv, pos=pos + 1, pages=table)

    def _prefill_chunk_raw(self, params, cache, tokens, start, length,
                           slot, all_logits=False, return_hidden=False):
        """One chunked-prefill dispatch (ISSUE 14): tokens (1, C_bucket)
        — the slot's context rows ``[start, start+length)`` padded to a
        chunk bucket — written into the slot's mapped pages, with the
        chunk's queries attending causally against everything the slot
        holds (earlier chunks' pages + this chunk's own rows). Returns
        (last-valid-row logits (V,), cache); the scheduler uses the
        logits only on the FINAL chunk (they are the TTFT sample).
        Rows past ``length`` are padding: their writes drop (sentinel
        page) and their outputs are garbage nothing reads.

        ``all_logits=True`` is the speculative-decode verify variant
        (ISSUE 19, the ``verify_chunk`` entry point): the head runs
        over EVERY row — (C_bucket, V) — so one dispatch judges all k
        draft proposals; rows past ``length`` are garbage the caller
        slices off."""
        cfg = self.cfg
        table = cache["pages"]
        npg, plen = cache["k"].shape[1], cache["k"].shape[2]
        per_slot = table.shape[1]
        h_, dh = cfg.n_heads, cfg.head_dim
        tok = tokens[0]                                  # (C,)
        c = tok.shape[0]
        gpos = start + jnp.arange(c, dtype=jnp.int32)    # global positions
        valid = jnp.arange(c) < length
        row = table[slot]                                # (P,)
        lp = gpos // plen
        ent = row[jnp.clip(lp, 0, per_slot - 1)]
        ent = jnp.where(valid & (lp < per_slot), ent, npg)
        off = gpos % plen
        # positions via _embed_rows' clipped take, NOT a dynamic
        # slice: a padded tail past max_seq must clamp row-wise
        # (garbage rows) without shifting the VALID rows' positions
        # the way a clamped dynamic_slice start would
        x = self._embed_rows(params, tok, gpos)          # (C, d)
        s_len = per_slot * plen
        mask = jnp.arange(s_len)[None, :] <= gpos[:, None]   # (C, S)
        quant = kvcache.is_quantized(cache)

        def _chunk_attention(q, kg, vg):
            # the chunk's C queries attend causally over the ONE
            # slot's gathered pages (earlier chunks + own rows) — the
            # multi-row analogue of the decode paths' single-row
            # _cached_attention
            scale = 1.0 / math.sqrt(dh)
            scores = jnp.einsum("qhd,shd->qhs",
                                (q.astype(jnp.float32) * scale),
                                kg.astype(jnp.float32))
            scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("qhs,shd->qhd", probs,
                              vg.astype(jnp.float32)).astype(cfg.dtype)

        if quant:
            from . import quant as quantmod

            def attend(q, kc, vc):
                kl, ks = kc
                vl, vs = vc
                kg = kl[row].reshape(s_len, h_, dh).astype(jnp.float32) \
                    * ks[row].reshape(s_len, h_)[..., None]
                vg = vl[row].reshape(s_len, h_, dh).astype(jnp.float32) \
                    * vs[row].reshape(s_len, h_)[..., None]
                return _chunk_attention(q, kg, vg)

            def write(kc, rows):
                arr, sc = kc
                qr, s = quantmod.quantize_rows(rows)
                return (arr.at[ent, off].set(qr),
                        sc.at[ent, off].set(s))
        else:
            def attend(q, kl, vl):
                return _chunk_attention(q, kl[row].reshape(s_len, h_, dh),
                                        vl[row].reshape(s_len, h_, dh))

            def write(kl, rows):
                return kl.at[ent, off].set(rows.astype(kl.dtype))

        x, kv = self._blocks_with_cache(params, cache, x,
                                        write=write, attend=attend)
        if return_hidden:
            # EMBED variant (ISSUE 20): the post-ln_f hidden rows the
            # pooling reduces host-side — (C_bucket, d) f32, rows past
            # ``length`` garbage the caller slices off. No head matmul:
            # an embedding request never needs the (C, V) logits.
            logits = tfm.hidden_rows(params, cfg, x)
        elif all_logits:
            logits = tfm.head_logits_rows(params, cfg, x)    # (C, V)
        else:
            x_last = x[jnp.clip(length - 1, 0, c - 1)]
            logits = tfm.head_logits_rows(params, cfg, x_last[None])[0]
        pos = cache["pos"].at[slot].set((start + length).astype(jnp.int32))
        return logits, dict(kv, pos=pos, pages=table)

    @staticmethod
    def _copy_page_raw(cache, src, dst):
        """Copy-on-write page split (ISSUE 16): duplicate pool page
        ``src``'s k/v rows (every layer) into page ``dst``. Scalar
        src/dst are traced operands, so ONE compile covers every split;
        the cache is donated — the copy lands in place in the pool. A
        quantized pool's scale arrays share the page axis, so the same
        two-slice move carries them and CoW splits stay exact (ISSUE
        19: scales ride sharing untouched)."""
        out = dict(cache)
        for name in ("k", "v", "k_scale", "v_scale"):
            a = cache.get(name)
            if a is None:
                continue
            page = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                a, page, dst, axis=1)
        return out

    # ------------------------------------------------------- host API
    def copy_page(self, cache, src: int, dst: int):
        """Duplicate pool page ``src`` into ``dst`` (paged cache only) —
        the device half of a CoW split, after ``PageTable.cow`` remapped
        the table entry. The cache is DONATED; keep only the return."""
        if not kvcache.is_paged(cache):
            raise ValueError("copy_page needs a paged cache")
        npg = kvcache.n_pages(cache)
        if not (0 <= int(src) < npg and 0 <= int(dst) < npg):
            raise ValueError(f"page copy {src}->{dst} outside the "
                             f"{npg}-page pool")
        return self._copy_page(cache, jnp.int32(src), jnp.int32(dst))

    def prefill(self, cache, tokens, lengths=None):
        """Prefill the whole pool. ``tokens`` (B, T) with B == cache
        slots; ``lengths`` (B,) defaults to the full T per row."""
        if kvcache.is_paged(cache):
            raise ValueError(
                "prefill is the dense-pool path; a paged cache admits "
                "via prefill_chunk (its rows live in mapped pages, not "
                "per-slot lanes)")
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2:
            raise ValueError(f"prefill wants (B, T) token ids, got shape "
                             f"{tokens.shape}")
        if tokens.shape[1] > self.max_len:
            raise ValueError(f"prompt length {tokens.shape[1]} exceeds the "
                             f"cache capacity max_len={self.max_len}")
        if tokens.shape[0] != kvcache.cache_slots(cache):
            raise ValueError(
                f"prefill batch {tokens.shape[0]} != cache slots "
                f"{kvcache.cache_slots(cache)} (use prefill_slot for "
                "single-request admission)")
        if lengths is None:
            lengths = jnp.full((tokens.shape[0],), tokens.shape[1],
                               jnp.int32)
        return self._prefill(self.params, cache, tokens,
                             jnp.asarray(lengths, jnp.int32))

    def prefill_slot(self, cache, tokens, slot: int):
        """Admit one 1-D prompt into ``slot``; pads to the next prefill
        bucket so mixed lengths reuse a few compiled kernels. Returns
        (last logits (V,), cache)."""
        if kvcache.is_paged(cache):
            raise ValueError(
                "prefill_slot is the dense-pool admission path; a paged "
                "cache admits via prefill_chunk (writing by slot index "
                "would land in an arbitrary pool page)")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.max_len:
            raise ValueError(f"prompt length {n} exceeds cache capacity "
                             f"max_len={self.max_len}")
        bucket = next(b for b in self.prefill_buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        return self._prefill_slot(self.params, cache, jnp.asarray(padded),
                                  jnp.int32(n), jnp.int32(slot))

    def _paged_kernel_choice(self, cache) -> str:
        """``"kernel"`` or ``"gather"`` for this cache geometry —
        resolved ONCE per (pool shape, dtype, table shape) via the
        fidelity-gated promotion race (``kernels.paged_attention``) and
        memoized, so the decode hot loop never re-decides. The race's
        probe caches share the live cache's abstract shapes, so losing
        a race never costs the serve loop a retrace."""
        key = (cache["k"].shape, str(jnp.dtype(cache["k"].dtype)),
               cache["pages"].shape)
        got = self._paged_plan.get(key)
        if got is None:
            from ..kernels.paged_attention import decide
            got = decide(self, cache)
            self._paged_plan[key] = got
        return got

    def decode_path(self, cache) -> str:
        """Which compiled decode ``decode_step`` dispatches for this
        cache: ``"dense"`` | ``"paged_gather"`` | ``"paged_kernel"``.
        One ladder — ``decode_step`` runs on it and ``chip_smoke.py``
        prints it. A quantized pool (ISSUE 19) always takes the gather
        path: dequant lives in its attend closure, which the pallas
        kernel has no analogue for."""
        if not kvcache.is_paged(cache):
            return "dense"
        if kvcache.is_quantized(cache):
            return "paged_gather"
        return ("paged_kernel" if self._paged_kernel_choice(cache) == "kernel"
                else "paged_gather")

    def decode_step(self, cache, tokens):
        """One token for every slot: tokens (B,) → (logits (B, V), cache).
        Dispatches on the cache layout — dense slots, or the block-paged
        pool (ISSUE 14) via either the XLA gather path or the promoted
        pallas kernel (ISSUE 17) — behind one call site
        (:meth:`decode_path`); the passed cache is DONATED either way,
        keep only the returned one. The weights the matvecs load come
        from ``_decode_params`` (int8 when promoted)."""
        fn = {"dense": self._decode, "paged_gather": self._decode_paged,
              "paged_kernel": self._decode_paged_kernel
              }[self.decode_path(cache)]
        return fn(self._decode_params(), cache,
                  jnp.asarray(tokens, jnp.int32).reshape(-1))

    def prefill_chunk(self, cache, tokens, slot: int, start: int = 0):
        """Write one chunk of a slot's context into its mapped pages
        (paged cache only): ``tokens`` are the context rows
        ``[start, start+len)``, at most ``prefill_chunk`` of them, and
        every position up to ``start+len`` must already be mapped by
        the slot's page table (the scheduler's job); ``chunk_len`` caps
        one chunk's tokens. Pads to a chunk
        bucket (≤ 1 compile per bucket). Returns (last logits (V,),
        cache) — the logits matter only on the final chunk."""
        if not kvcache.is_paged(cache):
            raise ValueError("prefill_chunk needs a paged cache "
                             "(init_paged_cache); dense pools admit via "
                             "prefill_slot")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError("empty chunk")
        if n > self.chunk_len:
            raise ValueError(f"chunk of {n} tokens exceeds chunk_len="
                             f"{self.chunk_len}")
        if start + n > self.max_len:
            raise ValueError(f"chunk ends at {start + n}, past cache "
                             f"capacity max_len={self.max_len}")
        bucket = next(b for b in self.chunk_buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        return self._prefill_chunk(self.params, cache, jnp.asarray(padded),
                                   jnp.int32(start), jnp.int32(n),
                                   jnp.int32(slot))

    def verify_chunk(self, cache, tokens, slot: int, start: int):
        """Speculative-decode verify (ISSUE 19): run ``tokens`` — the
        last accepted token followed by the draft's proposals — through
        the chunked-prefill body at positions ``[start, start+len)``
        and return ALL row logits ``((C_bucket, V) f32, cache)``; row i
        is the next-token distribution after ``tokens[:i+1]``, so one
        dispatch judges every proposal. Rows are WRITTEN into the
        slot's mapped pages as they go — the caller rolls back the
        rejected tail (``PageTable.trim`` + a pos rewind). Runs with
        ``_decode_params`` — the verify logits must be the ones
        ``decode_step`` would have produced, or greedy spec decode
        loses bit-identity with ``generate()``."""
        if not kvcache.is_paged(cache):
            raise ValueError("verify_chunk needs a paged cache: rollback "
                             "is a page-table operation")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError("empty verify chunk")
        if n > self.chunk_len:
            raise ValueError(f"verify chunk of {n} tokens exceeds "
                             f"chunk_len={self.chunk_len}")
        if start + n > self.max_len:
            raise ValueError(f"verify chunk ends at {start + n}, past "
                             f"cache capacity max_len={self.max_len}")
        bucket = next(b for b in self.chunk_buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        return self._verify_chunk(self._decode_params(), cache,
                                  jnp.asarray(padded), jnp.int32(start),
                                  jnp.int32(n), jnp.int32(slot))

    def embed_chunk(self, cache, tokens, slot: int, start: int = 0):
        """EMBED workload chunk (ISSUE 20): the ``prefill_chunk`` body
        with the head swapped for the post-``ln_f`` hidden rows —
        returns ``((C_bucket, d) f32 hidden rows, cache)``; rows past
        ``len(tokens)`` are padding garbage the caller slices off. KV
        rows are written into the slot's mapped pages exactly like a
        prefill chunk (same bucketing, ≤ 1 compile per bucket)."""
        if not kvcache.is_paged(cache):
            raise ValueError("embed_chunk needs a paged cache "
                             "(init_paged_cache)")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        if n < 1:
            raise ValueError("empty chunk")
        if n > self.chunk_len:
            raise ValueError(f"chunk of {n} tokens exceeds chunk_len="
                             f"{self.chunk_len}")
        if start + n > self.max_len:
            raise ValueError(f"chunk ends at {start + n}, past cache "
                             f"capacity max_len={self.max_len}")
        bucket = next(b for b in self.chunk_buckets if b >= n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        return self._embed_chunk(self.params, cache, jnp.asarray(padded),
                                 jnp.int32(start), jnp.int32(n),
                                 jnp.int32(slot))

    def sample(self, key, logits, temperature=0.0, top_k=0):
        """Next tokens from (B, V) logits; scalar knobs broadcast to the
        pool, vectors give per-slot control."""
        bsz = logits.shape[0]
        temperature = jnp.broadcast_to(
            jnp.asarray(temperature, jnp.float32), (bsz,))
        top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (bsz,))
        return self._sample(key, logits, temperature, top_k)

    def sample_masked(self, key, logits, temperature=0.0, top_k=0,
                      mask=None):
        """CONSTRAINED-decoding sampler (ISSUE 20): ``mask`` (B, V) or
        (V,) bool — True admits the token. ``mask=None`` falls through
        to the plain sampler (same compiled fn GENERATE uses)."""
        if mask is None:
            return self.sample(key, logits, temperature, top_k)
        bsz = logits.shape[0]
        temperature = jnp.broadcast_to(
            jnp.asarray(temperature, jnp.float32), (bsz,))
        top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (bsz,))
        mask = jnp.broadcast_to(jnp.asarray(mask, bool),
                                (bsz, logits.shape[-1]))
        return self._sample_masked(key, logits, temperature, top_k, mask)

    def generate(self, prompt_ids, max_new_tokens=32, *, key=None,
                 temperature=0.0, top_k=0, eos_id=None):
        """One-shot batched generation: prefill the prompt(s), then
        sample/decode up to ``max_new_tokens``. Returns generated ids
        (prompt excluded) as numpy — ``(B, n)`` (rows past their eos are
        padded with ``eos_id``) or ``(n,)`` for a 1-D prompt."""
        ids = np.asarray(prompt_ids, np.int32)
        squeeze = ids.ndim == 1
        if squeeze:
            ids = ids[None, :]
        if ids.ndim != 2 or ids.shape[1] < 1:
            raise ValueError(f"prompt_ids must be (T,) or (B, T) with "
                             f"T >= 1, got shape {ids.shape}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        bsz, t = ids.shape
        # the last sampled token is never written back, hence the -1
        if t + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt ({t}) + max_new_tokens ({max_new_tokens}) - 1 "
                f"exceeds cache capacity max_len={self.max_len}")
        if key is None:
            key = jax.random.PRNGKey(0)
        cache = self.init_cache(bsz)
        logits, cache = self.prefill(cache, ids)
        out = np.zeros((bsz, max_new_tokens), np.int32)
        done = np.zeros((bsz,), bool)
        pad = 0 if eos_id is None else int(eos_id)
        n = 0
        for i in range(max_new_tokens):
            key, sub = jax.random.split(key)
            toks = np.asarray(self.sample(sub, logits, temperature, top_k))
            out[:, i] = np.where(done, pad, toks)
            n = i + 1
            if eos_id is not None:
                done |= (toks == eos_id)
                if done.all():
                    break
            if i + 1 < max_new_tokens:
                logits, cache = self.decode_step(cache, jnp.asarray(toks))
        out = out[:, :n]
        return out[0] if squeeze else out

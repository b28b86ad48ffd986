"""Serving plane (ISSUE 10): KV-cache prefill/decode engine, sampling,
continuous-batching scheduler, and the ParallelInference deadline-flush
satellite. Fast tier-1 suite — tiny f32 configs on CPU.

The anchor is the ``rnn_time_step`` oracle style: everything the cache
path produces must match the full forward at every position within fp
tolerance. The cache is an optimization, never a different model.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.obs import get_registry
from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                        FunctionalInferenceModel,
                                        GenerationEngine, cache_len,
                                        cache_nbytes, cache_slots,
                                        init_cache, sample_tokens)
from deeplearning4j_tpu.zoo import transformer as tfm

ATOL = 2e-4


def tiny_cfg(**kw):
    base = dict(vocab_size=61, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                max_seq=32, dtype=jnp.float32, remat=False,
                attn_scores_bf16=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    return GenerationEngine(cfg, params)


def _toks(shape, vocab=61, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, shape).astype(
        np.int32)


# ------------------------------------------------------------- kv cache

def test_cache_shapes_and_accounting(model):
    cfg, _ = model
    cache = init_cache(cfg, 3, max_len=16)
    assert cache["k"].shape == (cfg.n_layers, 3, 16, cfg.n_heads,
                                cfg.head_dim)
    assert cache["pos"].shape == (3,) and cache["pos"].dtype == jnp.int32
    assert cache_slots(cache) == 3 and cache_len(cache) == 16
    expect = 2 * cfg.n_layers * 3 * 16 * cfg.d_model * 4 + 3 * 4
    assert cache_nbytes(cache) == expect


def test_cache_rejects_bad_geometry(model):
    cfg, _ = model
    with pytest.raises(ValueError, match="max_seq"):
        init_cache(cfg, 1, max_len=cfg.max_seq + 1)
    with pytest.raises(ValueError):
        init_cache(cfg, 0)


def test_engine_rejects_training_parallelism(model):
    cfg, params = model
    moe = tiny_cfg(n_experts=2)
    with pytest.raises(NotImplementedError, match="dense-only"):
        GenerationEngine(moe, tfm.init_params(jax.random.PRNGKey(1), moe))
    ring = tiny_cfg(use_ring_attention=True)
    with pytest.raises(NotImplementedError, match="ring"):
        GenerationEngine(ring, params)


# ------------------------------------------- logit equivalence (oracle)

def test_prefill_last_logits_match_full_forward(model, engine):
    cfg, params = model
    toks = _toks((3, 14))
    full, _ = tfm.forward(params, cfg, jnp.asarray(toks))
    logits, cache = engine.prefill(engine.init_cache(3), toks)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full)[:, -1], atol=ATOL)
    assert np.asarray(cache["pos"]).tolist() == [14, 14, 14]


def test_prefill_plus_decode_match_full_forward_every_position(model,
                                                               engine):
    """THE acceptance anchor: prefill a prefix, decode the rest one
    token at a time feeding the TRUE next ids — logits must match the
    full forward at every position."""
    cfg, params = model
    toks = _toks((2, 16), seed=3)
    full = np.asarray(tfm.forward(params, cfg, jnp.asarray(toks))[0])
    for prefix in (1, 7):
        logits, cache = engine.prefill(engine.init_cache(2),
                                       toks[:, :prefix])
        np.testing.assert_allclose(np.asarray(logits), full[:, prefix - 1],
                                   atol=ATOL, err_msg=f"prefill {prefix}")
        for t in range(prefix, 16):
            logits, cache = engine.decode_step(cache, toks[:, t])
            np.testing.assert_allclose(
                np.asarray(logits), full[:, t], atol=ATOL,
                err_msg=f"prefix {prefix}, decode position {t}")


def test_prefill_slot_padded_matches_full_forward(model, engine):
    """Per-slot admission: bucket padding and neighbour slots must not
    perturb the admitted request's logits."""
    cfg, params = model
    toks = _toks((1, 9), seed=5)[0]
    full = np.asarray(tfm.forward(params, cfg,
                                  jnp.asarray(toks)[None])[0])
    cache = engine.init_cache(3)
    # occupy slot 0 first so admission happens into a LIVE pool
    _, cache = engine.prefill_slot(cache, _toks((1, 4), seed=6)[0], 0)
    logits, cache = engine.prefill_slot(cache, toks, 2)
    np.testing.assert_allclose(np.asarray(logits), full[0, -1], atol=ATOL)
    pos = np.asarray(cache["pos"])
    assert pos[2] == 9 and pos[0] == 4 and pos[1] == 0


def test_decode_after_slot_admission_matches_oracle(model, engine):
    cfg, params = model
    toks = _toks((1, 12), seed=7)
    full = np.asarray(tfm.forward(params, cfg, jnp.asarray(toks))[0])
    cache = engine.init_cache(2)
    _, cache = engine.prefill_slot(cache, toks[0, :5], 1)
    for t in range(5, 12):
        logits, cache = engine.decode_step(
            cache, np.asarray([0, toks[0, t]], np.int32))
        np.testing.assert_allclose(np.asarray(logits)[1], full[0, t],
                                   atol=ATOL, err_msg=f"position {t}")


def test_generate_greedy_matches_forward_argmax_loop(model, engine):
    """Greedy generate == the naive recompute-everything argmax loop."""
    cfg, params = model
    prompt = _toks((1, 5), seed=9)[0]
    out = engine.generate(prompt, 8)
    ids = list(prompt)
    for _ in range(8):
        lg, _ = tfm.forward(params, cfg,
                            jnp.asarray(np.asarray(ids, np.int32))[None])
        ids.append(int(np.argmax(np.asarray(lg)[0, -1])))
    assert out.tolist() == ids[5:]
    # zoo-level entry point is the same path
    out2 = tfm.generate(params, cfg, prompt, 8)
    assert out2.tolist() == ids[5:]


def test_generate_capacity_and_shape_contract(engine):
    prompt = _toks((2, 4), seed=11)
    out = engine.generate(prompt, 5)
    assert out.shape == (2, 5)
    with pytest.raises(ValueError, match="max_len"):
        engine.generate(_toks((1, 30), seed=1)[0], 8)  # 30+8-1 > 32


# ------------------------------------------------------------- sampling

def test_sampling_deterministic_under_fixed_key(engine):
    prompt = _toks((1, 4), seed=13)[0]
    k = jax.random.PRNGKey(42)
    a = engine.generate(prompt, 10, key=k, temperature=1.0, top_k=8)
    b = engine.generate(prompt, 10, key=k, temperature=1.0, top_k=8)
    assert a.tolist() == b.tolist()
    c = engine.generate(prompt, 10, key=jax.random.PRNGKey(7),
                        temperature=1.0, top_k=8)
    assert a.tolist() != c.tolist()  # 61-way sampling, 10 draws


def test_top_k_mass_invariant():
    """Every sampled token lies in its row's top-k set, for per-row k."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 50))
    top_k = jnp.asarray([1, 3, 10, 0], jnp.int32)      # 0 = unrestricted
    temps = jnp.ones((4,), jnp.float32)
    order = np.argsort(np.asarray(logits), axis=-1)[:, ::-1]
    for i in range(64):
        toks = np.asarray(sample_tokens(jax.random.PRNGKey(i), logits,
                                        temps, top_k))
        for row, k in enumerate([1, 3, 10, 50]):
            assert toks[row] in order[row, :k], (row, k, toks[row])


def test_temperature_zero_is_argmax_and_ignores_key():
    logits = jax.random.normal(jax.random.PRNGKey(3), (5, 33))
    greedy = np.asarray(jnp.argmax(logits, -1))
    for i in range(3):
        toks = np.asarray(sample_tokens(jax.random.PRNGKey(i), logits,
                                        jnp.zeros((5,)),
                                        jnp.zeros((5,), jnp.int32)))
        assert toks.tolist() == greedy.tolist()


# ------------------------------------------------------------ scheduler

def test_scheduler_mixed_length_trace_slot_invariants(model, engine):
    """Scripted mixed-length arrival trace: occupancy never exceeds the
    pool, every future resolves, every output equals the one-shot
    greedy oracle, and the dl4j_serving_* accounting adds up."""
    reg = get_registry()
    reg.reset()
    sched = ContinuousBatchingScheduler(engine, n_slots=2)
    prompts = [_toks((1, n), seed=20 + n)[0] for n in (3, 7, 5, 9, 4, 6)]
    budgets = [5, 3, 6, 2, 4, 1]
    futs = []
    max_occ = 0.0
    for p, b in zip(prompts[:3], budgets[:3]):   # wave 1
        futs.append(sched.submit(p, max_new_tokens=b))
    for _ in range(3):
        sched.step()
        max_occ = max(max_occ, sched.occupancy())
    for p, b in zip(prompts[3:], budgets[3:]):   # wave 2 mid-flight
        futs.append(sched.submit(p, max_new_tokens=b))
    sched.run_until_idle()
    assert max_occ <= 1.0
    for p, b, f in zip(prompts, budgets, futs):
        res = f.result(timeout=5)
        assert res.finish_reason == "length"
        assert len(res.tokens) == b
        assert res.ttft_s is not None and res.ttft_s >= 0
        oracle = engine.generate(p, b)
        assert res.tokens.tolist() == oracle.tolist(), p
    assert reg.get("dl4j_serving_requests_total").value() == 6
    assert reg.get("dl4j_serving_completions_total").value(
        reason="length") == 6
    assert reg.get("dl4j_serving_tokens_total").value() == sum(budgets)
    assert reg.get("dl4j_serving_ttft_seconds").count() == 6
    assert reg.get("dl4j_serving_prefills_total").value() == 6
    # occupancy is replica-labeled now (fabric groundwork, ISSUE 11);
    # the pool is idle after run_until_idle but run_until_idle never
    # executes an idle step, so the last busy value is still visible
    assert 0 < reg.get("dl4j_serving_slot_occupancy").value(
        replica="0") <= 1.0
    # per-request inter-token latency: every request contributes
    # len(tokens) - 1 samples
    assert reg.get("dl4j_serving_itl_seconds").count() == \
        sum(b - 1 for b in budgets)


def test_scheduler_eos_stops_early(model, engine):
    """Finish-by-eos: pick as eos a token of the greedy continuation at
    an index before the budget's end where it occurs for the FIRST time
    (random weights repeat tokens, and eos stops at the first occurrence)
    — the scheduler must stop there and label the reason."""
    prompt = _toks((1, 6), seed=31)[0]
    oracle = engine.generate(prompt, 6).tolist()
    at = next(i for i in range(1, 5) if oracle[i] not in oracle[:i])
    sched = ContinuousBatchingScheduler(engine, n_slots=1)
    fut = sched.submit(prompt, max_new_tokens=6, eos_id=oracle[at])
    sched.run_until_idle()
    res = fut.result(timeout=5)
    assert res.finish_reason == "eos"
    assert res.tokens.tolist() == oracle[:at + 1]


def test_scheduler_preemption_is_output_transparent(model, engine):
    """Starvation preempts the longest-budget request; recompute
    re-admission must not change its greedy output, and the preemption
    is counted."""
    reg = get_registry()
    reg.reset()
    sched = ContinuousBatchingScheduler(engine, n_slots=1,
                                        starvation_ms=0.0)
    long_p = _toks((1, 5), seed=41)[0]
    short_p = _toks((1, 3), seed=42)[0]
    f_long = sched.submit(long_p, max_new_tokens=10)
    sched.step()                      # admit the long request
    time.sleep(0.002)
    f_short = sched.submit(short_p, max_new_tokens=2)
    time.sleep(0.002)
    sched.run_until_idle()
    r_long, r_short = f_long.result(5), f_short.result(5)
    assert r_long.preemptions >= 1
    assert reg.get("dl4j_serving_preemptions_total").value() >= 1
    assert r_long.tokens.tolist() == engine.generate(long_p, 10).tolist()
    assert r_short.tokens.tolist() == engine.generate(short_p, 2).tolist()


def test_scheduler_cancelled_future_dropped_neighbours_served(model,
                                                              engine):
    """A request cancelled while queued must cost nothing and must not
    wedge the pool: neighbours complete, the cancellation is counted."""
    reg = get_registry()
    reg.reset()
    sched = ContinuousBatchingScheduler(engine, n_slots=1)
    p1, p2 = _toks((1, 4), seed=71)[0], _toks((1, 5), seed=72)[0]
    f1 = sched.submit(p1, max_new_tokens=3)
    f2 = sched.submit(p2, max_new_tokens=3)
    assert f1.cancel()                       # still queued → cancellable
    sched.run_until_idle()
    assert f1.cancelled()
    assert f2.result(timeout=5).tokens.tolist() == \
        engine.generate(p2, 3).tolist()
    assert reg.get("dl4j_serving_completions_total").value(
        reason="cancelled") == 1
    assert reg.get("dl4j_serving_prefills_total").value() == 1  # p2 only


def test_scheduler_rejects_oversized_request(engine):
    sched = ContinuousBatchingScheduler(engine, n_slots=1)
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(_toks((1, 30), seed=1)[0], max_new_tokens=8)


def test_scheduler_background_thread(model, engine):
    sched = ContinuousBatchingScheduler(engine, n_slots=2).start()
    try:
        prompt = _toks((1, 4), seed=51)[0]
        fut = sched.submit(prompt, max_new_tokens=3)
        res = fut.result(timeout=30)
        assert res.tokens.tolist() == engine.generate(prompt, 3).tolist()
    finally:
        sched.stop()


# ------------------------------------------- SLO plane (ISSUE 11)

def test_idle_gauges_reset_after_pool_drains(model, engine):
    """Regression: occupancy/tokens-per-second were only written inside
    the decode sweep, so after the pool drained they froze at the last
    busy value — a load-aware router would keep avoiding a free
    replica. An idle step() must zero them."""
    reg = get_registry()
    reg.reset()
    sched = ContinuousBatchingScheduler(engine, n_slots=2)
    fut = sched.submit(_toks((1, 4), seed=81)[0], max_new_tokens=3)
    sched.run_until_idle()
    fut.result(timeout=5)
    occ = reg.get("dl4j_serving_slot_occupancy")
    tps = reg.get("dl4j_serving_tokens_per_second")
    assert occ.value(replica="0") > 0          # frozen busy reading
    assert tps.value(replica="0") > 0
    assert sched.step() is False               # fully idle iteration
    assert occ.value(replica="0") == 0.0
    assert tps.value(replica="0") == 0.0


def test_preempted_request_trace_spans_and_itl(model, engine):
    """Trace assembly under adversity: a preempted-and-resumed request's
    timeline records the admission, BOTH prefills and the requeue gap —
    and the gap is one of its ITL samples (the stall its caller actually
    saw, invisible to per-sweep timing)."""
    from deeplearning4j_tpu.obs import get_tracer
    reg = get_registry()
    reg.reset()
    tracer = get_tracer()
    tracer.clear()
    sched = ContinuousBatchingScheduler(engine, n_slots=1,
                                        starvation_ms=0.0)
    long_p = _toks((1, 5), seed=41)[0]
    short_p = _toks((1, 3), seed=42)[0]
    f_long = sched.submit(long_p, max_new_tokens=10)
    sched.step()                      # admit the long request
    time.sleep(0.002)
    f_short = sched.submit(short_p, max_new_tokens=2)
    time.sleep(0.002)
    sched.run_until_idle()
    assert f_long.result(5).preemptions >= 1
    f_short.result(5)

    traces = {t.request_id: t for t in sched.flight_recorder.requests()}
    tr = traces[0]                    # the long request submitted first
    assert len(tr.all("prefill")) == 2          # admission + re-admission
    assert len(tr.all("admit")) == 2
    assert len(tr.all("preempt")) == 1 and len(tr.all("requeue")) == 1
    assert tr.finish_reason() == "length" and tr.n_tokens() == 10
    # the requeue gap (last pre-preempt token -> first post-readmit
    # token) is exactly one of the ITL samples
    toks = tr.token_timestamps()
    t_pre = tr.all("preempt")[0][1]
    t_resume = tr.all("prefill")[1][1]
    before = max(t for t in toks if t <= t_pre)
    after = min(t for t in toks if t >= t_resume)
    gap = after - before
    itl = tr.itl_samples()
    assert len(itl) == 9
    assert any(abs(s - gap) < 1e-9 for s in itl)
    assert max(itl) >= gap            # nothing in-stream beats the stall
    # the ITL histogram saw every sample of both requests
    assert reg.get("dl4j_serving_itl_seconds").count() == 9 + 1

    # span tree: request root -> one serving.prefill per admission ->
    # token events parented to their own admission segment
    spans = [s for s in tracer.spans() if s.trace_id == tr.trace_id()]
    roots = [s for s in spans if s.name == "serving.request"]
    assert len(roots) == 1 and roots[0].parent_id is None
    root = roots[0]
    assert root.attrs["preemptions"] == 1
    prefills = sorted((s for s in spans if s.name == "serving.prefill"),
                      key=lambda s: s.attrs["admission"])
    assert len(prefills) == 2
    assert all(s.parent_id == root.span_id for s in prefills)
    tokens = sorted((s for s in spans if s.name == "serving.token"),
                    key=lambda s: s.attrs["i"])
    assert len(tokens) == 10
    # first segment's tokens hang off prefill 0, the rest off prefill 1
    seg_parents = {s.parent_id for s in tokens}
    assert seg_parents == {prefills[0].span_id, prefills[1].span_id}


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_serve_loop_crash_dumps_flight_recorder(model, engine, tmp_path,
                                                monkeypatch):
    """An injected serve-loop crash must fail every future AND leave a
    black box: a JSONL dump whose crash snapshot lists exactly the
    doomed request ids and whose traces carry the terminal fail."""
    from deeplearning4j_tpu.obs import load_flight_records
    dump = tmp_path / "blackbox.jsonl"
    sched = ContinuousBatchingScheduler(engine, n_slots=1,
                                        crash_dump_path=str(dump))
    f1 = sched.submit(_toks((1, 4), seed=91)[0], max_new_tokens=4)
    sched.step()                      # admit into slot 0 (healthy)
    f2 = sched.submit(_toks((1, 5), seed=92)[0], max_new_tokens=4)

    def boom(cache, tokens):
        raise RuntimeError("injected decode crash")
    monkeypatch.setattr(sched.engine, "decode_step", boom)
    sched.start(poll_s=0.001)
    with pytest.raises(RuntimeError, match="injected decode crash"):
        f1.result(timeout=30)
    with pytest.raises(RuntimeError):
        f2.result(timeout=30)
    sched._thread.join(timeout=30)    # dump written before the re-raise

    recs = load_flight_records(dump)
    assert any(r["kind"] == "flightrec" and r["reason"] == "fail_all"
               for r in recs)
    snaps = [r for r in recs if r["kind"] == "snapshot"]
    crash = [s for s in snaps if s.get("crash")]
    assert crash, snaps
    last = crash[-1]
    # the crash snapshot matches the failed futures: slot 0 held
    # request 0, request 1 was still queued
    assert last["slots"] == [0] and last["queue"] == [1]
    assert "injected decode crash" in last["error"]
    traces = [r for r in recs if r["kind"] == "reqtrace"]
    assert {t["request_id"] for t in traces} == {0, 1}
    assert all(t["summary"]["status"] == "fail" for t in traces)


def test_scheduler_with_slo_is_output_transparent_and_reports(model,
                                                              engine):
    """Acceptance (ISSUE 11): with the recorder, span assembly, ITL
    tracing AND an SLOTracker enabled, greedy scheduler output is
    bit-identical to generate(), and the SLO report carries goodput /
    ITL verdicts with replica-labeled gauges behind it."""
    from deeplearning4j_tpu.serving import SLOConfig
    reg = get_registry()
    reg.reset()
    sched = ContinuousBatchingScheduler(
        engine, n_slots=2, slo=SLOConfig(ttft_s=60.0, itl_s=60.0))
    prompts = [_toks((1, n), seed=100 + n)[0] for n in (3, 6, 4)]
    futs = [sched.submit(p, max_new_tokens=5) for p in prompts]
    sched.run_until_idle()
    for p, f in zip(prompts, futs):
        assert f.result(5).tokens.tolist() == \
            engine.generate(p, 5).tolist()
    rep = sched.slo.report()
    assert rep["window"]["requests"] == 3
    assert rep["goodput"] == 1.0 and rep["error_rate"] == 0.0
    assert rep["burn_rate"] == 0.0 and rep["met"] is True
    assert rep["itl"]["samples"] == 3 * 4 and rep["itl"]["p99_s"] > 0
    assert reg.get("dl4j_slo_goodput_ratio").value(replica="0") == 1.0
    assert reg.get("dl4j_slo_window_requests").value(replica="0") == 3
    # the flight recorder kept every trace and the debug state sees SLO
    dbg = sched.flight_recorder.debug_state()
    assert dbg["requests_recorded"] == 3
    assert dbg["slo"]["goodput"] == 1.0


def test_trace_overhead_within_budget():
    """Documented budget (the MetricsListener precedent): the SLO-plane
    bookkeeping — trace events, snapshots, close-out — self-times, and
    must cost <2% of the tier-1 CPU decode sweep's wall clock with
    everything enabled. Like test_obs's listener-budget test, this uses
    a deliberately non-trivial config: against a microscopic model the
    percentage measures Python noise, not the budget."""
    from deeplearning4j_tpu.serving import SLOConfig
    cfg = tiny_cfg(vocab_size=512, d_model=256, n_heads=4, n_layers=4,
                   d_ff=512, max_seq=64)
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    eng = GenerationEngine(cfg, params)
    sched = ContinuousBatchingScheduler(eng, n_slots=4, slo=SLOConfig())
    # compile outside the window
    sched.submit(_toks((1, 4), vocab=512, seed=110)[0], max_new_tokens=2)
    sched.run_until_idle()
    # best-of-3 waves: the budget is about inherent cost; a loaded CI
    # host can only inflate a sample, never deflate it
    ratios = []
    for attempt in range(3):
        base = sched.trace_overhead_seconds
        futs = [sched.submit(_toks((1, 3 + (i % 4)), vocab=512,
                                   seed=120 + 10 * attempt + i)[0],
                             max_new_tokens=24)
                for i in range(8)]
        t0 = time.perf_counter()
        sched.run_until_idle()
        wall = time.perf_counter() - t0
        for f in futs:
            f.result(timeout=5)
        ratios.append((sched.trace_overhead_seconds - base) / wall)
        if ratios[-1] < 0.02:
            break
    assert min(ratios) < 0.02, (
        f"SLO-plane bookkeeping cost "
        f"{[f'{100 * r:.2f}%' for r in ratios]} of serve wall across "
        f"{len(ratios)} waves — every wave over the 2% budget")


def test_debug_endpoints_serve_flight_recorder(model, engine):
    """GET /debug/serving and /debug/requests on the UI server expose
    the live black box next to /metrics."""
    import json
    import urllib.request
    from deeplearning4j_tpu.ui import UIServer
    sched = ContinuousBatchingScheduler(engine, n_slots=1,
                                        replica="dbg")
    fut = sched.submit(_toks((1, 4), seed=130)[0], max_new_tokens=3)
    sched.run_until_idle()
    fut.result(timeout=5)
    srv = UIServer(log_dir="runs/_dbg_test", port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        serving = json.loads(urllib.request.urlopen(
            base + "/debug/serving", timeout=10).read())
        mine = [r for r in serving["replicas"] if r["replica"] == "dbg"]
        assert mine and mine[0]["requests_recorded"] == 1
        assert mine[0]["queue_depth"] == 0 and mine[0]["occupancy"] == 0
        reqs = json.loads(urllib.request.urlopen(
            base + "/debug/requests?replica=dbg&n=5", timeout=10).read())
        assert len(reqs["requests"]) == 1
        rec = reqs["requests"][0]
        assert rec["kind"] == "reqtrace"
        assert rec["summary"]["status"] == "finish"
        assert rec["summary"]["tokens"] == 3
        names = [e[0] for e in rec["events"]]
        assert names[:3] == ["submit", "queue", "admit"]
        assert names.count("token") == 3 and names[-1] == "finish"
    finally:
        srv.stop()


# -------------------------------- ParallelInference satellites (ISSUE 10)

def _mlp_net():
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train import Adam
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_in=6, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init((6,))


def test_parallel_inference_deadline_flush():
    """A trickle below max_batch flushes at the max_wait_ms deadline —
    the request's future resolves without anyone calling flush()."""
    from deeplearning4j_tpu.parallel import ParallelInference
    net = _mlp_net()
    pi = ParallelInference(net, max_batch=64, max_wait_ms=30)
    fut = pi.submit(np.random.default_rng(0)
                    .normal(size=(4, 6)).astype(np.float32))
    out = fut.result(timeout=30)
    assert out.shape == (4, 3)
    assert pi._pending == [] and pi._timer is None
    assert get_registry().get(
        "dl4j_inference_deadline_flushes_total").value() >= 1


def test_parallel_inference_threshold_flush_keeps_legacy_contract():
    from deeplearning4j_tpu.parallel import ParallelInference
    net = _mlp_net()
    pi = ParallelInference(net, max_batch=8, max_wait_ms=10_000)
    f1 = pi.submit(np.zeros((4, 6), np.float32))
    parts = pi.submit(np.ones((4, 6), np.float32))
    assert isinstance(parts, list) and len(parts) == 2  # inline flush
    assert f1.done() and f1.result().shape == (4, 3)
    assert pi._timer is None            # deadline timer cancelled


def test_parallel_inference_cancelled_future_doesnt_starve_batch():
    """One caller cancelling its queued request must not stop the other
    futures in the same dynamic batch from resolving."""
    from deeplearning4j_tpu.parallel import ParallelInference
    net = _mlp_net()
    pi = ParallelInference(net, max_batch=64)
    f1 = pi.submit(np.zeros((2, 6), np.float32))
    f2 = pi.submit(np.ones((3, 6), np.float32))
    assert f1.cancel()
    parts = pi.flush()
    assert len(parts) == 2            # rows still computed and returned
    assert f2.result(timeout=5).shape == (3, 3)
    assert f1.cancelled()


def test_parallel_inference_mixed_shape_raises():
    from deeplearning4j_tpu.parallel import ParallelInference
    net = _mlp_net()
    pi = ParallelInference(net, max_batch=64)
    pi.submit(np.zeros((2, 6), np.float32))
    with pytest.raises(ValueError, match="mixed-shape"):
        pi.submit(np.zeros((2, 7), np.float32))
    # the well-shaped pending request is still servable
    assert len(pi.flush()) == 1


def test_functional_adapter_serves_bert_through_parallel_inference(model):
    """FunctionalInferenceModel: the functional BERT encoder runs
    through the dynamic-batching front end like any net."""
    from deeplearning4j_tpu.parallel import ParallelInference
    cfg = tfm.BertConfig(vocab_size=40, d_model=16, n_heads=2, n_layers=1,
                         d_ff=32, max_seq=8, dtype=jnp.float32)
    params = tfm.bert_init(jax.random.PRNGKey(0), cfg)
    bert = FunctionalInferenceModel(
        params, lambda p, ids: tfm.bert_forward(p, cfg, ids)[0])
    pi = ParallelInference(bert, max_batch=4)
    ids = _toks((2, 8), vocab=40, seed=61)
    direct = np.asarray(tfm.bert_forward(params, cfg, jnp.asarray(ids))[0])
    out = pi.output(ids)
    np.testing.assert_allclose(out, direct, atol=1e-5)


def test_clean_interpreter_exit_with_live_serving_threads():
    """Regression: an armed deadline timer or a live serve thread caught
    mid-dispatch while jax tears down used to abort the interpreter
    (std::terminate, rc=134). The atexit drains must make this exit 0."""
    import subprocess
    import sys
    code = """
import jax
import numpy as np, jax.numpy as jnp
from deeplearning4j_tpu.zoo import transformer as tfm
from deeplearning4j_tpu.parallel import ParallelInference
from deeplearning4j_tpu.serving import (FunctionalInferenceModel,
    GenerationEngine, ContinuousBatchingScheduler)
bcfg = tfm.BertConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                      d_ff=32, max_seq=8, dtype=jnp.float32)
bp = tfm.bert_init(jax.random.PRNGKey(1), bcfg)
pi = ParallelInference(FunctionalInferenceModel(
    bp, lambda p, ids: tfm.bert_forward(p, bcfg, ids)[0]),
    max_batch=64, max_wait_ms=40)
pi.submit(np.zeros((2, 8), np.int32))          # timer armed
cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq=16,
                            dtype=jnp.float32, attn_scores_bf16=False)
sp = tfm.init_params(jax.random.PRNGKey(0), cfg)
sched = ContinuousBatchingScheduler(GenerationEngine(cfg, sp),
                                    n_slots=2).start()
sched.submit([1, 2], max_new_tokens=4)         # serve thread live
print("exiting hot")
"""
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, (proc.returncode, proc.stderr[-500:])
    assert "exiting hot" in proc.stdout


# -------------------------------------------------------------- tooling

def test_serving_metric_names_pass_lint():
    """All dl4j_serving_* sites pass the repo metric-name lint (and at
    least the core names are actually registered by a scheduler run)."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    try:
        import check_metric_names
    finally:
        sys.path.pop(0)
    serving = pathlib.Path(__file__).resolve().parent.parent / \
        "deeplearning4j_tpu" / "serving"
    errors = check_metric_names.check(
        files=sorted(serving.rglob("*.py")))
    assert errors == [], errors

"""Benchmarks: ResNet-50 headline + SURVEY §6 secondary configs, MFU-audited.

Prints ONE JSON line on stdout (the headline, BASELINE.json contract):
  {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N,
   "flops_per_step": ..., "derived_tflops": ..., "mfu": ..., ...}

The headline routes through the REAL user entry point —
``ComputationGraph.fit(DataSetIterator)`` (VERDICT r2 item 1): iterator
protocol, async-wrap policy, optimizer build, donated jitted step and
listener plumbing all engaged. Batches are pre-staged on device (DataSet
keeps jax Arrays device-resident, like the reference's INDArray-backed
DataSet), so the row times the framework and not an input pipeline.
`resnet50_rawstep` keeps the hand-built-step variant for comparison.

One process per chip: a chip belongs to the process that first touches
JAX, so the parent (`python bench.py`, `--refresh`) imports no JAX and
runs EVERY row, the headline included, as a `--model NAME` child. A child
that finds no TPU exits NO_TPU_RC, and the parent then stops non-zero with
the artifact untouched: there is no CPU pass and no "unavailable" record.

Methodology:
- Every timed region ends in a scalar device->host fetch, which waits for
  the chained steps it depends on.
- Throughput is computed from the MARGINAL step time between two
  chained-step counts (t(n2)-t(n1))/(n2-n1), which cancels the fixed cost
  of the fetch and the dispatch ramp.
- Steps are data-dependent (params/opt-state carried through), so the chain
  cannot be reordered or elided.
- Every record carries analytic FLOPs/step (jaxpr walk, MXU ops only — see
  utils/tracing.py), derived TFLOP/s, and MFU vs the device kind's bf16
  peak (obs/floors.py PEAKS). An MFU > 1 is physically impossible and flags
  the record `timing_valid: false`.

Secondary configs (LeNet bf16, char-RNN, BERT fine-tune, Transformer-LM,
dp-8 overhead) run after the headline and are written to `bench_secondary.json`
(stderr progress only, stdout stays one line). `--model NAME [batch steps]`
runs a single config and prints its record alone.

Serving-plane configs (ISSUE 10: KV-cache decode tokens/s, TTFT at
T=1024/4096 prefill, ResNet/BERT batch-1 p50/p99 + best-batch throughput
through ParallelInference) run last into the artifact's `inference`
section (`bench.py --refresh inference_decode,...` re-captures rows).

Reference parity: DL4J's published ResNet-50 V100 cuDNN number (~360 img/s)
is the `vs_baseline` denominator — see BASELINE.md.

Longitudinal trend plane (ISSUE 15): every captured row also appends a
keyed record to `runs/perf_ledger.jsonl` (atomic single-write line; see
`deeplearning4j_tpu/obs/trend.py`). `scripts/perf_gate.py` replays the
ledger into per-row trend verdicts (stable/improved/regressed/unstable/
bimodal) and gates CI on out-of-band regressions vs a pinned baseline.
"""

from __future__ import annotations

import functools
import json
import sys
import time

BASELINE_SAMPLES_PER_SEC = 360.0  # DL4J ResNet-50 V100 cuDNN (BASELINE.md)
NO_TPU_RC = 3   # a `--model` child's exit code when jax finds no TPU

# Activation-remat policy for the ResNet configs (None = off; int = number
# of jax.checkpoint segments). Set from the diag_resnet G/H sweep when the
# measured winner beats the monolithic forward on-chip.
RESNET_REMAT = None


def _git_sha():
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, cwd=__import__("os").path.dirname(
                __import__("os").path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — provenance stamp, never fatal
        return None


def _stamp(rec):
    """Provenance: every record carries capture time + repo SHA + the
    device jax ran it on, so a stale artifact can never masquerade as
    current (the r3 failure mode) and a CPU rehearsal never as a chip."""
    import datetime

    import jax
    rec.setdefault("captured_at",
                   datetime.datetime.now(datetime.timezone.utc).isoformat(
                       timespec="seconds"))
    rec.setdefault("git_sha", _git_sha())
    rec.setdefault("backend", jax.default_backend())
    rec.setdefault("device_kind", jax.devices()[0].device_kind)
    rec.setdefault("device_count", jax.device_count())
    return rec


DPOVERHEAD_METRIC = "dp-8 per-step overhead vs single device (virtual CPU mesh)"


def _peak_flops(dtype="bf16"):
    """Attainable peak for the config's compute dtype on THIS device kind,
    from the one table obs/floors.py owns (an unknown kind raises there).
    None on the nominal CPU entry: no MFU is quoted against a made-up
    host number."""
    from deeplearning4j_tpu.obs import floors
    peaks = floors.device_peaks()
    if peaks.get("nominal"):
        return None
    return peaks["flops"]["bf16" if dtype == "bf16" else "f32"]


def _fetch(x):
    """Device->host fetch of one scalar: waits for everything it depends on."""
    import jax.numpy as jnp
    return float(jnp.asarray(x).reshape(-1)[0])


def measure_marginal(run_chain, n1=5, n2=25, repeats=2):
    """Marginal per-step seconds of `run_chain(n) -> fetchable`, best of
    `repeats` at each count (cancels the fixed cost of the fetch)."""
    n2 = max(n2, n1 + 2)
    _fetch(run_chain(2))  # compile + warmup
    t_at = {}
    for n in (n1, n2):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            _fetch(run_chain(n))
            best = min(best, time.perf_counter() - t0)
        t_at[n] = best
    per_step = (t_at[n2] - t_at[n1]) / (n2 - n1)
    # A non-positive marginal means the measurement is garbage (noise beat
    # the signal): report it as invalid rather than a clamped huge number.
    return max(per_step, 1e-9), per_step > 0


SUB_MS_S = 1e-3      # below this, single captures moved 6x intra-day
STABILITY_K = 5      # median-of-k pair captures for sub-ms rows
UNSTABLE_REL_IQR = 0.25   # IQR/median above this flags the row


def measure_stable(run_chain, n1=5, n2=25, repeats=2, k=STABILITY_K):
    """measure_marginal + stability discipline for sub-millisecond rows
    (the lenet row moved 6x intra-day across captures — docs/PERF.md):
    when the first marginal estimate lands under 1 ms, capture k
    independent (n1, n2) pairs, quote the MEDIAN, and flag the row
    ``unstable`` when the relative IQR exceeds 25% — so floors quote
    against a stable denominator or say loudly that none exists.
    Returns (per_step_s, valid, stability_dict_or_None)."""
    per_step, valid = measure_marginal(run_chain, n1, n2, repeats)
    if not valid or per_step >= SUB_MS_S or k <= 1:
        return per_step, valid, None
    samples = [per_step]
    for _ in range(k - 1):
        s, ok = measure_marginal(run_chain, n1, n2, repeats=1)
        if ok:
            samples.append(s)
    samples.sort()
    import statistics
    med = float(statistics.median(samples))
    n = len(samples)
    q25 = samples[max(0, round(0.25 * (n - 1)))]
    q75 = samples[min(n - 1, round(0.75 * (n - 1)))]
    iqr_rel = (q75 - q25) / med if med > 0 else float("inf")
    stability = {
        "median_of_k": n,
        "step_time_ms_samples": [round(s * 1e3, 4) for s in samples],
        "iqr_rel": round(iqr_rel, 4),
        "unstable": bool(iqr_rel > UNSTABLE_REL_IQR),
    }
    # bimodality verdict inline (ISSUE 15): when the retained samples
    # split into two tight modes, the median above is NOT a stable
    # denominator — record the per-cluster medians beside it (the
    # machine form of the T=4096 "82–152k across sessions" prose).
    # min_cluster=2: within one capture a mode must RECUR — a lone
    # host-jitter outlier among k samples is the `unstable`/median
    # discipline's problem, not a second mode
    try:
        from deeplearning4j_tpu.obs import trend
        split = trend.split_clusters(samples, min_cluster=2)
        stability["bimodal"] = split is not None
        if split is not None:
            stability["cluster_medians_ms"] = [
                round(split["lo_median"] * 1e3, 4),
                round(split["hi_median"] * 1e3, 4)]
    except Exception:  # noqa: BLE001 — the verdict is decoration
        pass
    return med, True, stability


def chain_runner(step_once, carry):
    """Chained-step closure shared by every config: `step_once(*carry) ->
    (new_carry, loss)`. Steps are data-dependent through `carry`, and because
    the jitted steps donate their state args, `carry` is updated in place so
    no call ever re-reads a donated buffer."""

    def run_chain(n):
        c, loss = tuple(carry), None
        for _ in range(n):
            c, loss = step_once(*c)
        carry[:] = c
        return loss

    return run_chain


def _record(metric, unit, samples_per_step, timing, flops_per_step,
            dtype="bf16", probe=None, **extra):
    per_step_s, valid = timing[0], timing[1]
    stability = timing[2] if len(timing) > 2 else None
    peak = _peak_flops(dtype)
    tflops = flops_per_step / per_step_s / 1e12
    rec = {
        "metric": metric,
        "value": round(samples_per_step / per_step_s, 2),
        "unit": unit,
        "step_time_ms": round(per_step_s * 1e3, 3),
        "flops_per_step": int(flops_per_step),
        "derived_tflops": round(tflops, 2),
        "compute_dtype": dtype,
        "peak_tflops_assumed": None if peak is None else peak / 1e12,
        "mfu": None if peak is None else round(flops_per_step / per_step_s / peak, 4),
        "timing": "marginal chained steps, host-fetch synced",
    }
    if stability is not None:
        rec.update(stability)   # median_of_k / samples / iqr_rel / unstable
    if not valid or (rec["mfu"] is not None and rec["mfu"] > 1.0):
        rec["timing_valid"] = False
    rec.update(extra)
    _emit_row_metrics(rec)
    _attach_floor(rec, probe, dtype,
                  per_step_s if rec.get("timing_valid", True) else None)
    return _stamp(rec)


def _attach_floor(rec, probe, dtype, per_step_s):
    """Roofline floor block (ISSUE 7): derive HLO flops/bytes for the
    row's jitted step via the probe the builder attached to its
    run_chain (``floor_probe``: cost_analysis with estimator fallback,
    lowered from shape structs so donation can't bite), combine with the
    per-backend peak table and record floor_ms / pct_of_floor /
    binding_resource / lever-or-ok verdict beside the row. Never fatal —
    a floor failure must not cost a captured row."""
    fp = getattr(probe, "floor_probe", None)
    if fp is None:
        return
    try:
        from deeplearning4j_tpu.obs import floors
        costs = fp()
        step_ms = None if per_step_s is None else per_step_s * 1e3
        block = floors.floor_block(costs, step_ms=step_ms, dtype=dtype)
        rec["floor"] = block
        try:
            m = floors.emit_floor_metrics(rec["metric"], block)
            if m and isinstance(rec.get("metrics"), dict):
                rec["metrics"].update(m)
        except Exception:  # noqa: BLE001 — gauge mirror is decoration
            pass
    except Exception as e:  # noqa: BLE001 — the row survives floorless
        rec["floor"] = {"na": f"floor derivation failed: "
                              f"{type(e).__name__}: {e}"[:300]}


def _emit_row_metrics(rec):
    """Telemetry-plane mirror of a bench row: observe the row into the
    process-wide dl4j_ registry AND embed the same schema beside the
    record, so the floor table (ROADMAP item 5) and a live /metrics
    scrape read identical names. Never fatal — a telemetry failure must
    not cost a captured row."""
    try:
        from deeplearning4j_tpu.obs import get_registry
        reg = get_registry()
        config = rec["metric"]
        step_s = rec["step_time_ms"] / 1e3
        reg.histogram("dl4j_bench_step_seconds",
                      "Measured marginal step time per bench row",
                      labelnames=("config",)).observe(step_s, config=config)
        reg.gauge("dl4j_bench_throughput",
                  "Bench row value in the row's own unit",
                  labelnames=("config", "unit")).set(
            rec["value"], config=config, unit=rec["unit"])
        metrics = {"dl4j_bench_step_seconds": step_s,
                   "dl4j_bench_throughput": rec["value"]}
        if rec.get("mfu") is not None:
            reg.gauge("dl4j_bench_mfu",
                      "Bench row model-flops utilization",
                      labelnames=("config",)).set(rec["mfu"], config=config)
            metrics["dl4j_bench_mfu"] = rec["mfu"]
        rec["metrics"] = metrics
    except Exception:  # noqa: BLE001 — decoration only
        pass


def _mln_chain(net, x, y):
    """Chained-train-step runner for a MultiLayerNetwork + its analytic FLOPs."""
    import jax
    from deeplearning4j_tpu.utils.tracing import total_flops

    net._build_optimizer(1)
    step = net._get_train_step()
    rng = jax.random.PRNGKey(0)
    flops = total_flops(
        lambda p, s, o: step.__wrapped__(p, s, o, x, y, rng, None, None)[:3],
        net.params, net.states, net._opt_state)

    def step_once(p, s, o, k):
        p, s, o, loss, _, k = step(p, s, o, x, y, k, None, None)
        return (p, s, o, k), loss

    run_chain = chain_runner(step_once, [net.params, net.states,
                                         net._opt_state, rng])
    run_chain.floor_probe = _make_floor_probe(
        step, net.params, net.states, net._opt_state, x, y, rng, None, None)
    return run_chain, flops


def _make_floor_probe(jitted_step, *args, extra_flops=0):
    """Zero-arg closure returning {flops, bytes, source} for one step.
    Shapes are captured NOW (ShapeDtypeStructs) because the chain will
    donate these very buffers; lowering needs avals, not data.
    ``extra_flops`` tops up work invisible to both cost_analysis and the
    jaxpr estimator (pallas kernels)."""
    from deeplearning4j_tpu.obs import floors
    shapes = floors.shape_probe(args)

    def probe():
        costs = floors.hlo_costs(jitted_step, *shapes)
        if extra_flops and "flops" in costs:
            costs["flops"] += extra_flops
        return costs

    return probe


def build_lenet(batch, compute_dtype="bf16"):
    """(run_chain, flops) for the LeNet config — importable by tests so the
    bench code path compiles in CI, not only at round end. Runs the mixed
    bf16 policy by default (params f32, compute bf16 — the framework's
    recommended TPU config); pass compute_dtype=None for the pure-f32
    DL4J-default comparison."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import LeNet

    cd = jnp.bfloat16 if compute_dtype == "bf16" else None
    net = LeNet(num_classes=10, compute_dtype=cd).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 28, 28, 1), np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    return _mln_chain(net, x, y)


def build_lenet_scan(batch, compute_dtype="bf16"):
    """(run_chain, flops) for the SCANNED LeNet fit: fit_scanned runs the
    epoch as one lax.scan dispatch, so the marginal per-step time is pure
    device compute — the dispatch overhead that dominates a ~1 ms model
    is paid once per chain call. Same step math as
    fit() (bit-identical trajectory, tests/test_fit_scanned.py)."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo import LeNet

    cd = jnp.bfloat16 if compute_dtype == "bf16" else None
    net = LeNet(num_classes=10, compute_dtype=cd).init()
    rng = np.random.default_rng(0)
    # a few distinct device-resident batches, reused cyclically
    dss = [DataSet(jnp.asarray(rng.random((batch, 28, 28, 1), np.float32)),
                   jnp.asarray(np.eye(10, dtype=np.float32)[
                       rng.integers(0, 10, batch)]))
           for _ in range(4)]
    net._build_optimizer(1)
    step = net._get_train_step()
    rng0 = __import__("jax").random.PRNGKey(0)
    flops = total_flops(
        lambda p, s, o: step.__wrapped__(
            p, s, o, dss[0].features, dss[0].labels, rng0, None, None)[:3],
        net.params, net.states, net._opt_state)

    def run_chain(n):
        return net.fit_scanned([dss[i % len(dss)] for i in range(n)])

    # floor of the per-step work (the scan dispatches K of these)
    run_chain.floor_probe = _make_floor_probe(
        step, net.params, net.states, net._opt_state,
        dss[0].features, dss[0].labels, rng0, None, None)
    return run_chain, flops


def bench_lenet_scan(batch, steps):
    run_chain, flops = build_lenet_scan(batch, compute_dtype="bf16")
    timing = measure_stable(run_chain, n1=5, n2=steps)
    return _record(
        "LeNet MNIST fit_scanned samples/sec/chip (bf16, scan-dispatch)",
        "samples/sec/chip", batch, timing, flops, dtype="bf16",
        probe=run_chain, batch=batch)


def bench_lenet(batch, steps):
    run_chain, flops = build_lenet(batch, compute_dtype="bf16")
    timing = measure_stable(run_chain, n1=5, n2=steps)
    return _record("LeNet MNIST train-step samples/sec/chip (bf16)",
                   "samples/sec/chip", batch, timing, flops, dtype="bf16",
                   probe=run_chain, batch=batch)


def build_charnn(batch, seq=60, vocab=77, compute_dtype="bf16"):
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationLSTM

    cd = jnp.bfloat16 if compute_dtype == "bf16" else None
    net = TextGenerationLSTM(num_classes=vocab, input_shape=(seq, vocab),
                             compute_dtype=cd).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[
        rng.integers(0, vocab, (batch, seq))])
    return _mln_chain(net, x, y)


def bench_charnn(batch, steps, compute_dtype="bf16"):
    seq = 60
    run_chain, flops = build_charnn(batch, seq=seq,
                                    compute_dtype=compute_dtype)
    timing = measure_stable(run_chain, n1=5, n2=steps)
    return _record(
        f"GravesLSTM char-RNN train-step tokens/sec/chip ({compute_dtype})",
        "tokens/sec/chip", batch * seq, timing, flops,
        dtype=compute_dtype, probe=run_chain, batch=batch, seq=seq)


def bench_charnn_f32(batch, steps):
    """Pure-f32 variant kept for the bf16-vs-f32 delta record."""
    return bench_charnn(batch, steps, compute_dtype="f32")


def build_bert(batch, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo import transformer as tfm

    key = jax.random.PRNGKey(0)
    params = tfm.bert_init(key, cfg)
    opt = optax.adamw(2e-5)
    opt_state = opt.init(params)

    def step(params, opt_state, ids, labels):
        loss, grads = jax.value_and_grad(tfm.bert_classifier_loss)(
            params, cfg, ids, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    jstep = jax.jit(step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    labels = jnp.asarray(rng.integers(0, cfg.num_labels, batch))
    flops = total_flops(step, params, opt_state, ids, labels)

    def step_once(p, o):
        p, o, loss = jstep(p, o, ids, labels)
        return (p, o), loss

    run_chain = chain_runner(step_once, [params, opt_state])
    run_chain.floor_probe = _make_floor_probe(jstep, params, opt_state,
                                              ids, labels)
    return run_chain, flops


def bench_bert(batch, steps):
    from deeplearning4j_tpu.zoo import transformer as tfm
    # r5 composition sweep (scripts/diag_bert_out.json): remat-full +
    # bf16-scores frees enough HBM for b128, MFU 0.40 -> 0.61 (b32 base
    # 0.40; b32 remat+bf16s 0.49; b64 0.59; b128 0.61)
    cfg = tfm.BertConfig(max_seq=128, remat=True, attn_scores_bf16=True)
    run_chain, flops = build_bert(batch, cfg)
    timing = measure_stable(run_chain, n1=3, n2=steps)
    return _record(
        "BERT-base fine-tune seq/sec/chip (T=128, remat-full bf16-scores)",
        "seq/sec/chip", batch, timing, flops, probe=run_chain,
        batch=batch, seq=cfg.max_seq)


def build_transformer(batch, cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo import transformer as tfm

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(key, cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    raw_step = tfm.make_train_step(cfg, opt)
    jstep = jax.jit(raw_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    tgt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq)))
    flops = total_flops(raw_step, params, opt_state, ids, tgt)
    # total_flops counts jaxpr dots — it cannot see inside a pallas_call,
    # so when the flash kernel engages the attention matmuls are missing
    # from the trace and every flash config at equal tokens/step traces
    # the same count. Add the kernel's analytic train-path flops (fwd 2 +
    # dq pass 3 + dkv pass 4 = 9 matmuls of 2*B*H*T*T*D, halved causal)
    # so flash-row MFU counts the T^2 work actually done. The engagement
    # test is the model's own gate (tfm.flash_engages), not a copy.
    # Known asymmetry (ADVICE r5 #2): under remat "full" the pallas fwd
    # re-runs to rebuild vjp residuals (~2 extra matmuls/layer; "save_attn"
    # keeps them and does not), which this top-up does NOT count — while
    # the XLA path's remat recompute IS in the jaxpr and counted. Flash rows'
    # MFU is therefore slightly UNDERstated relative to XLA rows when
    # cfg.remat is on; left uncounted deliberately (conservative skew —
    # the flash wins in PERF.md survive the handicap).
    t = cfg.max_seq
    flash_flops = 0
    if tfm.flash_engages(cfg, t):
        per_matmul = 0.5 * 2.0 * batch * cfg.n_heads * t * t * cfg.head_dim
        flash_flops = 9 * per_matmul * cfg.n_layers
        flops += flash_flops

    def step_once(p, o):
        p, o, loss = jstep(p, o, ids, tgt)
        return (p, o), loss

    run_chain = chain_runner(step_once, [params, opt_state])
    # the pallas flash kernel is opaque to cost_analysis AND the jaxpr
    # estimator — top the floor's flops up by the same analytic count
    # the MFU audit uses, so floor and MFU quote one flops accounting
    run_chain.floor_probe = _make_floor_probe(
        jstep, params, opt_state, ids, tgt, extra_flops=flash_flops)
    return run_chain, flops


def bench_transformer(batch, steps):
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    # r5 winner (scripts/diag_attn_r5_out.json): flash attention at the
    # grad-tuned flash5 blocks + remat that pins the attention outputs
    # ("save_attn") + fused chunked CE. At T=1024 b16 this measured 221k
    # tokens/s vs 187k for the r4 bf16-scores XLA config; b32 flash was
    # 223k. attn_scores_bf16 stays True for the off-TPU/multichip
    # fallback path (flash is single-chip TPU only).
    cfg = tfm.TransformerConfig(vocab_size=32000, d_model=512, n_heads=8,
                                n_layers=8, d_ff=2048, max_seq=1024,
                                dtype=jnp.bfloat16, fused_loss=True,
                                remat=True, remat_policy="save_attn",
                                attn_scores_bf16=True)
    run_chain, flops = build_transformer(batch, cfg)
    timing = measure_stable(run_chain, n1=3, n2=steps)
    return _record(
        "Transformer-LM (120M, T=1024, flash save-attn remat) tokens/sec/chip",
        "tokens/sec/chip", batch * cfg.max_seq, timing, flops,
        probe=run_chain, batch=batch, seq=cfg.max_seq)


def bench_transformer_long(batch, steps):
    """Long-context config: T=4096 at the same tokens/step as the T=1024
    config. This is the regime the pallas flash kernel exists for — the
    (B,H,T,T) score tensor the XLA path materializes is 1.6 GB bf16 per
    layer here, while the flash kernel streams it through VMEM. The r4
    0.057-MFU cliff was the fwd-only autotuner picking 128×128 blocks
    (34 ms/layer fwd+bwd vs 6.1 ms at 1024×1024 — diag_t4096 phase F);
    with grad-tuned flash5 blocks the r5 sweep measured 160k tokens/s
    remat-OFF (activations fit HBM at b4 once scores stay in VMEM) vs
    150k save-attn, 147k remat-full, 87k best-XLA
    (scripts/diag_attn_r5_out.json)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32000, d_model=512, n_heads=8,
                                n_layers=8, d_ff=2048, max_seq=4096,
                                dtype=jnp.bfloat16, remat=False)
    run_chain, flops = build_transformer(batch, cfg)
    timing = measure_stable(run_chain, n1=3, n2=steps)
    return _record(
        "Transformer-LM long-context (120M, T=4096, flash attn) tokens/sec/chip",
        "tokens/sec/chip", batch * cfg.max_seq, timing, flops,
        probe=run_chain, batch=batch, seq=cfg.max_seq)


def bench_transformer_xlong(batch, steps):
    """Extra-long context: T=8192 (double transformer_long's T at the same
    model). Pure flash-kernel territory — the XLA path's per-layer score
    tensor would be 4 GB bf16 and measured 2.4x slower (43.7k tokens/s,
    scripts/diag_attn_r5_out.json). Same lesson as T=4096: with scores
    streamed through VMEM the activations fit HBM without remat — b4
    remat-off measured 112.2k tokens/s vs 107k for b2 save_attn."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32000, d_model=512, n_heads=8,
                                n_layers=8, d_ff=2048, max_seq=8192,
                                dtype=jnp.bfloat16, remat=False)
    run_chain, flops = build_transformer(batch, cfg)
    timing = measure_stable(run_chain, n1=3, n2=steps)
    return _record(
        "Transformer-LM extra-long context (120M, T=8192, flash attn)"
        " tokens/sec/chip",
        "tokens/sec/chip", batch * cfg.max_seq, timing, flops,
        probe=run_chain, batch=batch, seq=cfg.max_seq)


def bench_dpoverhead(batch, steps):
    """Per-step wall-clock overhead of the dp-8 path vs single-device at the
    SAME global batch (8-device virtual CPU mesh).

    Unlike a "scaling efficiency" number — meaningless when 8 virtual
    devices share one host's cores — this isolates a real quantity: the
    extra per-step latency added by the ParallelWrapper machinery (sharding,
    psum collectives, multi-device dispatch) at equal total compute. ICI
    scaling itself is validated by the loss-equivalence tests in
    tests/test_parallel.py.

    Runs in a subprocess with a CPU-forced env (same reason as
    __graft_entry__.dryrun_multichip): the calling process holds the TPU.
    """
    import os
    import re
    import subprocess

    from deeplearning4j_tpu.utils.subproc import cpu_forced_env

    env, preamble = cpu_forced_env(8)
    code = (
        preamble + "import bench; import json;"
        f"print('DPOVERHEAD ' + json.dumps("
        f"bench._dpoverhead_impl({batch}, {steps})))"
    )
    repo = os.path.dirname(os.path.abspath(__file__))
    metric = DPOVERHEAD_METRIC
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=repo, capture_output=True, text=True,
                              timeout=900)
    except subprocess.TimeoutExpired as e:
        return {"metric": metric, "error": f"timeout after {e.timeout}s"}
    m = re.search(r"DPOVERHEAD (\{.*\})", proc.stdout)
    if proc.returncode != 0 or not m:
        return {"metric": metric,
                "error": (proc.stdout + proc.stderr)[-500:]}
    # stamp in the PARENT (the CPU-forced subprocess has no session
    # identity): the row keys trend history by the capture session's
    # backend/sha like every other row — without it the ledger files
    # this capture under backend "unknown", disconnected from the
    # BENCH_r* tail history (ISSUE 15 backfill found exactly that)
    return _stamp(json.loads(m.group(1)))


def _dpoverhead_impl(batch, steps):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn import (DenseLayer, MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
    from deeplearning4j_tpu.train import Adam

    def build():
        conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_in=256, n_out=512, activation="relu"))
                .layer(DenseLayer(n_out=512, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init((256,))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 256), np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])

    def per_step_ms(fit_once):
        fit_once()  # compile
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                fit_once()
            best = min(best, time.perf_counter() - t0)
        return best / steps * 1e3

    from deeplearning4j_tpu.data.dataset import DataSet
    ds = DataSet(x, y)
    net1 = build()
    t1 = per_step_ms(lambda: net1.fit(ds))
    net8 = build()
    pw = ParallelWrapper(net8, mesh=make_mesh(jax.devices()[:8], dp=8))
    t8 = per_step_ms(lambda: pw.fit([ds]))
    # scanned-dp: K batches per dispatch — the per-step dispatch share of
    # the dp overhead amortizes to ~1/K (r4-s2 ParallelWrapper.fit_scanned)
    k = max(4, steps)
    dss = [ds] * k
    net8s = build()
    pws = ParallelWrapper(net8s, mesh=make_mesh(jax.devices()[:8], dp=8))
    pws.fit_scanned(dss)   # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        pws.fit_scanned(dss)
        best = min(best, time.perf_counter() - t0)
    t8s = best / k * 1e3
    return {"metric": DPOVERHEAD_METRIC,
            # explicit floor-lack: this row is an overhead DELTA between
            # two configs, not a throughput with a single-step roofline
            "floor": {"na": "overhead-delta row; no single-step roofline"},
            # measured on the virtual mesh, whatever device the caller holds
            "device_kind": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "value": round(t8 - t1, 3), "unit": "ms/step",
            "single_ms": round(t1, 3), "dp8_ms": round(t8, 3),
            "dp8_scanned_ms": round(t8s, 3),
            "scanned_batches_per_dispatch": k,
            "global_batch": batch,
            "note": "equal global batch, equal total compute; the delta is "
                    "the sharding/collective/dispatch cost of the dp path "
                    "(dp8_scanned_ms = same step inside one lax.scan "
                    "dispatch per epoch). ICI scaling equivalence: "
                    "tests/test_parallel.py"}


def build_resnet50_fit(batch, num_classes=1000, n_distinct=8,
                       return_parts=False):
    """(run_fit(n)->last_loss, flops) through the REAL user entry point:
    ``ComputationGraph.fit(iterator)`` — iterator protocol, async-wrap
    check, optimizer build, jitted donated step, listener plumbing all
    engaged. Batches are PRE-STAGED on device, so the row times the fit
    loop and not an input pipeline; `n_distinct` staged batches cycle so
    no single-buffer reuse artifact exists on device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.train import Momentum
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    net = ResNet50(num_classes=num_classes, compute_dtype=jnp.bfloat16,
                   updater=Momentum(0.1, 0.9),
                   remat_segments=RESNET_REMAT).init()
    rng = np.random.default_rng(0)
    dss = []
    for i in range(n_distinct):
        x = jnp.asarray(rng.random((batch, 224, 224, 3), np.float32),
                        jnp.bfloat16)
        y = jnp.asarray(np.eye(num_classes, dtype=np.float32)[
            rng.integers(0, num_classes, batch)])
        dss.append(DataSet(x, y))

    net._build_optimizer(1)
    step = net._get_train_step()
    flops = total_flops(
        lambda p, s, o: step.__wrapped__(
            p, s, o, {"in": dss[0].features}, {"out": dss[0].labels},
            jax.random.PRNGKey(0), None, None)[:3],
        net.params, net.states, net._opt_state)

    def run_fit(n):
        batches = [dss[i % n_distinct] for i in range(n)]
        return net.fit(batches)   # float(last loss) = the host-fetch sync

    run_fit.floor_probe = _make_floor_probe(
        step, net.params, net.states, net._opt_state,
        {"in": dss[0].features}, {"out": dss[0].labels},
        jax.random.PRNGKey(0), None, None)
    if return_parts:
        return run_fit, flops, net, dss
    return run_fit, flops


def bench_resnet50_fitscan(batch, steps):
    """fit_scanned variant of the headline: the SAME ComputationGraph
    train step scanned over the epoch's batches in one dispatch
    (bit-identical trajectory to fit(); tests/test_fit_scanned.py). The
    delta vs the fit() record is the per-batch dispatch overhead a user
    recovers by switching entry points."""
    run_fit, flops, net, dss = build_resnet50_fit(batch, return_parts=True)

    def run_scan(n):
        return net.fit_scanned([dss[i % len(dss)] for i in range(n)])

    run_scan.floor_probe = run_fit.floor_probe   # same per-step work
    timing = measure_stable(run_scan, n1=3, n2=steps)
    rec = _record(
        "ComputationGraph.fit_scanned samples/sec/chip "
        "(ResNet-50, scan-dispatch)",
        "samples/sec/chip", batch, timing, flops, probe=run_scan,
        batch=batch)
    rec["vs_baseline"] = round(rec["value"] / BASELINE_SAMPLES_PER_SEC, 3)
    return rec


def bench_resnet50_fit(batch, steps):
    run_fit, flops = build_resnet50_fit(batch)
    timing = measure_stable(run_fit, n1=3, n2=steps)
    rec = _record(
        "ComputationGraph.fit(DataSetIterator) samples/sec/chip "
        "(ResNet-50 ImageNet)",
        "samples/sec/chip", batch, timing, flops, probe=run_fit,
        batch=batch,
        data_path="pre-staged device batches (fit loop fully engaged)")
    rec["vs_baseline"] = round(rec["value"] / BASELINE_SAMPLES_PER_SEC, 3)
    return rec


def build_resnet50(batch, num_classes=1000):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    net = ResNet50(num_classes=num_classes, compute_dtype=jnp.bfloat16,
                   remat_segments=RESNET_REMAT).init()
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(net.params)

    def train_step(params, states, opt_state, x, y):
        def loss_fn(p, s):
            acts, pre, new_s = net._forward(p, s, {"in": x}, train=True,
                                            rng=None,
                                            stop_at_output_preact=True)
            out_layer = net.conf.nodes["out"].op
            loss = out_layer.compute_loss(p["out"], pre["out"], y)
            return loss, new_s

        (loss, new_states), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, states)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_states, opt_state, loss

    jstep = jax.jit(train_step, donate_argnums=(0, 1, 2))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 224, 224, 3), np.float32),
                    jnp.bfloat16)
    y = jnp.asarray(np.eye(num_classes, dtype=np.float32)[
        rng.integers(0, num_classes, batch)])
    flops = total_flops(train_step, net.params, net.states, opt_state, x, y)

    def step_once(p, s, o):
        p, s, o, loss = jstep(p, s, o, x, y)
        return (p, s, o), loss

    run_chain = chain_runner(step_once, [net.params, net.states, opt_state])
    run_chain.floor_probe = _make_floor_probe(
        jstep, net.params, net.states, opt_state, x, y)
    return run_chain, flops


def bench_resnet50(batch, steps):
    run_chain, flops = build_resnet50(batch)
    timing = measure_stable(run_chain, n1=3, n2=steps)
    rec = _record(
        "MultiLayerNetwork.fit() samples/sec/chip (ResNet-50 ImageNet)",
        "samples/sec/chip", batch, timing, flops, probe=run_chain,
        batch=batch)
    rec["vs_baseline"] = round(rec["value"] / BASELINE_SAMPLES_PER_SEC, 3)
    return rec


# ------------------------------------------------------------ inference
# Serving-plane rows (ISSUE 10) — written to the `inference` section of
# bench_secondary.json; every record names its device (`_stamp`), and
# `main()` refuses to run a row where jax finds no TPU.

def _serving_engine(max_seq):
    """Flagship 120M Transformer-LM generation engine at context max_seq.
    remat off: generation is forward-only, there are no residuals to
    trade; flash/bf16-scores gating is the model's own (prefill runs the
    same _attention the training forward does)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving import GenerationEngine
    from deeplearning4j_tpu.zoo import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32000, d_model=512, n_heads=8,
                                n_layers=8, d_ff=2048, max_seq=max_seq,
                                dtype=jnp.bfloat16, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return GenerationEngine(cfg, params), cfg


def _slo_compact(report):
    """The compact `slo` block a bench row embeds: goodput + ITL/TTFT
    p99 beside the throughput number, so the decode-slot sweep (ROADMAP
    item 1) optimizes goodput at target, not raw tokens/s. The full
    targets ride along — the row self-describes its verdict."""
    if report.get("goodput") is None:
        return {"na": "no SLO-eligible requests"}
    t = report["targets"]
    ms = lambda v: None if v is None else round(v * 1e3, 2)  # noqa: E731
    out = {
        "goodput": round(report["goodput"], 4),
        "ttft_p99_ms": ms(report.get("ttft", {}).get("p99_s")),
        "itl_p99_ms": ms(report.get("itl", {}).get("p99_s")),
        "error_rate": round(report["error_rate"], 4),
        "burn_rate": round(report["burn_rate"], 3),
        "met": report["met"],
        "requests": report["window"]["requests"],
        "targets": {"ttft_ms": ms(t["ttft_s"]), "itl_ms": ms(t["itl_s"]),
                    "quantile": t["quantile"]},
    }
    if report.get("itl", {}).get("samples") is not None:
        out["itl_samples"] = report["itl"]["samples"]
    return out


def _mem_peak(pytree_total):
    """(peak_bytes, source): the allocator's peak where the backend has
    memory_stats (TPU/GPU), else the pytree census total — the CPU
    tier-1 path still gets a number (ISSUE 12)."""
    from deeplearning4j_tpu.obs import device_memory_stats
    stats = device_memory_stats()
    if stats and stats.get("peak_bytes_in_use"):
        return int(stats["peak_bytes_in_use"]), "memory_stats"
    return int(pytree_total), "pytree"


def _mem_basic(params_tree, kv_pool_bytes=None, **kv_fields):
    """Memory block builder — the ONE place the row schema lives
    (peak/source/params_bytes core + optional kv_* fields), so the
    decode, TTFT, and batch-1 rows can't drift apart. For a paged pool
    (ISSUE 14) ``kv_pool_bytes`` is the device's actual KV reservation
    (allocated_bytes tracks MAPPED pages, which undercounts the pytree
    footprint). Never fatal."""
    try:
        from deeplearning4j_tpu.obs import tree_bytes
        pb = tree_bytes(params_tree)
        kv_dev = kv_pool_bytes if kv_pool_bytes is not None \
            else kv_fields.get("kv_allocated_bytes", 0)
        peak, src = _mem_peak(pb + (kv_dev or 0))
        return {"peak_bytes": peak, "source": src, "params_bytes": pb,
                **kv_fields}
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        return {"na": f"memory block failed: "
                      f"{type(e).__name__}: {e}"[:300]}


def _fid_compact(report):
    """The compact per-pair fidelity evidence a bench row embeds."""
    r = lambda v, n=8: round(float(v), n)  # noqa: E731
    return {"max_abs_err": r(report["max_abs_err"]),
            "mean_abs_err": r(report["mean_abs_err"]),
            "kl_mean": r(report["kl_mean"], 9),
            "kl_max": r(report["kl_max"], 9),
            "topk_agreement": r(report["topk_agreement"], 4),
            "greedy_match_frac": r(report["greedy_match_frac"], 4),
            "greedy_prefix_len": report["greedy_prefix_len"]}


def _fidelity_block(eng, probe_tokens=128):
    """Fidelity evidence beside the floor/slo/memory blocks (ISSUE 13):
    the row's engine forward run over the SAME probe prompt through
    three attention/dtype paths, compared by ``obs.fidelity``:

    - ``flash_vs_xla``: pallas flash kernel (interpret mode off-TPU —
      the same numerics CI covers) vs the row's XLA attention path,
      same compute dtype;
    - ``bf16_vs_fp32``: the row's deployed path (bf16 activations +
      bf16-scores gating as configured) vs an exact-f32 reference.

    These are the measured logit-error baselines the quantized-KV and
    spec-decode rows (ROADMAP 3) will be judged against — a candidate
    that beats the floor but drifts past today's flash/bf16 envelope
    is a different model, not a faster one. Never fatal."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.obs.fidelity import FidelityProbe
    from deeplearning4j_tpu.zoo import transformer as tfm

    cfg = eng.cfg
    t = int(min(probe_tokens, cfg.max_seq))
    ids = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab_size, (1, t)), jnp.int32)

    def logits(**over):
        c = dataclasses.replace(cfg, **over) if over else cfg
        return np.asarray(tfm.forward(eng.params, c, ids)[0], np.float32)

    # the row's deployed XLA path at its own dtype/score gating — also
    # the bf16 candidate (flash's auto-gate never engages at the probe
    # length, so this IS what the row serves off-flash)
    xla = logits(use_flash_attention=False)
    flash = logits(use_flash_attention=True)
    f32 = logits(use_flash_attention=False, dtype=jnp.float32,
                 attn_scores_bf16=False)
    return {
        "probe_tokens": t,
        "flash_vs_xla": _fid_compact(
            FidelityProbe("flash_vs_xla").compare(xla, flash)),
        "bf16_vs_fp32": _fid_compact(
            FidelityProbe("bf16_vs_fp32").compare(f32, xla)),
    }


def _attach_fidelity(rec, eng):
    try:
        rec["fidelity"] = _fidelity_block(eng)
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        rec["fidelity"] = {"na": f"fidelity probe failed: "
                                 f"{type(e).__name__}: {e}"[:300]}
    return rec


def _paged_kernel_ab(eng, slots=4, floor_ms=None):
    """Kernel-vs-XLA A/B for the decode row (ISSUE 17): run the
    fidelity-gated promotion race over probe paged caches of the row's
    own geometry (dense byte budget re-cut into DEFAULT_PAGE_LEN
    pages) and report both arms — tokens/s, pct_of_floor against the
    row's roofline floor, the fidelity kl_max that gated promotion,
    and the verdict that landed as a sha-stamped cost record. Off-TPU
    the kernel arm runs in pallas interpret mode, so its timing is a
    plumbing check (the verdict records ``fallback_slower`` — the
    baseline is NOT re-pinned on it); on-chip the same block is the
    promotion's citable evidence."""
    from deeplearning4j_tpu.kernels.paged_attention import race
    from deeplearning4j_tpu.serving import kvcache

    plen = kvcache.DEFAULT_PAGE_LEN
    n_pages = slots * (-(-eng.max_len // plen))
    cache = eng.init_paged_cache(slots, n_pages, plen)
    res = race(eng, cache)

    def arm(step_s):
        if step_s is None:
            return None
        return {"step_time_ms": round(step_s * 1e3, 3),
                "tokens_per_s": round(slots / step_s, 2),
                "pct_of_floor": (None if not floor_ms or step_s <= 0
                                 else round(floor_ms / (step_s * 1e3), 4))}

    rep = eng.compile_report()
    return {
        "slots": slots, "page_len": plen, "n_pages": n_pages,
        "verdict": res["verdict"],
        "promoted": res["choice"] == "kernel",
        "gather": arm(res["gather_s"]),
        "kernel": arm(res["kernel_s"]),
        "speedup_kernel_over_gather": res["speedup"],
        "fidelity_kl_max": res["fidelity"]["kl_max"],
        "greedy_match_frac": res["fidelity"]["greedy_match_frac"],
        "cost_record": res["key"],
        # one compile per arm, both pre-warmed by the race itself — the
        # dispatch decision never costs the serve loop a retrace
        "kernel_compiles": rep["decode_paged_kernel"]["compiles"],
    }


def _serve_blocks(eng, slots, n_requests=None, new_tokens=8,
                  prompt_len=64, paged=False, concurrency_x=3):
    """(slo, memory) evidence from ONE real continuous-batching serve
    over the row's engine: submit a mixed-length wave through the
    scheduler with per-request ITL tracing + KV residency accounting
    on, report the rolling-window SLO verdict beside the memory
    attribution (ISSUE 11 + 12). The warm-up request keeps compile time
    out of the steady-state verdict (the same discipline every timed
    row uses); prompt lengths step down across the wave so the
    kv_waste_ratio is measured under genuinely mixed traffic.

    ``paged=True`` (ISSUE 14) serves the SAME wave shape through the
    block-paged pool at the SAME KV byte budget as the dense baseline
    (``slots × max_len`` rows re-cut into DEFAULT_PAGE_LEN pages) but
    ``concurrency_x × slots`` decode lanes — the measured
    ``peak_concurrent`` vs the dense slot count is the
    concurrency-at-equal-bytes claim, and ``kv_waste_ratio`` drops from
    the dense 0.96 to page-tail-only waste. Never fatal — the row
    survives block-less."""
    import numpy as np
    from deeplearning4j_tpu.obs import SLOConfig, SLOTracker
    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            DEFAULT_PAGE_LEN)

    if paged:
        # equal KV byte budget: the dense pool's slots × max_len rows,
        # re-cut into pages shared by concurrency_x× as many lanes
        n_pages = slots * eng.max_len // DEFAULT_PAGE_LEN
        sched = ContinuousBatchingScheduler(
            eng, n_slots=slots * concurrency_x,
            page_len=DEFAULT_PAGE_LEN, n_pages=n_pages)
    else:
        sched = ContinuousBatchingScheduler(eng, n_slots=slots)
    n_requests = n_requests or 2 * sched.n_slots
    rng = np.random.default_rng(1)
    warm = sched.submit(rng.integers(0, eng.cfg.vocab_size, (prompt_len,)),
                        max_new_tokens=2)
    sched.run_until_idle()
    warm.result(timeout=600)
    eng.mark_warm()    # any compile past here is a warned retrace
    sched.slo = SLOTracker(SLOConfig())   # measured window starts here
    sched.reset_kv_window()   # memory evidence covers the SAME window
    lstep = max(1, prompt_len // 16)
    futs = [sched.submit(
        rng.integers(0, eng.cfg.vocab_size,
                     (max(1, prompt_len - (i % 8) * lstep),)),
        max_new_tokens=new_tokens + (i % 3)) for i in range(n_requests)]
    sched.run_until_idle()
    for f in futs:
        f.result(timeout=600)
    kv = sched.kv_report()
    mem = _mem_basic(
        eng.params,
        kv_pool_bytes=kv["pool_bytes"] if paged else None,
        kv_allocated_bytes=(kv["allocated_bytes_mean"] if paged
                            else kv["allocated_bytes"]),
        kv_token_bytes=kv["token_bytes"],
        kv_waste_ratio=kv["waste_ratio_mean"],
        final_residency_mean=kv["final_residency_mean"],
        retraces_after_warm=sum(s["retraces_after_warm"]
                                for s in eng.compile_report().values()))
    if paged:
        # the ISSUE 14 claim, measured: lanes actually served
        # concurrently from the dense baseline's byte budget
        mem["paged"] = {
            **kv["paged"],
            "pool_bytes": kv["pool_bytes"],
            "dense_equiv_slots": slots,
            "peak_concurrent": kv["peak_concurrent"],
            "concurrency_x": round(kv["peak_concurrent"] / slots, 2),
        }
    # HBM bytes the pool pays per token actually resident (mean over
    # the serve) — the serving-efficiency number paged KV and quantized
    # caches (ROADMAP items 1, 3) must push down
    res_tokens = (kv["resident_bytes_mean"] / kv["token_bytes"]
                  if kv["token_bytes"] else 0.0)
    if "peak_bytes" in mem:
        mem["bytes_per_resident_token"] = \
            round(mem["peak_bytes"] / res_tokens, 1) if res_tokens else None
    return _slo_compact(sched.slo.report()), mem


def _chunked_admission_itl(eng, seq, dense_stall_ms=None, slots=8,
                           baseline_sweeps=24, short_len=32,
                           chunk_len=16):
    """The ISSUE 14 ITL claim, measured: decode-sweep wall (= the
    active requests' ITL) for a paged pool of ``slots`` short decoding
    requests, with vs without a T=``seq`` prompt chunk-prefilling in.
    Under chunked admission each step is one chunk + one sweep, so the
    p99 must hold ≤2× the no-admission baseline — where the dense path
    stalls every slot for the WHOLE prefill (``dense_stall_ms``: the
    row's own TTFT median, the before number).

    ``chunk_len`` is the ITL-bound side of the knob trade: one chunk's
    cost must stay well under one decode sweep's (measured on the CPU
    capture: a chunk has a ~0.8 s floor at ctx=4096 — the full-width
    page gather — plus ~10 ms/token, so 128-token chunks cost ~2.5
    2-slot sweeps → 3.5× p99; 16-token chunks ride just above the
    floor). The TTFT-amortization side picks larger chunks — that is
    the ``serving_prefill_chunk`` autotune record's verdict; this
    block records both sides. ``slots`` sizes the
    baseline pool the admission disturbs: the sweep cost scales with
    occupancy while the chunk cost is constant, so the claim is judged
    at a realistically busy pool (the decode row's 8 lanes), not an
    idle one a single chunk would dominate."""
    import numpy as np
    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            DEFAULT_PAGE_LEN,
                                            GenerationEngine)

    if chunk_len != eng.chunk_len:
        # chunk size is engine geometry (it fixes the chunk buckets):
        # a dedicated engine over the SAME params serves the experiment
        eng = GenerationEngine(eng.cfg, eng.params, max_len=eng.max_len,
                               prefill_chunk=chunk_len)
    # the admission prompt: T=seq less the decode budget that keeps it
    # resident through the steady window (stays inside max_len)
    long_len = min(seq, eng.max_len - baseline_sweeps - 1)
    chunks = -(-long_len // eng.chunk_len)
    rng = np.random.default_rng(3)
    budget = 2 * baseline_sweeps + chunks + 12
    # pages for the full working set: the long admission + every short
    # request's whole prompt+budget — page PRESSURE preemptions would
    # contaminate the ITL measurement
    per_short = -(-(short_len + budget) // DEFAULT_PAGE_LEN)
    n_pages = -(-seq // DEFAULT_PAGE_LEN) + slots * per_short + 4
    sched = ContinuousBatchingScheduler(eng, n_slots=slots + 1,
                                        page_len=DEFAULT_PAGE_LEN,
                                        n_pages=n_pages)
    # warm every shape this experiment touches: a chunk_len-long prompt
    # (the long admission's bucket), a short_len prompt, decode, sample
    for warm_len in (eng.chunk_len, short_len):
        w = sched.submit(rng.integers(0, eng.cfg.vocab_size, (warm_len,)),
                         max_new_tokens=2)
        sched.run_until_idle()
        w.result(timeout=600)
    shorts = [sched.submit(
        rng.integers(0, eng.cfg.vocab_size, (short_len,)),
        max_new_tokens=budget) for _ in range(slots)]
    for _ in range(2):
        sched.step()                    # admit; exclude ramp-up steps
    base = []
    for _ in range(baseline_sweeps):
        t0 = time.perf_counter()
        sched.step()
        base.append(time.perf_counter() - t0)
    # budget > 1 keeps the long request DECODING (pages mapped) after
    # its prefill, so the steady window below sees the same working set
    long_fut = sched.submit(
        rng.integers(0, eng.cfg.vocab_size, (long_len,)),
        max_new_tokens=baseline_sweeps + 2)

    def _prefilling():
        return any(r is not None and r.pending is not None
                   for r in sched.slots)

    adm = []
    while len(adm) < 4 * chunks + 8:
        t0 = time.perf_counter()
        sched.step()       # first iteration admits the long request
        adm.append(time.perf_counter() - t0)
        if not _prefilling():
            break
    # steady-state baseline at EQUAL residency: the T=seq context is
    # resident and decoding, no admission in progress — sweeps here pay
    # the same KV bytes the admission-window sweeps paid, so the ratio
    # isolates the admission MECHANICS (the chunk interleave) from the
    # permanent cost of holding seq more resident tokens, which any
    # admission policy pays
    steady = []
    for _ in range(baseline_sweeps):
        t0 = time.perf_counter()
        sched.step()
        steady.append(time.perf_counter() - t0)
    sched.run_until_idle()
    for f in shorts:
        f.result(timeout=600)
    long_res = long_fut.result(timeout=600)
    p99 = lambda xs: sorted(xs)[min(len(xs) - 1,  # noqa: E731
                                    int(round(0.99 * (len(xs) - 1))))]
    base_p99, adm_p99, steady_p99 = p99(base), p99(adm), p99(steady)
    ratio_resident = round(adm_p99 / steady_p99, 3) if steady_p99 else None
    ratio_idle = round(adm_p99 / base_p99, 3) if base_p99 else None
    return {
        "page_len": DEFAULT_PAGE_LEN, "chunk_len": eng.chunk_len,
        "chunks": chunks, "long_prompt_tokens": long_len,
        "decode_slots": slots,
        "baseline_itl_p99_ms": round(steady_p99 * 1e3, 2),
        "pre_admission_itl_p99_ms": round(base_p99 * 1e3, 2),
        "admission_itl_p99_ms": round(adm_p99 * 1e3, 2),
        "admission_over_baseline": ratio_resident,
        "admission_over_pre_admission": ratio_idle,
        "met_2x": ratio_resident is not None and ratio_resident <= 2.0,
        "dense_admission_stall_ms": dense_stall_ms,
        "long_ttft_ms": round(long_res.ttft_s * 1e3, 1),
        "note": "per-sweep wall of the decoding pool while the T="
                f"{seq} prompt chunks in. Baseline = steady-state "
                "sweeps at EQUAL residency (the prompt resident and "
                "decoding, no admission running): paged KV reads "
                "scale with resident bytes, so pre-admission sweeps "
                "(pre_admission_itl_p99_ms) are structurally cheaper "
                "in a way any admission policy would forfeit. Dense "
                "admission stalls every slot for the whole prefill "
                "(the row's TTFT median)",
    }


def bench_inference_decode(batch, steps):
    """Decode tokens/sec/chip: one jitted donated-cache decode_step +
    greedy sample per sweep over a `batch`-slot pool (the serving hot
    path, T=1024 cache). Marginal chained-step timing like every other
    row; flops from the traced decode step (attention against the full
    static cache length — the work actually dispatched)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.utils.tracing import total_flops

    eng, cfg = _serving_engine(1024)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, 64)))
    cache = eng.init_cache(batch)
    logits, cache = eng.prefill(cache, prompt)
    tokens = jnp.argmax(logits, -1).astype(jnp.int32)
    flops = total_flops(eng._decode_raw, eng.params, cache, tokens)

    def step_once(cache, tokens):
        logits, cache = eng.decode_step(cache, tokens)
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        return (cache, toks), toks

    run_chain = chain_runner(step_once, [cache, tokens])
    run_chain.floor_probe = _make_floor_probe(eng._decode, eng.params,
                                              cache, tokens)
    timing = measure_stable(run_chain, n1=5, n2=steps)
    rec = _record(
        "Serving decode tokens/sec/chip (Transformer-LM 120M, KV-cache "
        "T=1024, greedy)",
        "tokens/sec/chip", batch, timing, flops, probe=run_chain,
        slots=batch, prefill_tokens=64,
        note="one continuous-batching decode sweep = one token per slot; "
             "scheduler occupancy metrics: dl4j_serving_*")
    # the SLO + memory verdicts beside the floor block (ISSUE 11 + 12 +
    # 14): goodput at target AND kv waste from ONE real mixed-length
    # scheduler serve — now through the block-paged pool at the dense
    # baseline's byte budget (slots × max_len re-cut into pages,
    # concurrency_x× the lanes): memory.paged carries the measured
    # peak_concurrent / concurrency_x, and kv_waste_ratio is page-tail
    # waste, not the dense 0.96
    try:
        rec["slo"], rec["memory"] = _serve_blocks(eng, slots=batch,
                                                  paged=True)
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        rec["slo"] = {"na": f"slo serve failed: "
                            f"{type(e).__name__}: {e}"[:300]}
        rec["memory"] = {"na": "see slo"}
    # fidelity evidence (ISSUE 13): flash-vs-XLA + bf16-vs-fp32 logit
    # error over the row's own engine — the measured numerics envelope
    # the quantized-KV / spec-decode rows must stay inside
    _attach_fidelity(rec, eng)
    # paged-decode kernel-vs-XLA A/B (ISSUE 17): the promotion race's
    # verdict + both arms' tokens/s beside the row, and the race's own
    # fidelity probe joins the fidelity block so fidelity_report.py
    # gates the kernel capture like every other pair
    try:
        floor_ms = (rec.get("floor") or {}).get("floor_ms")
        rec["paged_kernel_ab"] = _paged_kernel_ab(eng, slots=4,
                                                  floor_ms=floor_ms)
        if isinstance(rec.get("fidelity"), dict) \
                and "na" not in rec["fidelity"]:
            from deeplearning4j_tpu.kernels import autotune as _at
            meta = _at.measurement_meta(
                rec["paged_kernel_ab"]["cost_record"]) or {}
            fid = meta.get("fidelity")
            if fid:
                rec["fidelity"]["paged_kernel_vs_xla"] = _fid_compact(fid)
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        rec["paged_kernel_ab"] = {"na": f"kernel A/B failed: "
                                        f"{type(e).__name__}: {e}"[:300]}
    return rec


def _ttft_row(seq, reps, chunked_admission=False):
    """Time-to-first-token at a `seq`-token prompt: wall-clock of one
    jitted prefill + greedy sample + host fetch (compile excluded,
    median of `reps`). This is the latency a request pays before its
    decode slot starts streaming. ``chunked_admission`` additionally
    measures the ISSUE 14 interleave claim: a paged pool's decode ITL
    p99 while this row's prompt chunk-prefills in, vs no admission."""
    import jax.numpy as jnp
    import numpy as np
    import statistics

    eng, cfg = _serving_engine(seq)
    rng = np.random.default_rng(0)
    prompt = np.asarray(rng.integers(0, cfg.vocab_size, (seq,)), np.int32)
    # caches pre-allocated outside the timed region (prefill donates its
    # cache arg; a served slot reuses pool HBM, it doesn't re-alloc)
    caches = [eng.init_cache(1) for _ in range(reps + 1)]
    samples = []
    for i, cache in enumerate(caches):
        t0 = time.perf_counter()
        logits, cache = eng.prefill_slot(cache, prompt, 0)
        tok = int(np.asarray(jnp.argmax(logits)))
        dt = time.perf_counter() - t0
        if i:                      # first call pays compile — excluded
            samples.append(dt)
    med = float(statistics.median(samples))
    try:
        from deeplearning4j_tpu.obs import get_registry
        get_registry().histogram(
            "dl4j_serving_ttft_seconds",
            "Time from submit to first generated token").observe(med)
    except Exception:  # noqa: BLE001 — telemetry mirror is decoration
        pass
    rec = {
        "metric": f"Serving time-to-first-token, T={seq} prefill "
                  "(Transformer-LM 120M)",
        "value": round(med * 1e3, 1), "unit": "ms",
        "prefill_tokens": seq, "reps": len(samples),
        "ttft_ms_samples": [round(s * 1e3, 1) for s in samples],
        "first_token": tok,
        "timing": "wall-clock prefill_slot + greedy sample + host fetch, "
                  "compile excluded, median of reps",
        "metrics": {"dl4j_serving_ttft_seconds": med},
    }
    # offline SLO verdict over the same samples (each rep is one
    # 1-token request): TTFT attainment/goodput at the default target
    try:
        from deeplearning4j_tpu.obs import SLOConfig, SLOTracker
        slo = SLOTracker(SLOConfig(), registry=False)
        for s in samples:
            slo.observe_summary({"status": "finish", "ttft_s": s,
                                 "itl_s": []})
        rec["slo"] = _slo_compact(slo.report())
    except Exception as e:  # noqa: BLE001 — the row survives SLO-less
        rec["slo"] = {"na": f"slo derivation failed: "
                            f"{type(e).__name__}: {e}"[:300]}
    if chunked_admission:
        # the chunked-prefill ITL verdict (ISSUE 14) rides this row's
        # slo block: its prompt length is the admission under test
        try:
            rec["slo"]["chunked_admission"] = _chunked_admission_itl(
                eng, seq, dense_stall_ms=rec["value"])
        except Exception as e:  # noqa: BLE001 — row survives block-less
            rec["slo"]["chunked_admission"] = {
                "na": f"admission experiment failed: "
                      f"{type(e).__name__}: {e}"[:300]}
    # memory attribution for the prefill path (ISSUE 12): one slot
    # filled to its prompt length — waste is the tail of max_len the
    # fixed slot preallocates past the prompt
    try:
        from deeplearning4j_tpu.serving import cache_nbytes, token_nbytes
        rec["memory"] = _mem_basic(
            eng.params,
            kv_allocated_bytes=cache_nbytes(cache),
            kv_token_bytes=token_nbytes(cache),
            kv_waste_ratio=round(1.0 - seq / eng.max_len, 6))
        if "peak_bytes" in rec["memory"]:
            rec["memory"]["bytes_per_resident_token"] = \
                round(rec["memory"]["peak_bytes"] / seq, 1)
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        rec["memory"] = {"na": f"memory block failed: "
                               f"{type(e).__name__}: {e}"[:300]}
    # fidelity evidence (ISSUE 13) beside the slo/memory blocks
    _attach_fidelity(rec, eng)
    return _stamp(rec)


def bench_inference_ttft_1024(batch, steps):
    return _ttft_row(1024, reps=max(steps, 2))


def bench_inference_ttft_4096(batch, steps):
    # the T=4096 admission is the ISSUE 14 worst case: measure the
    # chunked-prefill ITL interleave beside the raw prefill latency
    return _ttft_row(4096, reps=max(steps, 2), chunked_admission=True)


def bench_inference_prefix_shared(batch, steps):
    """CoW prefix cache row (ISSUE 16): `batch` requests share a
    1024-token common prefix (the system-prompt shape) with mixed
    random tails. Three phases against the same page budget:

    - sharing ON, sequential: a cold leader pays the full prefill,
      then every follower admits against the cached prefix and
      chunk-prefills only its tail — warm TTFT median is the row value;
    - sharing ON, concurrent: `slots` requests decode together while
      the page table is sampled — tokens-resident-per-user with the
      prefix counted ONCE (used pages) vs per-slot (mapped pages, what
      a no-sharing pool holds);
    - sharing OFF, same prompts: measured cold TTFT AND a greedy
      bit-equivalence check against the sharing-on outputs.
    """
    import numpy as np
    import statistics
    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            DEFAULT_PAGE_LEN)

    prefix_len, slots = 1024, 8
    n_req = max(batch, 2)
    new_tokens = max(steps, 2)
    eng, cfg = _serving_engine(prefix_len + 128)
    pages_per_slot = -(-cfg.max_seq // DEFAULT_PAGE_LEN)
    n_pages = slots * pages_per_slot     # the dense-equivalent budget
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(
        np.int32)
    prompts = [np.concatenate([prefix, rng.integers(
        0, cfg.vocab_size, (int(rng.integers(8, 65)),)).astype(np.int32)])
        for _ in range(n_req)]

    sched = ContinuousBatchingScheduler(eng, n_slots=slots,
                                        page_len=DEFAULT_PAGE_LEN,
                                        n_pages=n_pages,
                                        prefix_cache=True)
    # cold leader: full-prefix prefill, pages cached at retirement
    leader = sched.submit(prompts[0], max_new_tokens=new_tokens)
    sched.run_until_idle()
    ttft_cold = leader.result(timeout=1200).ttft_s
    # warm followers, sequential (queue-free TTFT): tail-only prefill
    warm_samples, on_tokens = [], {}
    for i, p in enumerate(prompts[1:], start=1):
        f = sched.submit(p, max_new_tokens=new_tokens)
        sched.run_until_idle()
        res = f.result(timeout=1200)
        warm_samples.append(res.ttft_s)
        on_tokens[i] = res.tokens.tolist()
    warm_med = float(statistics.median(warm_samples))
    # concurrent wave: residency per user while `slots` decode
    # together. Generation long enough to span several sweeps — the
    # page table is sampled AFTER each step, and a too-short wave
    # retires inside the first one, leaving nothing to observe
    wave = [sched.submit(p, max_new_tokens=max(new_tokens, 8))
            for p in prompts[:slots]]
    best = (0, 0, 0, 0)                 # (active, used, mapped, shared)
    while sched.step():
        with sched._lock:
            active = sum(1 for s in sched.slots if s is not None)
            if active >= best[0]:
                best = (active, sched._pages.used_pages,
                        sched._pages.mapped_pages,
                        sched._pages.shared_pages)
    for f in wave:
        f.result(timeout=1200)
    assert sched.check_pages()
    prefix_rep = sched.kv_report()["prefix"]
    active, used, mapped, shared = best
    per_user_shared = (used * DEFAULT_PAGE_LEN / active) if active else None
    per_user_dense = (mapped * DEFAULT_PAGE_LEN / active) if active else None

    # sharing OFF: measured cold TTFT over a subset of the SAME
    # prompts + greedy bit-equivalence vs the sharing-on outputs
    sched_off = ContinuousBatchingScheduler(eng, n_slots=slots,
                                            page_len=DEFAULT_PAGE_LEN,
                                            n_pages=n_pages)
    off_samples, mismatches = [], 0
    n_off = min(4, n_req - 1)
    for i in range(1, 1 + n_off):
        f = sched_off.submit(prompts[i], max_new_tokens=new_tokens)
        sched_off.run_until_idle()
        res = f.result(timeout=1200)
        off_samples.append(res.ttft_s)
        if res.tokens.tolist() != on_tokens[i]:
            mismatches += 1
    off_med = float(statistics.median(off_samples))

    rec = {
        "metric": f"Serving TTFT under a shared {prefix_len}-token "
                  f"prefix, {n_req} requests, CoW prefix cache "
                  "(Transformer-LM 120M)",
        "value": round(warm_med * 1e3, 1), "unit": "ms",
        "requests": n_req, "prefix_tokens": prefix_len,
        "decode_slots": slots, "n_pages": n_pages,
        "ttft_ms_samples": [round(s * 1e3, 1) for s in warm_samples],
        "ttft_cold_ms": round(ttft_cold * 1e3, 1),
        "ttft_no_sharing_ms": round(off_med * 1e3, 1),
        "ttft_speedup_x": round(off_med / warm_med, 2) if warm_med else None,
        "tokens_resident_per_user_shared": round(per_user_shared, 1)
        if per_user_shared else None,
        "tokens_resident_per_user_dense": round(per_user_dense, 1)
        if per_user_dense else None,
        "residency_sample_active_users": active,
        "shared_pages_sampled": shared,
        "prefix_hits": prefix_rep["prefix_hits"],
        "prefix_hit_tokens": prefix_rep["prefix_hit_tokens"],
        "cow_copies": prefix_rep["cow_copies"],
        "greedy_bitmatch_vs_no_sharing": mismatches == 0,
        "no_sharing_reps": n_off,
        "timing": "wall submit→first-token through the scheduler, "
                  "sequential (queue-free); value = warm (prefix-hit) "
                  "median, vs measured no-sharing cold median over "
                  f"{n_off} of the same prompts",
    }
    assert mismatches == 0, (
        f"{mismatches}/{n_off} prompts decoded differently with the "
        "prefix cache on — sharing broke greedy bit-equivalence")
    return _stamp(rec)


def bench_inference_scoring(batch, steps):
    """SCORE workload row (ISSUE 20): prefill-only per-token logprob
    scoring through the scheduler — `batch` prompts of ~512 tokens
    each, `steps` timed waves. A SCORE request retires at its final
    prefill chunk (no decode sweeps), so the row measures the chunked
    prefill pipeline's SCORING throughput: prompt tokens scored per
    second. Each wave's perplexities are cross-checked for finiteness
    and the first wave's logprob count must be exactly prompt-1 per
    request (the oracle contract tests pin the values on CPU)."""
    import time as _time
    import numpy as np
    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            DEFAULT_PAGE_LEN)

    n_req = max(batch, 1)
    reps = max(steps, 1)
    prompt_len, slots = 512, 8
    eng, cfg = _serving_engine(prompt_len + 16)
    pages_per_slot = -(-cfg.max_seq // DEFAULT_PAGE_LEN)
    sched = ContinuousBatchingScheduler(eng, n_slots=slots,
                                        page_len=DEFAULT_PAGE_LEN,
                                        n_pages=slots * pages_per_slot)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(
        np.int32) for _ in range(n_req)]
    # warm the chunk buckets once (compile excluded from timing)
    sched.submit(prompts[0], kind="score")
    sched.run_until_idle()
    wave_tps, ppl0 = [], None
    for _ in range(reps):
        futs = [sched.submit(p, kind="score") for p in prompts]
        t0 = _time.perf_counter()
        sched.run_until_idle()
        dt = _time.perf_counter() - t0
        results = [f.result(timeout=1200) for f in futs]
        assert all(np.isfinite(r.perplexity) for r in results)
        assert all(len(r.logprobs) == prompt_len - 1 for r in results)
        if ppl0 is None:
            ppl0 = [round(float(r.perplexity), 2) for r in results[:4]]
        wave_tps.append(n_req * prompt_len / dt)
    tps = max(wave_tps)
    rec = {"metric": "Serving SCORE throughput: prefill-only per-token "
                     f"logprobs, {n_req} x {prompt_len}-token prompts "
                     "(Transformer-LM 120M)",
           "value": round(tps, 1), "unit": "tokens/sec/chip",
           "requests": n_req, "prompt_tokens": prompt_len,
           "decode_slots": slots, "reps": reps,
           "wave_tokens_per_s": [round(t, 1) for t in wave_tps],
           "perplexity_head": ppl0,
           "timing": "wall submit→all-retired per wave through the "
                     "scheduler, warm buckets (compile excluded); "
                     "value = best wave"}
    return _stamp(rec)


def bench_inference_beam(batch, steps):
    """BEAM workload row (ISSUE 20): width-`batch` beam search through
    the scheduler's paged pool, `steps` new tokens. The beams
    ``map_shared`` the prompt's pages and CoW-split only where they
    diverge, so the row reports BOTH the lane throughput (beams advance
    in one decode sweep — width-k costs one sweep, not k) and the page
    census (shared vs mapped) that proves the sharing, plus the search
    quality signal: beam gain = best beam total logprob − greedy total
    logprob over the same horizon (greedy continuation re-scored
    through a SCORE request; ≥ 0 up to fp tolerance by construction,
    fidelity_report.py --min-beam-gain gates it)."""
    import time as _time
    import statistics
    import numpy as np
    from deeplearning4j_tpu.serving import (ContinuousBatchingScheduler,
                                            DEFAULT_PAGE_LEN)

    width = max(batch, 2)
    new_tokens = max(steps, 4)
    prompt_len = 256
    slots = max(width, 8)
    eng, cfg = _serving_engine(prompt_len + new_tokens + 16)
    pages_per_slot = -(-cfg.max_seq // DEFAULT_PAGE_LEN)
    sched = ContinuousBatchingScheduler(eng, n_slots=slots,
                                        page_len=DEFAULT_PAGE_LEN,
                                        n_pages=slots * pages_per_slot)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(
        np.int32) for _ in range(3)]
    # warm: one narrow beam + one greedy + one score (compile excluded)
    sched.submit(prompts[0], max_new_tokens=2, kind="beam",
                 beam_width=width)
    sched.submit(prompts[0], max_new_tokens=2)
    sched.submit(prompts[0], kind="score")
    sched.run_until_idle()

    gains, lane_tps, census = [], [], (0, 0, 0)
    for p in prompts:
        fb = sched.submit(p, max_new_tokens=new_tokens, kind="beam",
                          beam_width=width)
        t0 = _time.perf_counter()
        while sched.step():
            with sched._lock:
                active = sum(1 for s in sched.slots if s is not None)
                if active >= census[0]:
                    census = (active, sched._pages.shared_pages,
                              sched._pages.mapped_pages)
        dt = _time.perf_counter() - t0
        br = fb.result(timeout=1200)
        assert sched.check_pages()
        lane_tps.append(len(br.sequences[0]) * width / dt)
        # greedy baseline over the same horizon, scored exactly
        fg = sched.submit(p, max_new_tokens=new_tokens)
        sched.run_until_idle()
        greedy = fg.result(timeout=1200).tokens
        fs = sched.submit(np.concatenate([p, greedy]), kind="score")
        sched.run_until_idle()
        lps = fs.result(timeout=1200).logprobs
        greedy_lp = float(np.sum(lps[p.size - 1:]))
        gains.append(br.best_logprob - greedy_lp)
    gain_med = float(statistics.median(gains))
    active, shared, mapped = census
    rec = {"metric": f"Serving width-{width} beam search, "
                     f"{prompt_len}-token prompt + {new_tokens} new "
                     "tokens, CoW page-shared beams "
                     "(Transformer-LM 120M)",
           "value": round(float(statistics.median(lane_tps)), 1),
           "unit": "tokens/sec/chip",
           "beam_width": width, "new_tokens": new_tokens,
           "prompt_tokens": prompt_len, "n_prompts": len(prompts),
           "beam_gain_nats": round(gain_med, 4),
           "beam_gain_samples": [round(g, 4) for g in gains],
           "census_active_lanes": active,
           "census_shared_pages": shared,
           "census_mapped_pages": mapped,
           "timing": "wall submit→finish per beam request, warm "
                     "buckets (compile excluded); value = median lane "
                     "tokens/s (width x generated / wall)"}
    assert gain_med >= -1e-3, (
        f"beam best ({gain_med:+.4f} nats vs greedy) lost to greedy — "
        "the joint ranking is broken")
    return _stamp(rec)


def bench_inference_fleet(batch, steps):
    """Fleet serving fabric row (ISSUE 18): a seeded open-loop Poisson
    trace with a burst window drives a ``FleetRouter`` that autoscales
    between 1 and 3 replicas on sustained SLO burn. The row value is
    FLEET goodput (every replica's requests replayed through ONE
    offline tracker — the same aggregation `scripts/slo_report.py
    --fleet` renders), with p99 TTFT/ITL, the replica min→max span and
    the scale-event counts riding along.

    ``batch`` = decode slots per replica, ``steps`` = decode tokens per
    request. The burst deliberately overloads one replica so the
    autoscaler has something to do; goodput below 100% during the burst
    is the signal this row trends, not a failure.
    """
    import importlib.util
    import tempfile
    import numpy as np
    from pathlib import Path
    from deeplearning4j_tpu.obs import load_flight_records
    from deeplearning4j_tpu.obs.slo import SLOConfig
    from deeplearning4j_tpu.serving import (AutoscalerConfig,
                                            ContinuousBatchingScheduler,
                                            FleetRouter, TrafficConfig,
                                            run_episode)

    slots = max(batch, 2)
    new_tokens = max(steps, 2)
    eng, cfg = _serving_engine(256)
    # episode SLO: ITL generous (one CPU decode sweep is tens of ms),
    # TTFT tight enough that burst queue-wait registers as burn — the
    # autoscale signal. The offline replay judges against the SAME
    # targets.
    slo = SLOConfig(ttft_s=5.0, itl_s=2.0, window_s=4.0)
    prompt_lens = (8, 16, 32)
    # warm the shared engine OUTSIDE the fleet: the compile storm must
    # not appear in the episode's flight record. Same slot count + the
    # same prompt-length set → the jitted shapes every replica will hit
    # (replicas share the engine; its jitted fns are cache-stateless).
    rng = np.random.default_rng(0)
    warm = ContinuousBatchingScheduler(eng, n_slots=slots)
    for plen in prompt_lens:
        warm.submit(rng.integers(1, cfg.vocab_size, (plen,)).astype(
            np.int32), max_new_tokens=2)
    warm.run_until_idle()

    router = FleetRouter(
        eng, n_replicas=1, n_slots=slots, slo=slo,
        autoscaler=AutoscalerConfig(min_replicas=1, max_replicas=3,
                                    high_burn=1.0, low_burn=0.5,
                                    high_queue=3.0, patience=2,
                                    cooldown=3),
        autoscale_every=4)
    # base rate below one warm replica's service rate (so the tail is
    # calm enough to earn the scale-down), burst far above it (so the
    # autoscaler has to act); the long tail lets the burn window clear
    traffic = TrafficConfig(rate_rps=1.0, duration_s=30.0,
                            prompt_lens=prompt_lens,
                            max_new_tokens=(new_tokens, new_tokens + 2),
                            vocab=cfg.vocab_size,
                            burst_start_s=1.0, burst_end_s=3.5,
                            # seed picked by enumerating the (seeded)
                            # trace: the piecewise draw can step clean
                            # over the burst window from a pre-burst
                            # gap (seeds 0/4 do); seed 1 lands 26 of
                            # 54 arrivals inside it, leaving a ~26s
                            # calm tail for the scale-down
                            burst_mult=10.0, seed=1)
    with tempfile.TemporaryDirectory() as td:
        dump = Path(td) / "fleet_episode.jsonl"
        ep = run_episode(router, traffic, dump_path=dump,
                         max_wall_s=1500.0)
        records = load_flight_records(dump)

    # offline replay through the slo_report aggregation — one
    # semantics for the bench row and the operator tool
    spec = importlib.util.spec_from_file_location(
        "dl4j_bench_slo_report",
        Path(__file__).resolve().parent / "scripts" / "slo_report.py")
    slo_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(slo_report)
    reports = slo_report.build_reports(records, slo, fleet=True)
    fleet_rep = reports["FLEET"]
    rng_rep = slo_report.replica_range(records)
    evs = slo_report.scale_events(records)
    ups = sum(1 for e in evs if e["scale_event"] == "up")
    downs = sum(1 for e in evs if e["scale_event"] == "down")
    goodput = fleet_rep.get("goodput")

    rec = {
        "metric": "Fleet goodput under a Poisson burst trace, "
                  "SLO-autoscaled 1→3 replicas (Transformer-LM 120M)",
        "value": None if goodput is None else round(100.0 * goodput, 1),
        "unit": "% goodput",
        "decode_slots": slots, "decode_tokens": new_tokens,
        "requests": ep.submitted, "completed": ep.completed,
        "failed": ep.failed, "episode_wall_s": ep.wall_s,
        "replicas_min": rng_rep[0] if rng_rep else None,
        "replicas_max": rng_rep[1] if rng_rep else None,
        "scale_ups": ups, "scale_downs": downs,
        "reprefills": ep.fleet.get("reprefills"),
        "ghost_results": ep.fleet.get("ghost_results"),
        "goodput_per_replica": {
            r: round(rep["goodput"], 4)
            for r, rep in sorted(reports.items())
            if r != "FLEET" and rep.get("goodput") is not None},
        "traffic": {"rate_rps": traffic.rate_rps,
                    "duration_s": traffic.duration_s,
                    "burst_s": [traffic.burst_start_s,
                                traffic.burst_end_s],
                    "burst_mult": traffic.burst_mult,
                    "seed": traffic.seed},
        "slo": _slo_compact(fleet_rep),
        "timing": "wall-clock open-loop episode (arrivals paced against "
                  "the clock, independent of completions); value = FLEET "
                  "goodput from the offline replay of the episode dump "
                  "at the live targets",
    }
    assert ep.failed == 0, (
        f"{ep.failed}/{ep.submitted} fleet futures failed — the "
        "never-hang contract resolved them with exceptions")
    return _stamp(rec)


def bench_inference_quant_kv(batch, steps):
    """Quantized-KV row (ISSUE 19): run the fidelity-gated int8-vs-bf16
    promotion races (``quant.race_kv`` over one paged-pool geometry,
    ``quant.race_weights`` over the block stack) and report both arms —
    decode tokens/s, the KV bytes-per-resident-token each pool pays,
    the kl_max that gated promotion, and the verdicts that landed as
    sha-stamped cost records. The row VALUE is the byte-shrink factor
    (bf16 / int8 KV bytes per token) — the claim that holds on any
    backend; the speed verdict is the chip's to make (CPU dequant
    overhead records ``fallback_slower`` without re-pinning anything,
    exactly the paged-kernel A/B discipline). The races' own fidelity
    probes land in the ``fidelity`` block so ``fidelity_report.py
    --max-kl`` gates this capture like every other pair.

    ``batch`` = probe decode slots, ``steps`` unused (the race times
    marginal chained sweeps itself)."""
    from deeplearning4j_tpu.serving import kvcache
    from deeplearning4j_tpu.serving.quant import race_kv, race_weights

    slots = max(batch, 2)
    eng, cfg = _serving_engine(512)
    plen = kvcache.DEFAULT_PAGE_LEN
    n_pages = slots * (-(-eng.max_len // plen))
    kv = race_kv(eng, slots, n_pages, plen)
    bpt = kv["bytes_per_token"]

    def arm(step_s):
        if step_s is None:
            return None
        return {"step_time_ms": round(step_s * 1e3, 3),
                "tokens_per_s": round(slots / step_s, 2)}

    rec = {
        "metric": "KV-cache bytes/token shrink from int8 page storage, "
                  "fidelity-gated (Transformer-LM 120M, paged pool)",
        "value": round(bpt["bf16"] / bpt["int8"], 2), "unit": "x fewer "
                 "KV bytes/token (int8+scales vs bf16)",
        "slots": slots, "page_len": plen, "n_pages": n_pages,
        "kv_bytes_per_token": bpt,
        "verdict": kv["verdict"],
        "promoted": kv["choice"] == "int8",
        "bf16": arm(kv["bf16_s"]), "int8": arm(kv["int8_s"]),
        "speedup_int8_over_bf16": kv["speedup"],
        "fidelity_kl_max": kv["fidelity"]["kl_max"],
        "cost_record": kv["key"],
        "timing": "marginal chained decode sweeps per arm (the race's "
                  "own autotune timing); identical probe content both "
                  "pools — the fidelity diff is quantization error and "
                  "nothing else",
    }
    rec["fidelity"] = {"quant_kv_vs_bf16": kv["fidelity"]}
    # int8 weights ride along: same race shape over the decode matvecs
    try:
        w = race_weights(eng)
        rec["weights"] = {
            "verdict": w["verdict"], "promoted": w["choice"] == "int8",
            "bf16_s": w["bf16_s"], "int8_s": w["int8_s"],
            "speedup": w["speedup"], "cost_record": w["key"]}
        rec["fidelity"]["quant_w_vs_bf16"] = w["fidelity"]
    except Exception as e:  # noqa: BLE001 — the row survives block-less
        rec["weights"] = {"na": f"weight race failed: "
                                f"{type(e).__name__}: {e}"[:300]}
    return _stamp(rec)


def bench_inference_spec_decode(batch, steps):
    """Speculative-decoding row (ISSUE 19): race draft arms (prompt-
    lookup ``NgramDraft`` + self-draft ``EngineDraft``) against the
    plain paged greedy decode on one prompt via ``spec.race_spec``.
    The row VALUE is the best arm's tokens/s with the baseline riding
    along; ``accepted_per_step`` (tokens per verify dispatch — the
    ``fidelity_report.py --min-accept`` gate input) and the per-arm
    bit-identity + promotion verdicts land beside it. An arm that
    loses falls back silently (counted in
    ``dl4j_autotune_promotions_total``) — the row still captures, the
    verdict is the evidence.

    ``batch`` = draft window k, ``steps`` = decode tokens per rep."""
    import numpy as np
    from deeplearning4j_tpu.serving import EngineDraft, NgramDraft
    from deeplearning4j_tpu.serving.spec import SpeculativeDecoder, \
        race_spec

    k = max(batch, 2)
    new_tokens = max(steps, 16)
    eng, cfg = _serving_engine(256)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (32,)).astype(np.int32)
    # warm every jitted shape the race will hit OUTSIDE its timed reps:
    # plain decode + chunked prefill, the verify chunk, and the engine
    # draft's dense prefill_slot/decode_step
    warm = SpeculativeDecoder(eng, NgramDraft(), k=k)
    warm.generate(prompt, k + 2)
    warm.release()
    d = EngineDraft(eng)
    d.propose([int(t) for t in prompt] + [0], 2)
    d.reset()

    res = race_spec(eng, {"ngram": NgramDraft(), "engine": EngineDraft(eng)},
                    prompt, new_tokens, k=k)
    base_tps = res["tokens"] / res["base_s"] if res["base_s"] else None
    # best arm by wall time whether or not it promoted — the row trends
    # the measured number; the verdict says what dispatches
    best_name = min(res["arms"], key=lambda n: res["arms"][n]["spec_s"])
    best = res["arms"][best_name]

    rec = {
        "metric": f"Speculative decode tokens/s, draft-verify k={k} "
                  "vs plain greedy (Transformer-LM 120M, paged pool)",
        "value": round(res["tokens"] / best["spec_s"], 2)
        if best["spec_s"] else None,
        "unit": "tokens/sec (best draft arm)",
        "k": k, "decode_tokens": res["tokens"],
        "baseline_tokens_per_s": round(base_tps, 2) if base_tps else None,
        "choice": res["choice"],
        "best_arm": best_name,
        "speedup_vs_plain": best["speedup"],
        "arms": {
            name: {kk: a[kk] for kk in ("verdict", "spec_s", "speedup",
                                        "accepted_per_step",
                                        "bit_identical")}
            for name, a in res["arms"].items()},
        "spec": {                       # the --min-accept gate's input
            "accepted_per_step": best["accepted_per_step"],
            "bit_identical": best["bit_identical"],
            "rounds": (best["stats"] or {}).get("rounds"),
            "rollback_pages": (best["stats"] or {}).get("rollback_pages"),
        },
        # greedy bit-identity IS the fidelity evidence here (token
        # space, not logits) — the pair rides the fidelity block so the
        # report renders it beside the kl pairs
        "fidelity": {"spec_vs_plain": {
            "greedy_match_frac": 1.0 if best["bit_identical"] else 0.0,
            "greedy_prefix_len": res["tokens"]
            if best["bit_identical"] else 0}},
        "timing": "median wall of full generates per arm (prefill + "
                  "rounds), identical prompt and token budget; baseline "
                  "= plain chunked-prefill + per-token decode over an "
                  "identical private paged pool",
    }
    return _stamp(rec)


def _latency_sweep(pi, make_batch, iters, batches=(1, 8, 32)):
    """batch-1 p50/p99 + best-batch throughput through a LIVE
    ParallelInference (jit dispatch, padding, host round-trip included —
    the quantity a serving SLO is written against)."""
    import numpy as np
    x1 = make_batch(1)
    pi.output(x1)                       # compile
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        pi.output(x1)
        lat.append(time.perf_counter() - t0)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(round(0.99 * (len(lat) - 1))))]
    sweep, best = {}, (None, 0.0)
    for b in batches:
        xb = make_batch(b)
        pi.output(xb)                   # compile this batch shape
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            pi.output(xb)
            times.append(time.perf_counter() - t0)
        thr = b / min(times)
        sweep[str(b)] = round(thr, 2)
        if thr > best[1]:
            best = (b, thr)
    return {"p50_ms": round(p50 * 1e3, 2), "p99_ms": round(p99 * 1e3, 2),
            "iters": iters, "best_batch": best[0],
            "best_batch_throughput": round(best[1], 2),
            "batch_sweep_samples_per_s": sweep}


def bench_inference_resnet_b1(batch, steps):
    """ResNet-50 online-serving latency through ParallelInference."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    net = ResNet50(num_classes=1000, compute_dtype=jnp.bfloat16).init()
    pi = ParallelInference(net, max_batch=64)
    rng = np.random.default_rng(0)

    def make_batch(b):
        return rng.random((b, 224, 224, 3), np.float32)

    stats = _latency_sweep(pi, make_batch, iters=max(steps, 5))
    rec = {"metric": "ResNet-50 batch-1 serving latency via "
                     "ParallelInference (bf16)",
           "value": stats["p50_ms"], "unit": "ms p50 (batch 1)",
           "best_batch_unit": "samples/sec", **stats,
           "memory": _mem_basic(net.params),
           "timing": "wall-clock ParallelInference.output round-trips, "
                     "compile excluded"}
    return _stamp(rec)


def bench_inference_bert_b1(batch, steps):
    """BERT-base (T=128) serving latency: the functional encoder served
    through ParallelInference via serving.FunctionalInferenceModel."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.parallel import ParallelInference
    from deeplearning4j_tpu.serving import FunctionalInferenceModel
    from deeplearning4j_tpu.zoo import transformer as tfm

    cfg = tfm.BertConfig(max_seq=128)
    params = tfm.bert_init(jax.random.PRNGKey(0), cfg)
    model = FunctionalInferenceModel(
        params, lambda p, ids: tfm.bert_forward(p, cfg, ids)[0])
    pi = ParallelInference(model, max_batch=64)
    rng = np.random.default_rng(0)

    def make_batch(b):
        return rng.integers(0, cfg.vocab_size, (b, cfg.max_seq)).astype(
            np.int32)

    stats = _latency_sweep(pi, make_batch, iters=max(steps, 5),
                           batches=(1, 8, 16))
    rec = {"metric": "BERT-base batch-1 serving latency via "
                     "ParallelInference (T=128)",
           "value": stats["p50_ms"], "unit": "ms p50 (batch 1)",
           "best_batch_unit": "samples/sec", **stats,
           "memory": _mem_basic(params),
           "timing": "wall-clock ParallelInference.output round-trips, "
                     "compile excluded"}
    return _stamp(rec)


INFERENCE_ROWS = ("inference_decode", "inference_ttft_1024",
                  "inference_ttft_4096", "inference_prefix_shared",
                  "inference_fleet", "inference_quant_kv",
                  "inference_spec_decode", "inference_scoring",
                  "inference_beam",
                  "inference_resnet_b1", "inference_bert_b1")

CONFIGS = {
    "resnet50": bench_resnet50_fit,   # headline: the REAL fit() entry point
    "resnet50_rawstep": bench_resnet50,
    "resnet50_fitscan": bench_resnet50_fitscan,
    "lenet": bench_lenet,
    "lenet_scan": bench_lenet_scan,
    "charnn": bench_charnn,
    "charnn_f32": bench_charnn_f32,
    "bert": bench_bert,
    "transformer": bench_transformer,
    "transformer_long": bench_transformer_long,
    "transformer_xlong": bench_transformer_xlong,
    "dpoverhead": bench_dpoverhead,
    "inference_decode": bench_inference_decode,
    "inference_ttft_1024": bench_inference_ttft_1024,
    "inference_ttft_4096": bench_inference_ttft_4096,
    "inference_prefix_shared": bench_inference_prefix_shared,
    "inference_fleet": bench_inference_fleet,
    "inference_quant_kv": bench_inference_quant_kv,
    "inference_spec_decode": bench_inference_spec_decode,
    "inference_scoring": bench_inference_scoring,
    "inference_beam": bench_inference_beam,
    "inference_resnet_b1": bench_inference_resnet_b1,
    "inference_bert_b1": bench_inference_bert_b1,
}

DEFAULTS = {  # (batch, steps) — batch swept on the real chip (r2): charnn
    # peaks at 256. r5: charnn runs the lax.scan LSTM path (the fused
    # pallas kernel measured slower in both dtypes — see
    # nn/layers/recurrent.py `fused` and scripts/diag_attn_r5_out.json)
    "resnet50": (128, 13),
    "resnet50_rawstep": (128, 13),
    "resnet50_fitscan": (128, 13),
    "lenet": (512, 25),
    "lenet_scan": (512, 25),
    "charnn": (256, 25),
    "charnn_f32": (256, 25),
    # bert: r5 composition sweep — remat-full + bf16-scores frees HBM for
    # b128 (MFU 0.61 vs 0.40 at the r4 b32 base config)
    "bert": (128, 13),
    # transformer: b32 composes the two measured r5 winners (b16
    # flash+save_attn 221.4k, b32 flash remat-full 223.3k tok/s); the
    # composed cell is captured by the official bench run itself
    "transformer": (32, 13),
    "transformer_long": (4, 9),   # 16k tokens/step (T=1024 runs 32k at b32)
    "transformer_xlong": (4, 9),  # T=8192 b4 remat-off — 32k tokens/step
    "dpoverhead": (1024, 20),
    # serving rows: batch = decode slots / fixed 1; steps = chain length
    # (decode) or timed reps (latency rows)
    "inference_decode": (8, 25),
    "inference_ttft_1024": (1, 3),
    "inference_ttft_4096": (1, 2),   # T=4096 prefill is minutes on CPU
    # prefix row: batch = requests sharing the 1024-token prefix, steps
    # = decode tokens per request; one cold prefill + batch-1 warm tails
    "inference_prefix_shared": (64, 4),
    # fleet row: batch = decode slots per replica, steps = decode tokens
    # per request; the burst trace + autoscaler window are fixed in-row
    "inference_fleet": (4, 6),
    # quant row: batch = probe decode slots; spec row: batch = draft
    # window k, steps = decode tokens per rep
    "inference_quant_kv": (4, 8),
    "inference_spec_decode": (8, 48),
    # scoring row: batch = prompts per wave, steps = timed waves;
    # beam row: batch = beam width, steps = new tokens per request
    "inference_scoring": (8, 3),
    "inference_beam": (4, 24),
    "inference_resnet_b1": (1, 15),
    "inference_bert_b1": (1, 12),
}


def _write_secondary(headline, secondary, inference=None):
    """Atomic write (temp + rename) after EVERY config, so a crash mid-run
    can never leave a stale artifact claiming to be current (the r3 failure:
    bench_secondary.json on disk was still the r2 output).

    ``inference`` (ISSUE 10 serving rows) defaults to whatever the
    artifact on disk already holds — a training-only capture must not
    silently drop the serving section."""
    import os
    path = _artifact_path()
    if inference is None:
        try:
            inference = json.loads(path.read_text()).get("inference")
        except Exception:  # noqa: BLE001 — absent/corrupt previous artifact
            inference = None
    out = {"headline": headline, "secondary": secondary}
    if inference:
        out["inference"] = inference
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(out, indent=2) + "\n")
    os.replace(tmp, path)


def _artifact_path():
    import os
    import pathlib
    return pathlib.Path(os.environ.get(
        "DL4J_TPU_BENCH_ARTIFACT",
        pathlib.Path(__file__).with_name("bench_secondary.json")))


@functools.lru_cache(maxsize=None)
def _trend_standalone():
    """obs/trend.py loaded by file path (the scripts/perf_gate.py
    precedent): the module is jax-free by design, the package import is
    not, and the parent process imports no JAX."""
    import importlib.util
    import pathlib
    spec = importlib.util.spec_from_file_location(
        "_dl4j_obs_trend_standalone",
        pathlib.Path(__file__).with_name("deeplearning4j_tpu")
        / "obs" / "trend.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ledger_append(row, rec):
    """Feed the perf trend ledger (ISSUE 15): one keyed record per
    captured row into runs/perf_ledger.jsonl — the longitudinal
    history scripts/perf_gate.py replays for regression verdicts.
    Called from the PARENT process only (_run_row_subprocess, for every
    row) so a `--model` subprocess can never double-append its own
    capture. Self-timed; the <2%-of-a-
    row-capture budget is pinned in tests/test_trend.py. Never fatal —
    a ledger failure must not cost a captured row."""
    try:
        trend = _trend_standalone()
        entry = trend.ledger_record(row, rec)
        if entry is None:
            return
        dt = trend.append_record(entry)
        print(f"[bench] trend ledger += {row} "
              f"({dt * 1e3:.2f} ms)", file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — decoration only
        print(f"[bench] trend ledger append failed for {row}: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)


class NoTPUError(RuntimeError):
    """A `--model` child found no TPU: nothing can be measured here."""


def require_tpu():
    """For a process that is about to take the chip (a `--model` child, a
    `scripts/diag_*.py` run): any other platform ends it NO_TPU_RC with
    no record printed — a CPU timing is never written under a chip
    metric's name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench] no TPU: jax found {dev.platform!r} "
              f"({dev.device_kind}); bench.py measures on the chip only",
              file=sys.stderr, flush=True)
        sys.exit(NO_TPU_RC)


def _run_row_subprocess(name, batch=None, steps=None):
    """One row in a fresh interpreter: the parent holds no chip, and rows
    stay isolated (residual allocator/compile state measurably depresses
    shared-process configs). Returns the row's record dict, or
    {"error": ...} on a row failure; raises NoTPUError when the child
    found no TPU. Serving rows get a longer leash (wall-clock rows with
    long prefills, not marginal chains)."""
    import os
    import subprocess
    script = os.path.abspath(__file__)
    timeout = 1800 if name in INFERENCE_ROWS else 900
    cmd = [sys.executable, script, "--model", name]
    cmd += [str(v) for v in (batch, steps) if v is not None]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, cwd=os.path.dirname(script))
    except Exception as e:  # noqa: BLE001 — callers keep other rows' records
        return {"error": f"{type(e).__name__}: {e}"[:500]}
    if proc.returncode == NO_TPU_RC:
        raise NoTPUError(proc.stderr.strip()[-500:])
    if proc.returncode == 0 and proc.stdout.strip():
        try:
            rec = json.loads(proc.stdout.strip().splitlines()[-1])
        except ValueError as e:
            return {"error": f"unparseable record: {e}"[:500]}
        if not isinstance(rec, dict):
            # a stray print can make the last stdout line parse to a
            # non-dict JSON value; callers rec.get() — never hand one
            # back (ADVICE r5 #3: it aborted the remaining rows)
            return {"error": f"non-dict record: {rec!r:.200}"}
        _ledger_append(name, rec)
        return rec
    return {"error": (proc.stdout + proc.stderr)[-500:]}


def _refresh_rows(names):
    """Re-capture the named secondary rows into the existing artifact —
    the tool-supported way to redo a contaminated row (e.g. a CPU-mesh
    measurement taken while the host was loaded) without hand-editing
    bench_secondary.json or paying for a full re-capture. The headline
    and untouched rows keep their records; a row whose re-capture FAILS
    also keeps its previous record (the error goes to stderr only —
    never overwrite a verified capture with an error entry)."""
    art = json.loads(_artifact_path().read_text())
    headline = art.get("headline", {})
    secondary = art.get("secondary", {})
    inference = art.get("inference", {})
    if headline.get("value") is None:
        print("no headline in artifact; run a full capture first",
              file=sys.stderr)
        return
    secondary.pop("_incomplete", None)  # a crashed full run may have left it
    for name in names:
        if name == "resnet50":
            print("resnet50 is the headline row — run a full capture "
                  "(python bench.py) to refresh it", file=sys.stderr)
            continue
        if name not in CONFIGS:
            print(f"unknown row {name!r}", file=sys.stderr)
            continue
        # serving rows live in the `inference` section, everything else
        # in `secondary` — one refresh path serves both
        section = inference if name in INFERENCE_ROWS else secondary
        rec = _run_row_subprocess(name)
        if rec.get("value") is None and name in section \
                and isinstance(section[name], dict) \
                and section[name].get("value") is not None:
            print(f"[bench] {name}: refresh FAILED "
                  f"({rec.get('error', rec)!s:.200}); previous record kept",
                  file=sys.stderr, flush=True)
            continue
        section[name] = rec
        print(f"[bench] {name}: {rec.get('value', rec)}",
              file=sys.stderr, flush=True)
        # write per row (crash safety)
        _write_secondary(headline, secondary, inference)


def main():
    """Returns the process exit code; NO_TPU_RC when a row child found no
    TPU (nothing is measured or written then)."""
    try:
        return _main(list(sys.argv[1:]))
    except NoTPUError as e:
        print(e, file=sys.stderr, flush=True)    # the child's own message
        return NO_TPU_RC


def _main(argv):
    """Parent: imports no JAX, runs rows as `--model` children. Child
    (`--model NAME [batch steps]`): requires a TPU, runs the one row
    in-process and prints its record."""
    if argv and argv[0] == "--refresh":
        if len(argv) < 2 or not argv[1]:
            print("usage: bench.py --refresh row1[,row2,...]   rows: "
                  + ",".join(sorted(CONFIGS)), file=sys.stderr)
            return 2
        _refresh_rows(argv[1].split(","))
        return 0
    if argv and argv[0] == "--model":
        model = argv[1]
        b, s = DEFAULTS[model]
        batch = int(argv[2]) if len(argv) > 2 else b
        steps = int(argv[3]) if len(argv) > 3 else s
        require_tpu()
        from deeplearning4j_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        print(json.dumps(CONFIGS[model](batch, steps)))
        return 0

    batch, steps = DEFAULTS["resnet50"]
    if argv:
        batch = int(argv[0])
    if len(argv) > 1:
        steps = int(argv[1])

    # The headline is a child like every other row: this process never
    # holds the chip. Nothing is written before it has a record — a run
    # without a TPU (or whose headline fails) leaves the artifact as it was.
    headline = _run_row_subprocess("resnet50", batch, steps)
    if headline.get("value") is None:
        print(f"[bench] headline failed: {headline.get('error', headline)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(headline), flush=True)
    _write_secondary(headline, {"_incomplete": "run in progress"})

    # Secondary configs (SURVEY §6) -> bench_secondary.json; never stdout.
    t_start = time.perf_counter()
    secondary = {}
    # transformer_xlong runs LAST: its T=8192 compile+run took ~10.5 min
    # in the first capture — against the 1500 s budget it must not be able
    # to starve the established rows of their slots.
    for name in ("lenet", "lenet_scan", "charnn", "bert", "transformer",
                 "transformer_long", "dpoverhead",
                 "resnet50_rawstep", "resnet50_fitscan",
                 "charnn_f32", "transformer_xlong"):
        if time.perf_counter() - t_start > 1500:
            secondary[name] = {"skipped": "time budget"}
        else:
            secondary[name] = _run_row_subprocess(name)
        print(f"[bench] {name}: "
              f"{secondary[name].get('value', secondary[name])}",
              file=sys.stderr, flush=True)
        secondary["_incomplete"] = "run in progress"
        _write_secondary(headline, secondary)
    secondary.pop("_incomplete", None)
    _write_secondary(headline, secondary)

    # Serving-plane rows (ISSUE 10) -> `inference` section. Own time
    # budget so a slow training capture can't permanently starve the
    # serving numbers (and vice versa). Prior rows are preserved on
    # per-row failure by _write_secondary's read-back only when this loop
    # never runs.
    t_inf = time.perf_counter()
    inference = {}
    for name in INFERENCE_ROWS:
        if time.perf_counter() - t_inf > 1200:
            inference[name] = {"skipped": "time budget"}
        else:
            inference[name] = _run_row_subprocess(name)
        print(f"[bench] {name}: "
              f"{inference[name].get('value', inference[name])}",
              file=sys.stderr, flush=True)
        _write_secondary(headline, secondary, inference)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""ResNet-50 MFU gap diagnostic (r4 item 2: verified fit() MFU >= 0.42).

Decomposes the ~51ms step (MFU 0.32 @ b128) into attributable costs, on the
real chip, using the bench harness's marginal-timing methodology:

  A. compiled cost_analysis: HLO-estimated bytes + flops -> roofline check
     (is the step bandwidth-bound? bytes / 819 GB/s v5e HBM vs flops / 197T)
  B. batch sweep 128/192/256 (donated step; MXU tiling efficiency)
  C. forward-only vs full train step (backward multiplier)
  D. BN-stats ablation: same net with BN in inference mode inside the step
     (running stats frozen) -> bounds what a fused/cheaper stats path could
     ever recover
  E. f32-stats vs bf16 activations audit: count of convert ops in the HLO

Usage: python scripts/diag_resnet.py [A B C D ...]   (default: all)
Writes scripts/diag_resnet_out.json.
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402

OUT = pathlib.Path(__file__).with_name("diag_resnet_out.json")
RESULTS = []


def emit(tag, **kw):
    rec = {"tag": tag, **kw}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    OUT.write_text(json.dumps(RESULTS, indent=2))


def _mk_step(batch, bn_frozen=False, s2d=False, remat=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    net = ResNet50(num_classes=1000, compute_dtype=jnp.bfloat16,
                   stem_space_to_depth=s2d, remat_segments=remat).init()
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(net.params)
    train_flag = not bn_frozen

    def train_step(params, states, opt_state, x, y):
        def loss_fn(p, s):
            acts, pre, new_s = net._forward(p, s, {"in": x}, train=train_flag,
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

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 224, 224, 3), np.float32),
                    jnp.bfloat16)
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    flops = total_flops(train_step, net.params, net.states, opt_state, x, y)
    jstep = jax.jit(train_step, donate_argnums=(0, 1, 2))

    def step_once(p, s, o):
        p, s, o, loss = jstep(p, s, o, x, y)
        return (p, s, o), loss

    carry = [net.params, net.states, opt_state]
    return bench.chain_runner(step_once, carry), flops, (jstep, net, x, y,
                                                         opt_state)


def phase_a():
    """HLO cost analysis roofline."""
    import jax
    run_chain, flops, (jstep, net, x, y, opt_state) = _mk_step(128)
    lowered = jstep.lower(net.params, net.states, opt_state, x, y)
    compiled = lowered.compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        bytes_acc = ca.get("bytes accessed", None)
        hlo_flops = ca.get("flops", None)
        rec = {"bytes_accessed": bytes_acc, "hlo_flops": hlo_flops,
               "analytic_flops": flops}
        if bytes_acc:
            rec["hbm_floor_ms_at_819GBs"] = round(bytes_acc / 819e9 * 1e3, 2)
        if hlo_flops:
            rec["mxu_floor_ms_at_197T"] = round(hlo_flops / 197e12 * 1e3, 2)
        emit("A cost_analysis b128", **rec)
    except Exception as e:  # noqa: BLE001 — diagnostic best-effort
        emit("A cost_analysis b128", error=f"{type(e).__name__}: {e}"[:300])


def phase_b():
    for b in (128, 192, 256):
        try:
            run_chain, flops, _ = _mk_step(b)
            timing = bench.measure_marginal(run_chain, n1=3, n2=13)
            rec = bench._record(f"B rawstep b{b}", "samples/sec/chip", b,
                                timing, flops, batch=b)
            emit(rec.pop("metric"), **rec)
        except Exception as e:  # noqa: BLE001
            emit(f"B rawstep b{b}", error=f"{type(e).__name__}: {e}"[:300])


def phase_c():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.utils.tracing import total_flops
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    batch = 128
    net = ResNet50(num_classes=1000, compute_dtype=jnp.bfloat16).init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((batch, 224, 224, 3), np.float32),
                    jnp.bfloat16)

    def fwd(params, states, x):
        acts, pre, new_s = net._forward(params, states, {"in": x},
                                        train=True, rng=None,
                                        stop_at_output_preact=True)
        return pre["out"], new_s

    jfwd = jax.jit(fwd)
    flops = total_flops(fwd, net.params, net.states, x)

    # chain on states so steps are data-dependent
    carry_ps = (net.params, net.states)

    def run_chain(n):
        nonlocal carry_ps
        out = None
        for _ in range(n):
            out, new_s = jfwd(carry_ps[0], carry_ps[1], x)
            carry_ps = (carry_ps[0], new_s)
        return out[0, 0]

    timing = bench.measure_marginal(run_chain, n1=3, n2=13)
    rec = bench._record("C forward-only b128 (train=True)",
                        "samples/sec/chip", batch, timing, flops)
    emit(rec.pop("metric"), **rec)


def phase_d():
    try:
        run_chain, flops, _ = _mk_step(128, bn_frozen=True)
        timing = bench.measure_marginal(run_chain, n1=3, n2=13)
        rec = bench._record("D rawstep b128 BN-frozen (stats ablation)",
                            "samples/sec/chip", 128, timing, flops)
        emit(rec.pop("metric"), **rec)
    except Exception as e:  # noqa: BLE001
        emit("D BN-frozen", error=f"{type(e).__name__}: {e}"[:300])


def phase_e():
    import re
    _run, _fl, (jstep, net, x, y, opt_state) = _mk_step(128)
    txt = jstep.lower(net.params, net.states, opt_state, x, y
                      ).as_text()
    conv_f32 = len(re.findall(r"convert.*f32", txt))
    conv_bf16 = len(re.findall(r"convert.*bf16", txt))
    convs = len(re.findall(r"conv_general_dilated|convolution", txt))
    emit("E HLO convert audit b128", converts_to_f32=conv_f32,
         converts_to_bf16=conv_bf16, convolutions=convs,
         hlo_bytes=len(txt))


def phase_f():
    """r4: space-to-depth stem A/B (exact-equivalent transformation)."""
    for b in (128, 256):
        try:
            run_chain, flops, _ = _mk_step(b, s2d=True)
            timing = bench.measure_marginal(run_chain, n1=3, n2=13)
            rec = bench._record(f"F rawstep b{b} s2d-stem",
                                "samples/sec/chip", b, timing, flops,
                                batch=b)
            emit(rec.pop("metric"), **rec)
        except Exception as e:  # noqa: BLE001
            emit(f"F rawstep b{b} s2d", error=f"{type(e).__name__}: {e}"[:300])


def phase_g():
    """r4: segmented activation remat (jax.checkpoint over live-set-minimal
    cuts). The step is HBM-bound with idle MXU headroom (A: 14.6ms MXU floor
    vs 47.5ms measured) — recompute is free if it cuts activation traffic."""
    for nseg in (16, 8, 4):   # block-boundary-ish first: likeliest winner
        try:
            run_chain, flops, _ = _mk_step(128, remat=nseg)
            timing = bench.measure_marginal(run_chain, n1=3, n2=13)
            rec = bench._record(f"G rawstep b128 remat{nseg}",
                                "samples/sec/chip", 128, timing, flops,
                                batch=128)
            emit(rec.pop("metric"), **rec)
        except Exception as e:  # noqa: BLE001
            emit(f"G remat{nseg}", error=f"{type(e).__name__}: {e}"[:300])


def phase_h():
    """remat + space-to-depth stem composed: s2d measured FLAT while the
    step was bandwidth-bound (idle MXU absorbed the stem's padded-lane
    waste); if remat shifts the bottleneck toward compute, the stem's MXU
    saving should start to pay."""
    for nseg in (16, 8):
        try:
            run_chain, flops, _ = _mk_step(128, s2d=True, remat=nseg)
            timing = bench.measure_marginal(run_chain, n1=3, n2=13)
            rec = bench._record(f"H rawstep b128 remat{nseg}+s2d",
                                "samples/sec/chip", 128, timing, flops,
                                batch=128)
            emit(rec.pop("metric"), **rec)
        except Exception as e:  # noqa: BLE001
            emit(f"H remat{nseg}+s2d", error=f"{type(e).__name__}: {e}"[:300])


PHASES = {"A": phase_a, "B": phase_b, "C": phase_c, "D": phase_d,
          "E": phase_e, "F": phase_f, "G": phase_g, "H": phase_h}

if __name__ == "__main__":
    which = sys.argv[1:] or list(PHASES)
    bench.require_tpu()    # one process per chip: this one takes it
    for w in which:
        t0 = time.perf_counter()
        PHASES[w]()
        print(f"[diag] phase {w} done in {time.perf_counter()-t0:.0f}s",
              file=sys.stderr, flush=True)

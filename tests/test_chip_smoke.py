"""chip_smoke.py rehearsed on the CPU: every phase function driven at a
tiny configuration (the ``--chips 4`` phases on four virtual devices), the
no-accelerator exit of ``chip_smoke.py`` and ``bench.py``, and the rule that
``bench.py``'s parent process imports no JAX. The chip itself is reached
only by running ``python chip_smoke.py`` through the builder's tool."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402  (repo-root script)

from deeplearning4j_tpu.zoo import transformer as tfm  # noqa: E402
from deeplearning4j_tpu.zoo.resnet import ResNet50  # noqa: E402


def _tiny_lm(**kw):
    base = dict(vocab_size=128, d_model=32, n_heads=2, n_layers=2, d_ff=64,
                max_seq=64, dtype=jnp.float32, attn_scores_bf16=False)
    base.update(kw)
    return tfm.TransformerConfig(**base)


@pytest.fixture()
def autotune_store(tmp_path, monkeypatch):
    from deeplearning4j_tpu.kernels import autotune
    monkeypatch.setattr(autotune, "_CACHE_PATH", tmp_path / "autotune.json")
    autotune._memory_cache.clear()
    return autotune


def test_phase_train_lm_tiny():
    rec = chip_smoke.phase_train_lm(
        _tiny_lm(fused_loss=True, remat=True, remat_policy="save_attn"),
        batch=4, steps=4)
    assert rec["phase"] == "train_lm" and len(rec["losses"]) == 4
    assert rec["losses"][-1] < rec["losses"][0]
    assert rec["attention_path"] == "xla_sdpa"      # no flash off the chip
    assert rec["flash_in_program"] is False and rec["flash_blocks"] is None
    assert rec["flash_calls_per_layer"] == 0
    assert rec["compile_s"] > 0 and rec["steady_step_ms"] > 0


@pytest.mark.parametrize("policy,calls", [("save_attn", 3), ("full", 4)])
def test_phase_train_lm_counts_flash_calls_per_layer(monkeypatch, policy,
                                                     calls):
    """With the kernels in the step (interpret mode here) the record says
    how many Pallas calls a layer holds: under ``save_attn`` the backward
    pass reads the saved output and lse, under ``full`` it runs the forward
    kernel again."""
    monkeypatch.setattr(jax, "device_count", lambda: 1)   # as on one chip
    rec = chip_smoke.phase_train_lm(
        _tiny_lm(fused_loss=True, remat=True, remat_policy=policy,
                 use_flash_attention=True), batch=2, steps=2)
    assert rec["attention_path"] == "flash"
    assert rec["flash_calls_per_layer"] == calls
    assert rec["losses"][-1] < rec["losses"][0]


def test_phase_train_lm_requires_flash_when_asked():
    """On the chip the phase insists on the kernel; a program without it
    (here: the CPU) fails the phase, and with it the smoke."""
    with pytest.raises(chip_smoke.SmokeFailure, match="flash kernel"):
        chip_smoke.phase_train_lm(_tiny_lm(), batch=2, steps=2,
                                  require_flash=True)


@dataclass
class _ShallowResNet(ResNet50):
    """ResNet50's own graph builder, one narrow block per stage."""
    STAGES = ((1, (8, 8, 16)), (1, (8, 8, 32)))


def test_phase_fit_resnet_shallow():
    model = _ShallowResNet(num_classes=5, input_shape=(32, 32, 3),
                           compute_dtype=jnp.bfloat16)
    rec = chip_smoke.phase_fit_resnet50(model, batch=4, iters=3)
    assert rec["phase"] == "fit_resnet50" and len(rec["losses"]) == 3
    assert rec["fused_bn_act_in_output_program"] is False   # "auto": TPU only
    assert rec["fused_bn_act_max_err_vs_reference"] <= 0.0625
    assert rec["compile_s"]["cg_train_step"] > 0
    with pytest.raises(chip_smoke.SmokeFailure, match="fused BN-act"):
        chip_smoke.phase_fit_resnet50(model, batch=4, iters=2,
                                      require_fused=True)


def _plain_batch(i, n):
    from deeplearning4j_tpu.data.dataset import DataSet
    return DataSet(np.full((n, 8), i, np.float32),
                   np.full((n, 2), i, np.float32))


def _multi_batch_with_masks(i, n):
    """Two inputs, one output, a mask on the second input and on the
    labels: the masks are what takes the large ones over the slot's
    size (features and labels alone are 12 KB of its 16 KB)."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    return MultiDataSet(
        [np.full((n, 2), i, np.float32), np.full((n, 3), i, np.float32)],
        [np.full((n, 1), i, np.float32)],
        [None, np.full((n, 3), i % 2, np.float32)],
        [np.full((n, 1), 1, np.float32)])


@pytest.mark.parametrize("batch, large", [(_plain_batch, 4096),
                                          (_multi_batch_with_masks, 512)],
                         ids=["DataSet", "MultiDataSet_with_masks"])
def test_async_iterator_batch_larger_than_ring_slot_keeps_order(batch, large):
    """What stopped `fit(DataSetIterator)` at the headline's own size: a
    batch that does not fit a ring slot (ImageNet b128 f32 is 77 MB
    against the 64 MB default) killed the prefetch producer. It now rides
    the queue with a marker in the ring, unpacked (ISSUE 27: the very
    arrays the source made, never serialized): every batch arrives, in
    order, mixed with ring-sized ones, through `reset()` too."""
    from deeplearning4j_tpu.data.async_iter import (
        AsyncDataSetIterator, _arrays)
    from deeplearning4j_tpu.utils import native
    if not native.has_native():
        pytest.skip("no native ring to overflow")
    sizes = (4, large, 4, large, large, 4)
    made = {}       # by place in the epoch; each epoch makes its own

    class Mixed:
        batch_size = 4

        def __iter__(self):
            for i, n in enumerate(sizes):
                made[i] = batch(i, n)
                yield made[i]

    slot = 16 << 10
    it = AsyncDataSetIterator(Mixed(), queue_size=2, slot_size=slot)
    try:
        assert it._ring is not None
        for epoch in range(2):
            got = list(it)      # the producer has ended when this has
            assert [b.num_examples() for b in got] == list(sizes)
            for i, b in enumerate(got):
                m = made[i]
                sent, have = _arrays(m), _arrays(b)
                assert list(have) == list(sent)     # a missing mask stays so
                nbytes = sum(a.nbytes for a in sent.values())
                assert (nbytes > slot) == (sizes[i] == large)
                # over the slot: by reference; under it: an equal copy
                assert (b is m) == (sizes[i] == large)
                for name in sent:
                    np.testing.assert_array_equal(have[name], sent[name])
                    assert have[name].dtype == sent[name].dtype
                assert float(np.asarray(have[next(iter(have))]).flat[0]) == i
            it.reset()
    finally:
        it.close()


def test_phase_serve_lm_tiny(autotune_store):
    cfg = _tiny_lm(max_seq=256)          # chunk_len 128: 150 needs two
    rec = chip_smoke.phase_serve_lm(
        cfg, n_slots=4, page_len=8, prompt_lens=(5, 12, 150), shared=(16, 6),
        new_tokens=6)
    assert rec["phase"] == "serve_lm" and rec["requests"] == 5
    assert rec["decode_path"] == "paged_gather"   # auto never races off-TPU
    assert rec["kv_dtype"] == "float32" and rec["races"] == {}
    assert rec["compiles_after_warm"] == 0
    assert rec["prefix"]["prefix_hits"] >= 1
    assert max(c or 1 for c in rec["prefill_chunks"]) >= 2
    assert rec["first_token_logits_vs_f32_dense"]["kl_max"] <= 1e-6
    assert rec["decode_logits_vs_f32_dense"]["max_abs_err"] <= 1e-3


def test_phase_serve_lm_fails_on_wrong_logits(autotune_store, monkeypatch):
    """A phase whose check fails raises — main() has no handler, so the
    smoke ends non-zero."""
    monkeypatch.setattr(chip_smoke, "LOGIT_MAX_ABS", -1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="dense forward"):
        chip_smoke.phase_serve_lm(
            _tiny_lm(max_seq=128), n_slots=2, page_len=8,
            prompt_lens=(5,), shared=(16, 6), new_tokens=4)


def test_phase_sharded_train_lm_four_virtual_devices(devices8):
    cfg = _tiny_lm(fused_loss=True, remat=True, remat_policy="save_attn")
    rec = chip_smoke.phase_sharded_train_lm(cfg, batch=4,
                                            devices=devices8[:4], steps=3)
    arms = rec["arms"]
    assert set(arms) == {"one_device", "dp2_tp2"}
    assert arms["dp2_tp2"]["wqkv_devices"] == 4
    assert arms["one_device"]["wqkv_devices"] == 1
    # column-parallel: the tp axis halves wqkv's last dimension
    assert arms["dp2_tp2"]["wqkv_shard_shape"][-1] * 2 == \
        arms["one_device"]["wqkv_shard_shape"][-1]
    assert max(rec["loss_abs_diff"]) <= 1e-4        # float32 here
    assert {a["attention_path"] for a in arms.values()} == {"xla_sdpa"}


def test_phase_sharded_fit_conv_four_virtual_devices(devices8):
    rec = chip_smoke.phase_sharded_fit_conv(batch=16, devices=devices8[:4],
                                            iters=3)
    assert rec["param_devices"] == 4
    assert rec["replica_drift"]["bit_identical"] is True
    assert max(rec["loss_abs_diff"]) <= chip_smoke.SHARDED_LOSS_TOL


def _run(cmd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *cmd], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_main_without_accelerator_exits_nonzero_and_prints_no_ok(argv):
    proc = _run(["chip_smoke.py", *argv])
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout and proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_main_alone_in_a_directory_fails(tmp_path):
    """The script without the program (as the driver also runs it) cannot
    pass: past the device check it needs the package."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    code = ("import jax, sys, types\n"
            "dev = types.SimpleNamespace(platform='tpu', device_kind='x')\n"
            "jax.devices = lambda *a: [dev]\n"
            "import chip_smoke\n"
            "sys.exit(chip_smoke.main([]))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env={**os.environ, "JAX_PLATFORMS": "cpu",
                               "PYTHONPATH": ""},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and '"ok": true' not in proc.stdout
    assert "deeplearning4j_tpu" in proc.stderr          # ModuleNotFoundError


def test_bench_parent_imports_no_jax_before_its_first_child(tmp_path):
    """One process per chip: the parent of `python bench.py` (and of
    --refresh) must not have JAX loaded when it starts a row child."""
    code = (
        "import json, sys\n"
        "import bench\n"
        "seen = []\n"
        "def child(name, *a):\n"
        "    seen.append((name, 'jax' in sys.modules))\n"
        "    raise bench.NoTPUError('synthetic: no TPU')\n"
        "bench._run_row_subprocess = child\n"
        "out = {}\n"
        "for argv in (['bench.py'], ['bench.py', '--refresh', 'lenet']):\n"
        "    sys.argv = argv\n"
        "    out[' '.join(argv)] = bench.main()\n"
        "print(json.dumps({'seen': seen, 'rc': out,\n"
        "                  'jax_after': 'jax' in sys.modules}))\n")
    art = tmp_path / "bench_secondary.json"
    art.write_text(json.dumps({"headline": {"value": 1.0}, "secondary": {}}))
    proc = _run(["-c", code], DL4J_TPU_BENCH_ARTIFACT=str(art))
    assert proc.returncode == 0, proc.stderr[-800:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["seen"] == [["resnet50", False], ["lenet", False]]
    assert got["jax_after"] is False
    assert set(got["rc"].values()) == {3}               # bench.NO_TPU_RC


def test_compile_cache_helper_sets_one_fixed_directory():
    """Unset: <checkout>/.jax_cache; set: the code sets none (JAX reads
    the variable itself)."""
    code = ("import jax, json\n"
            "from deeplearning4j_tpu.utils import compile_cache as cc\n"
            "before = getattr(jax.config, cc.CONFIG_NAME)\n"
            "got = cc.enable_compile_cache()\n"
            "print(json.dumps([before, got, "
            "getattr(jax.config, cc.CONFIG_NAME)]))\n")
    proc = _run(["-c", code])
    assert proc.returncode == 0, proc.stderr[-500:]
    before, got, after = json.loads(proc.stdout.strip().splitlines()[-1])
    assert before is None and got == after == str(REPO / ".jax_cache")
    proc = _run(["-c", code], JAX_COMPILATION_CACHE_DIR="/somewhere/fixed")
    before, got, after = json.loads(proc.stdout.strip().splitlines()[-1])
    assert before == got == after == "/somewhere/fixed"

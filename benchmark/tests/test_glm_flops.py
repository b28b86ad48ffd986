"""``glm_flops.py`` against counts worked out by hand from the published
sizes of GLM-4.7-Flash and the share the configuration file holds (ISSUE
34's arithmetic)."""
import json
from pathlib import Path

import pytest

import glm_flops

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "glm-4.7-flash.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "glm47flash-train-b1-t8192"


def test_config_file_holds_the_published_widths_and_the_share():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == "glm-4.7-flash"][0]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) \
        == sorted(CONFIG["published"])
    assert entry["source"] == CONFIG["source"]
    for key, value in {"hidden_size": 2048, "intermediate_size": 10240,
                       "moe_intermediate_size": 1536, "q_lora_rank": 768,
                       "kv_lora_rank": 512, "qk_nope_head_dim": 192,
                       "qk_rope_head_dim": 64, "v_head_dim": 256,
                       "num_attention_heads": 20, "num_key_value_heads": 20,
                       "num_experts_per_tok": 4, "n_shared_experts": 1,
                       "routed_scaling_factor": 1.8, "norm_topk_prob": True,
                       "first_k_dense_replace": 1, "n_group": 1,
                       "topk_group": 1, "num_nextn_predict_layers": 1,
                       "rope_theta": 1000000, "rms_norm_eps": 1e-05,
                       "tie_word_embeddings": False,
                       "rope_scaling": None}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19360)
    for item in ("rope_pairs", "router_bias", "weight_guard", "no_aux_loss",
                 "prediction_module", "prediction_weight", "init",
                 "optimizer"):
        assert len(CONFIG["assumed"][item]) > 40, item
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("glm-4.7-flash", "lm-b1-t8192", 1)
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "lm_train_tokens_per_s"][0]
    assert rate["workloads"][-1] == CELL
    # five metrics read what this model alone has; the eight readings the
    # cell shares with the accepted expert cells keep those cells' names
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    assert sorted(n for n in mine if n.endswith(".glm")) == [
        "experts_roofline_pct.glm", "flash_roofline_pct.glm",
        "mfu_pct.glm", "mla_time_share_pct.glm", "mtp_time_share_pct.glm"]
    assert sorted(n for n in mine if not n.endswith(".glm")) == [
        "attn_time_share_pct.moe", "compiles_in_window.moe",
        "expert_load_max_over_mean.moe", "head_time_share_pct.lm",
        "idle_pct.moe", "moe_dropped.moe", "moe_time_share_pct.moe",
        "peak_hbm_gb.moe"]
    for name in mine:
        assert (BENCH / "layer_metrics" / f"{name}.json").is_file(), name


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog on this machine")
def test_every_key_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "GLM-4.7-Flash")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_parameters_held_are_11_30_gb_at_16_bytes():
    d = 2048
    attention = d * 768 + 768 + 768 * 5120 + d * 576 + 512 + 512 * 8960 \
        + 5120 * d
    assert attention == 21_759_232                   # ISSUE 34: 21.76 M
    expert = 3 * d * 1536
    dense = attention + 2 * d + 3 * d * 10240
    layer = attention + 2 * d + d * 64 + 64 + expert + 8 * expert
    module = 3 * d + 2 * d * d + layer
    total = dense + 4 * layer + module + 2 * 19360 * d + d
    assert (round(dense / 1e6, 2), round(layer / 1e6, 2),
            round(module / 1e6, 2)) == (84.68, 106.83, 115.22)
    assert total == 706_518_848
    assert f"{total:,}" in CONFIG["held_here"]["parameters"]
    assert round(total * 16 / 1e9, 2) == 11.30
    # whole, an expert layer is 10.2 GB: a chip cannot hold two
    whole = attention + 2 * d + d * 64 + 64 + 65 * expert
    assert round(whole / 1e6, 1) == 635.3 and whole * 16 > 10.1e9


def test_forward_parts_are_the_issues_arithmetic():
    parts = glm_flops.forward_parts_per_token(CONFIG, 8192)
    m = {k: round(v / 1e6, 2) for k, v in parts.items()}
    assert m == {"latent_projections": 261.10, "scores": 503.32,
                 "dense_mlp": 125.83, "router": 1.31, "shared_expert": 94.37,
                 "routed_experts": 47.19, "joining_projection": 16.78,
                 "head": 158.60}
    # per layer: 43.5 M of latent projections, 83.9 M of scores
    assert round(parts["latent_projections"] / 6e6, 1) == 43.5
    assert round(parts["scores"] / 6e6, 1) == 83.9
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 1208.5
    assert glm_flops.train_flops_per_token(CONFIG, 8192) == 3 * total
    assert round(3 * total * 8192 / 1e12, 2) == 29.70
    latent = parts["latent_projections"] + parts["scores"]
    assert round(100 * latent / total) == 63
    assert round(100 * parts["scores"] / total) == 42
    assert round(100 * parts["head"] / total) == 13
    assert glm_flops.held_assignments_per_token(CONFIG) == 0.5
    # at half the context only the scores change
    half = glm_flops.forward_parts_per_token(CONFIG, 4096)
    assert half["scores"] == parts["scores"] / 2
    assert {k: v for k, v in half.items() if k != "scores"} == \
        {k: v for k, v in parts.items() if k != "scores"}


def test_flash_and_expert_counts_of_one_step():
    flops, nbytes = glm_flops.flash_flops_bytes(1, 20, 8192, 192, 64, 256,
                                                5, 1)
    # six blocks x six products x 2 x 20 heads x 8192 x 4096 keys x 256
    assert flops == 6 * 6 * 2 * 20 * 8192 * 4096 * 256
    assert nbytes == 6 * 12 * 20 * 8192 * 256 * 2
    # compute-bound on a v5e: 62.8 ms against 7.4 ms of traffic
    assert flops / 197e12 > 8 * nbytes / 819e9
    flops, nbytes = glm_flops.experts_flops_bytes(1, 8192, 2048, 1536, 8, 4,
                                                  5, 1, 1, 0.125)
    rows = 8192 * 4 * 0.125             # 512 a held expert
    assert rows / 8 == 512
    assert flops == 5 * 3 * 2 * rows * (2048 * 3072 + 1536 * 2048)
    assert nbytes == 5 * 3 * 2 * (
        rows * 2048 + 8 * 2048 * 3072 + rows * 3072
        + rows * 1536 + 8 * 1536 * 2048 + rows * 2048)
    # twice the share, twice the operations
    assert glm_flops.experts_flops_bytes(1, 8192, 2048, 1536, 8, 4, 5, 1, 1,
                                         0.25)[0] == 2 * flops


def test_metric_files_name_functions_and_keys_that_exist():
    import flops
    traffic = json.loads((BENCH / "traffic" / "lm-b1-t8192.json").read_text())
    assert (traffic["driver"], traffic["batch"], traffic["seq"]) == \
        ("glm_train", 1, 8192)
    assert (traffic["pool_batches"], traffic["loss_fetch_every"],
            traffic["check_steps"], traffic["trace_from_step"],
            traffic["trace_steps"]) == (64, 5, 3, 30, 5)
    look = {**CONFIG, **traffic, "moe_local_share": 0.125}
    for name in ("flash_roofline_pct", "experts_roofline_pct"):
        spec = json.loads((BENCH / "layer_metrics" / f"{name}.glm.json")
                          .read_text())["args"]
        work, nbytes = flops.resolve(spec["flops_fn"])(
            *[look[k] for k in spec["shape"]])
        assert work > 1e12 and nbytes > 1e9
    spec = json.loads((BENCH / "layer_metrics" / "mfu_pct.glm.json")
                      .read_text())["args"]
    assert flops.resolve(spec["flops_fn"])(CONFIG, traffic["seq"]) > 3.6e9
    limits = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())
    held = {k for k, v in limits.items() if v["limit"] is not None}
    assert {"loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
            "mtp_loss_gap_step1", "grad_norm_gap", "delta_norm_gap",
            "choice_mismatch_share"} <= held
    for name in held:
        spec = limits[name]
        assert spec["lower"] < spec["limit"], name
        assert spec["upper"] is None or spec["limit"] < spec["upper"], name

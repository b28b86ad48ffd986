"""``zaya_flops.py`` against counts worked out by hand from the published
sizes of ZAYA1-8B and the share the configuration file holds."""
import json
from pathlib import Path

import pytest

import zaya_flops

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "zaya1-8b.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_config_file_holds_the_published_widths_and_the_share():
    row = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in row["configs"] if c["name"] == "zaya1-8b"][0]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) \
        == sorted(CONFIG["published"])
    assert entry["source"] == CONFIG["source"]
    for key, value in {"hidden_size": 2048, "head_dim": 128,
                       "num_attention_heads": 8, "num_key_value_heads": 2,
                       "moe_intermediate_size": 2048, "router_hidden_size": 256,
                       "num_experts_per_tok": 1, "cca_time0": 2, "cca_time1": 2,
                       "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
                       "max_position_embeddings": 131072, "hidden_act": "silu",
                       "tie_word_embeddings": True,
                       "sliding_window": None}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert CONFIG["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                   "vocab_size": 262272}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 8, 131136)
    assert CONFIG["layer_types"] == ["hybrid"] * 40
    assert len(CONFIG["departures"]) == 2    # the two draws, assumed.init
    for step in ("papers", "step2_values", "step3_mean", "step4_convolutions",
                 "step5_norm_rope", "step6_residual", "step7_router", "init"):
        assert len(CONFIG["assumed"][step]) > 40, step


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog on this machine")
def test_every_key_of_the_catalogs_row_is_in_the_file_or_in_reduced():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines()
               if json.loads(line)["name"] == "ZAYA1-8B")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_parameters_held_are_11_14_gb_at_16_bytes():
    d, f, r, c = 2048, 2048, 256, 1280
    cca = d * (1024 + 256 + 256) + 1024 * d \
        + 2 * c + c + 2 * 10 * 128 * 128 + c + 2
    router = d * r + r + r + 2 * (r * r + r) + r * 17 + 17
    layer = cca + router + 2 * d + 8 * d + 8 * 3 * d * f
    total = 4 * layer + 131136 * d + d
    assert total == 696249420       # the tree init_params draws
    assert round(total / 1e6, 1) == 696.2
    assert round(16 * total / 1e9, 2) == 11.14


def test_forward_parts_and_the_whole_training_token():
    parts = zaya_flops.forward_parts_per_token(CONFIG, 32768)
    assert parts["projections"] == 4 * 2 * (2048 * 1536 + 1024 * 2048) \
        == 41943040
    assert parts["convolutions"] == 4 * 2 * (2 * 1280 + 2 * 10 * 128 * 128) \
        == 2641920
    assert parts["scores"] == 4 * 4 * 1024 * 16384 == 268435456
    assert parts["router"] == 4 * 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 17) \
        == 5277696
    assert parts["experts"] == pytest.approx(4 * 8 / 17 * 6 * 2048 * 2048)
    assert parts["head"] == 2 * 2048 * 131136 == 537133056
    assert zaya_flops.held_share(CONFIG) == 8 / 17
    total = zaya_flops.train_flops_per_token(CONFIG, 32768)
    assert total == 3 * sum(parts.values())
    assert round(sum(parts.values()) / 1e6, 1) == 902.8
    assert round(parts["head"] / sum(parts.values()), 3) == 0.595
    # 32,768 tokens a step
    assert round(total * 32768 / 1e12, 2) == 88.75
    # the whole model: 40 layers and the whole table
    model = 10 * (sum(parts.values()) - parts["head"]) + 2 * parts["head"]
    assert round(2 * parts["head"] / model, 2) == 0.23


def test_flash_counts_four_causal_layers_of_grouped_heads():
    flops, nbytes = zaya_flops.flash_flops_bytes(1, 8, 2, 32768, 128, 4)
    assert flops == 4 * 6 * 2 * 8 * 32768 * 128 * 16384
    assert nbytes == 4 * 6 * (8 + 2) * 32768 * 128 * 2
    # the whole step's score work is 3 x the forward's two products
    assert flops == 3 * 32768 * zaya_flops.forward_parts_per_token(
        CONFIG, 32768)["scores"]


def test_expert_products_at_the_load_the_counters_read():
    flops, nbytes = zaya_flops.experts_flops_bytes(
        1, 32768, 2048, 2048, 8, 1, 4, 8 / 17)
    rows = 32768 * 8 / 17
    assert flops == pytest.approx(4 * 3 * 2 * rows * (2048 * 4096 + 2048 * 2048))
    assert flops == pytest.approx(3 * 32768 * zaya_flops.forward_parts_per_token(
        CONFIG, 32768)["experts"])
    weights = 8 * 3 * 2048 * 2048
    acts = rows * (2048 + 4096 + 2048 + 2048)
    assert nbytes == pytest.approx(4 * 3 * (weights + acts) * 2)

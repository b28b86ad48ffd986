"""``moe_flops.py`` against counts worked out by hand from the published
sizes of SmallThinker-21BA3B and the share the configuration file holds."""
import json
from pathlib import Path

import pytest

import moe_flops

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "smallthinker-21b-a3b.json").read_text())


def test_config_file_holds_the_published_widths_and_the_share():
    row = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in row["configs"] if c["name"] == "smallthinker-21b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) \
        == sorted(CONFIG["published"])
    for key, value in {"hidden_size": 2560, "head_dim": 128,
                       "moe_ffn_hidden_size": 768, "sliding_window_size": 4096,
                       "moe_num_active_primary_experts": 6,
                       "rope_theta": 1500000, "rms_norm_eps": 1e-06,
                       "max_position_embeddings": 16384}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "vocab_size": 151936}
    assert (CONFIG["num_hidden_layers"], CONFIG["moe_num_primary_experts"],
            CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"],
            CONFIG["vocab_size"]) == (4, 16, 7, 1, 37984)
    assert len(CONFIG["rope_layout"]) == len(CONFIG["sliding_window_layout"]) == 52
    assert CONFIG["rope_layout"][:4] == [0, 1, 1, 1] and CONFIG["departures"] == []


def test_parameters_held_are_9_5_gb_at_16_bytes():
    d, f = 2560, 768
    layer = d * (7 + 1 + 1) * 128 + 7 * 128 * d + d * 64 + 16 * 3 * d * f + 2 * d
    total = 4 * layer + 2 * 37984 * d + d
    assert round(total / 1e6, 1) == 593.6
    assert round(16 * total / 1e9, 2) == 9.50


@pytest.mark.parametrize("seq,window,want", [
    (8192, None, 4096.0), (8192, 4096, 3072.0), (1024, 4096, 512.0),
    (4096, 4096, 2048.0)])
def test_keys_a_query_sees_on_average(seq, window, want):
    assert moe_flops.keys_seen(seq, window) == want


def test_forward_parts_and_the_whole_training_token():
    parts = moe_flops.forward_parts_per_token(CONFIG, 8192)
    assert parts == {"projections": 41943040.0, "router": 1310720.0,
                     "experts": 70778880.0, "scores": 47710208.0,
                     "head": 194478080.0}
    assert moe_flops.held_assignments_per_token(CONFIG) == 1.5
    total = moe_flops.train_flops_per_token(CONFIG, 8192)
    assert total == 3 * sum(parts.values()) == 1068662784.0
    assert round(parts["head"] / sum(parts.values()), 3) == 0.546
    # 16,384 tokens a step
    assert round(total * 16384 / 1e12, 2) == 17.51


def test_flash_band_counts_three_window_layers_and_one_full():
    flops, nbytes = moe_flops.flash_band_flops_bytes(
        2, 7, 1, 8192, 128, 4096, CONFIG["sliding_window_layout"], 4)
    one_product = 2 * 2 * 7 * 8192 * 128          # per key seen
    assert flops == 6 * one_product * (4096 + 3 * 3072)
    assert nbytes == 4 * 6 * 2 * (7 + 1) * 8192 * 128 * 2
    # the whole step's score work is 3 x the forward's two products
    assert flops == 3 * 16384 * moe_flops.forward_parts_per_token(
        CONFIG, 8192)["scores"]


def test_expert_products_at_the_load_the_counters_read():
    flops, nbytes = moe_flops.experts_flops_bytes(
        2, 8192, 2560, 768, 16, 6, 4, 0.25)
    rows = 16384 * 1.5
    assert flops == 4 * 3 * 2 * rows * (2560 * 1536 + 768 * 2560)
    # even routing is what the whole step's count assumes
    assert flops == 3 * 16384 * moe_flops.forward_parts_per_token(
        CONFIG, 8192)["experts"]
    weights = 16 * 3 * 2560 * 768
    acts = rows * (2560 + 1536 + 768 + 2560)
    assert nbytes == 4 * 3 * (weights + acts) * 2
    less, _ = moe_flops.experts_flops_bytes(2, 8192, 2560, 768, 16, 6, 4, 0.2)
    assert less == pytest.approx(0.8 * flops)

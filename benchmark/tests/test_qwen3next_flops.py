"""``qwen3next_flops.py`` against counts worked out by hand from the
published sizes of Qwen3-Next-80B-A3B and the share the configuration file
holds."""
import json
from pathlib import Path

import pytest

import qwen3next_flops as qf

BENCH = Path(__file__).resolve().parents[1]
CONFIG = json.loads((BENCH / "configs" / "qwen3-next-80b-a3b.json")
                    .read_text())
CELL = "qwen3next-train-b1-t8192"


def test_config_file_holds_the_published_widths_and_the_share():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"]
             if c["name"] == "qwen3-next-80b-a3b"][0]
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"]) \
        == sorted(CONFIG["published"])
    assert entry["source"] == CONFIG["source"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 18992)
    for item in ("column_order", "draws", "no_prediction_module",
                 "no_aux_loss", "optimizer"):
        assert len(CONFIG["assumed"][item]) > 40, item
    cell = [w for w in bench["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("qwen3-next-80b-a3b", "lm-b1-t8192-gdn", 1)
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "lm_train_tokens_per_s"][0]
    assert rate["workloads"][-1] == CELL
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    assert sorted(n for n in mine if n.endswith(".qwen3next")) == [
        "experts_roofline_pct.qwen3next", "flash_roofline_pct.qwen3next",
        "gdn_roofline_pct.qwen3next", "gdn_time_share_pct.qwen3next",
        "mfu_pct.qwen3next"]
    # the accepted shares whose readers fit the cell unchanged; not the two
    # that read scopes this cell does not run alone (attn_core, mlp)
    assert "attn_core_time_share_pct.lm" not in mine
    assert "mlp_time_share_pct.lm" not in mine
    assert len(mine) == 5 + 19


#: the published config.json's keys as the configuration file must hold them
#: (the three reduced ones under ``published``)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_every_published_key_is_as_published_or_reduced():
    for key, value in PUBLISHED.items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key


def test_forward_parts_by_hand():
    """Per token at 8,192, by hand (the docstring's table):

    DeltaNet projections 3 x 2 x (2048 x 12288 + 2048 x 64 + 4096 x 2048)
                       = 3 x 2 x 33,685,504      = 202,113,024
    convolution        3 x 2 x 4 x 8192          =     196,608
    rule               3 x 32 x (4 x 64 x 128 + 64 x 256 + 2 x 64 x 128
                                 + 6 x 128 x 128)
                       = 3 x 32 x 163,840        =  15,728,640
    attention proj.    2 x (2048 x 9216 + 4096 x 2048)  =  54,525,952
    scores             2 x 2 x 16 x 256 x 4096   =  67,108,864
    router             4 x 2 x 2048 x 512        =   8,388,608
    shared             4 x 2 x (3 x 2048 x 512 + 2048) = 25,182,208
    routed             4 x 0.625 x 2 x 3 x 2048 x 512  = 15,728,640
    head               2 x 2048 x 18,992         =  77,791,232"""
    parts = qf.forward_parts_per_token(CONFIG, 8192)
    assert parts == {
        "gdn_projections": 202_113_024, "gdn_conv": 196_608,
        "gdn_rule": 15_728_640, "attention_projections": 54_525_952,
        "scores": 67_108_864, "router": 8_388_608,
        "shared_expert": 25_182_208, "routed_experts": 15_728_640,
        "head": 77_791_232}
    assert sum(parts.values()) == 466_763_776
    assert qf.train_flops_per_token(CONFIG, 8192) == 3 * 466_763_776
    assert qf.held_assignments_per_token(CONFIG) == 0.625
    # the DeltaNet layers' share of the forward: 54.6 %
    gdn = sum(parts[k] for k in ("gdn_projections", "gdn_conv", "gdn_rule")) \
        + 3 / 4 * sum(parts[k] for k in ("router", "shared_expert",
                                         "routed_experts"))
    assert gdn / sum(parts.values()) == pytest.approx(0.5463, abs=1e-4)


def test_the_rule_is_counted_from_its_shapes():
    """One step at 1 x 8,192, 3 DeltaNet layers of 4: operations 3 (passes)
    x 3 (layers) x 8192 x 5,242,880 = 386.55 G; bytes 3 x (2 x 8192 x (2 x
    16 x 128 + 2 x 32 x 128 + 2 x 32) x 2 + 2 x 128 x 32 x 128 x 128 x 4)
    = 3 x (404,750,336 + 536,870,912) = 2.8249 G: memory-bound on the v5e
    (2.0 ms of compute against 3.4 ms of bytes)."""
    flops, nbytes = qf.gdn_flops_bytes(1, 8192, 16, 32, 128, 128, 4, 4)
    assert flops == 3 * 3 * 8192 * 5_242_880
    assert nbytes == 3 * (404_750_336 + 536_870_912)
    # a row that is no multiple of the chunk counts its padded last chunk's
    # state, not its positions
    f2, b2 = qf.gdn_flops_bytes(1, 8200, 16, 32, 128, 128, 4, 4)
    assert f2 == flops * 8200 / 8192
    assert b2 > nbytes * 8200 / 8192
    # no DeltaNet layer, no work
    assert qf.gdn_flops_bytes(1, 8192, 16, 32, 128, 128, 1, 1) == (0, 0)


def test_metric_files_name_the_functions_and_the_shapes():
    m = json.loads((BENCH / "layer_metrics" / "mfu_pct.qwen3next.json")
                   .read_text())
    assert m["args"]["flops_fn"] == "qwen3next_flops:train_flops_per_token"
    r = json.loads((BENCH / "layer_metrics" / "gdn_roofline_pct.qwen3next.json")
                   .read_text())
    assert r["args"]["scopes"] == ["gdn_rule"]
    traffic = json.loads((BENCH / "traffic" / "lm-b1-t8192-gdn.json")
                         .read_text())
    look = {**CONFIG, **traffic}
    dims = [look[k] for k in r["args"]["shape"]]
    assert dims == [1, 8192, 16, 32, 128, 128, 4, 4]


def test_attention_and_experts_are_counted_from_their_shapes():
    """One step at 1 x 8,192. Flash, the one attention layer of four: 6 x 2
    x 16 x 8192 x 256 x 4096 = 1,649,267,441,664 operations and 6 x (16 +
    2) x 8192 x 256 x 2 = 452,984,832 bytes (compute-bound: 8.37 ms of
    operations against 0.55 ms of bytes). The experts (moe_flops' count) at
    even routing, 8192 x 10 x 0.0625 = 5,120 rows a layer: 4 x 3 x 2 x 5120
    x (2048 x 1024 + 512 x 2048) = 386,547,056,640 operations; bytes 4 x 3 x
    2 x ((5120 x 2048 + 32 x 2048 x 1024 + 5120 x 1024) + (5120 x 512 + 32 x
    512 x 2048 + 5120 x 2048)) = 4 x 6 x 129,499,136 = 3,107,979,264
    (memory-bound: 1.96 ms against 3.79 ms)."""
    import moe_flops
    flops, nbytes = qf.flash_flops_bytes(1, 16, 2, 8192, 256, 4, 4)
    assert flops == 1_649_267_441_664
    assert nbytes == 452_984_832
    assert qf.flash_flops_bytes(1, 16, 2, 8192, 256, 8, 4) == (
        2 * flops, 2 * nbytes)
    flops, nbytes = moe_flops.experts_flops_bytes(
        1, 8192, 2048, 512, 32, 10, 4, 0.0625)
    assert flops == 386_547_056_640
    assert nbytes == 3_107_979_264


@pytest.mark.parametrize("name,fn,dims", [
    ("flash_roofline_pct.qwen3next", "qwen3next_flops:flash_flops_bytes",
     [1, 16, 2, 8192, 256, 4, 4]),
    ("experts_roofline_pct.qwen3next", "moe_flops:experts_flops_bytes",
     [1, 8192, 2048, 512, 32, 10, 4, 0.0625])])
def test_roofline_files_read_the_cells_shapes(name, fn, dims):
    m = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert m["args"]["flops_fn"] == fn
    traffic = json.loads((BENCH / "traffic" / "lm-b1-t8192-gdn.json")
                         .read_text())
    look = {**CONFIG, **traffic, "moe_local_share": 0.0625}
    assert [look[k] for k in m["args"]["shape"]] == dims

"""The FLOP functions of the ``solar_open2`` family against hand-worked
numbers, and the Solar Open 2 configuration's file against the catalog row."""
import json
import math
import os

import pytest

from harness import loader


def _files():
    return (loader.load_json("configs", "solar_open2_250b_ep40_tp8"),
            loader.load_json("traffic", "train_b1_s8192"),
            loader.load_module("flops", "solar_open2"))


def test_the_chips_share_by_hand_for_each_kind_of_layer_and_the_step():
    config, traffic, f = _files()
    t = 8192
    # multiply-adds a token. A delta-attention layer of 8 heads of 128: qkv 4096
    # x 3072, the decay's and the gate's pairs 4096 x 128 + 128 x 1024 each,
    # beta 4096 x 8, o 1024 x 4096; the delta rule by the recurrence, 7 x 128 x
    # 128 operations a head (not doubled: it is a count of operations)
    kda = 4096 * 3072 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8 + 1024 * 4096
    assert kda == 18120704
    rule = 7 * 8 * 128 * 128
    assert rule == 917504 == f.delta_rule_flops_per_token(config)
    # attention of 8 query heads on 1 K/V head: q, gate and o 4096 x 1024, k and
    # v 4096 x 256, and 8 heads x (128 + 128) a pair of the causal half
    attn = 3 * 4096 * 1024 + 4096 * 256
    assert attn == 13631488
    pairs = t * (t + 1) // 2
    assert pairs == 33558528
    # every layer: the router 4096 x 320, 8 x 8 / 320 = 0.2 routed experts a
    # token and one shared, 3 x 4096 x 1280 each
    expert = 3 * 4096 * 1280
    moe = 4096 * 320 + expert // 5 + expert
    assert (expert, moe) == (15728640, 20185088)
    head = 4096 * 24576
    token = 2 * (3 * kda + attn + 4 * moe + head) + 3 * rule
    assert token == 501547008
    assert f.forward_flops_per_token(config, traffic) * t == token * t + 2 * 8 * 256 * pairs
    by_hand = 3 * (token * t + 2 * 8 * 256 * pairs)
    assert by_hand == 12738386460672 == f.train_flops_per_sample(config, traffic)
    assert abs(by_hand - 12.8e12) / 12.8e12 < 1e-2  # ISSUE 47's "12.8 TFLOP a sample"
    # the cell's `why`: the head is 39 % of the forward's operations, and the
    # delta rule, by the recurrence's count, half a percent
    assert abs(2 * head * t / (by_hand / 3) - 0.39) < 0.005
    assert 0.005 < 3 * rule * t / (by_hand / 3) < 0.006
    assert f.kda_layers(config) == 3


def test_delta_rule_op_counts_the_recurrence_and_each_operand_once():
    config, traffic, f = _files()
    rows = 8192
    ops, nbytes = f.delta_rule_op(config, traffic, backward=False)
    assert ops == 917504 * rows == 7516192768
    # q, k, v (128 each, two bytes), g (128, FOUR bytes), beta (two bytes) a
    # head and token, and o
    operands = rows * 8 * (3 * 256 + 512 + 2)
    assert operands == 84017152 and nbytes == operands + rows * 8 * 256 == 100794368
    ops_b, bytes_b = f.delta_rule_op(config, traffic, backward=True)
    assert ops_b == 2 * ops and bytes_b == 2 * operands + rows * 8 * 256 == 184811520
    # both passes are bound by the memory, 0.12 and 0.23 ms a layer: the floor
    # ISSUE 47 reckoned ("some 0.1 ms a layer forward")
    assert nbytes / 819e9 > 3 * ops / 197e12 and bytes_b / 819e9 > 2 * ops_b / 197e12
    assert abs((nbytes + bytes_b) / 819e9 - 0.3487e-3) < 1e-7
    # the chunked form's own products (the pairs twice, W and U, the carry's two
    # and the read's two at 64 tokens a chunk) are some 60 % more: a kernel
    # that does less than they cannot read over 100 %
    chunked = rows * 8 * 2 * (2 * 64 * 128 + 64 * 256 + 2 * 128 * 128 + 128 * 128 + 64 * 128)
    assert 1.5 < chunked / ops < 1.8


def test_causal_conv_op_moves_data_and_result_once_each_way():
    config, traffic, f = _files()
    rows = 8192 * 3072
    ops, nbytes = f.causal_conv_op(config, traffic, backward=False)
    assert ops == 12 * rows  # 4 multiply-adds, the SiLU as 4, no bias
    assert nbytes == 2 * rows * 2 + 3072 * 4 * 2 == 100687872  # in, out, taps
    ops_b, bytes_b = f.causal_conv_op(config, traffic, backward=True)
    assert ops_b == 33 * rows
    assert bytes_b == 3 * rows * 2 + 2 * 3072 * 4 * 2 == 151044096
    assert nbytes / 819e9 > 50 * ops / 197e12 and bytes_b / 819e9 > 40 * ops_b / 197e12


READERS = {
    # reader: (kernels' seconds, scopes' seconds, floor of both passes a layer by hand)
    "delta_rule_roofline.train": ({}, {"delta_rule": 0.30, "delta_rule_bwd": 0.50},
                                  (100794368 + 184811520) / 819e9),
    "kda_conv_roofline.train": ({"causal_conv_silu_fwd": 0.02, "causal_conv_silu_bwd": 0.04},
                                {"causal_conv": 0.5, "causal_conv_bwd": 0.5},
                                (100687872 + 151044096) / 819e9),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_roofline_reader_counts_three_layers_a_step_over_its_own_seconds(name, monkeypatch):
    """The two readers over one arithmetic: the floor of both passes of one
    layer (the bytes decide in all four), times the three delta-attention
    layers held and the steps traced, over the kernels' seconds where the
    kernels ran and else the scopes'; over 100 % is an error, and a run with no
    trace, or a family that does not count the op, reads nothing."""
    from harness import program_trace
    from harness.loader import BenchError

    config, traffic, _ = _files()
    kernels, scopes, floor = READERS[name]
    reader = loader.load_module("layer_metrics", name)
    run = {"config": config, "traffic": traffic, "device_kind": "TPU v5 lite",
           "trace": {"steps": 30}, "trace_dir": "somewhere"}
    agg = {"kernel_s": dict(kernels), "named_s": dict(scopes)}
    monkeypatch.setattr(program_trace, "aggregate",
                        lambda run: agg if run.get("trace_dir") else None)
    seconds = sum(kernels.values()) or sum(scopes.values())
    assert reader.read(run) == pytest.approx(100.0 * floor * 3 * 30 / seconds, rel=1e-9)
    assert 3.0 < reader.read(run) < 60.0
    assert reader.read(dict(run, trace_dir=None)) is None
    assert reader.read(dict(run, trace=None)) is None
    assert reader.read(dict(run, config=dict(config, family="granite_hybrid"))) is None
    agg["kernel_s"], agg["named_s"] = {}, dict.fromkeys(scopes, floor)  # 150 % of the floor
    with pytest.raises(BenchError, match="of its roofline"):
        reader.read(run)
    agg["named_s"] = {}
    assert reader.read(run) is None


def test_attention_kernel_counts_the_causal_half_and_kv_once_a_kv_head():
    config, traffic, f = _files()
    t, pairs = 8192, 8192 * 8193 // 2
    ops, nbytes = f.attention_kernel(config, traffic, backward=False)
    assert ops == 2 * 8 * pairs * 256 == 137455730688
    assert nbytes == 2 * 8 * t * 128 * 2 + 2 * 1 * t * 128 * 2 + 8 * t * 4 == 38010880
    ops_b, bytes_b = f.attention_kernel(config, traffic, backward=True)
    assert ops_b == 2 * 8 * pairs * 5 * 128 == 343639326720
    assert bytes_b == 3 * 8 * t * 128 * 2 + 4 * 1 * t * 128 * 2 + 2 * 8 * t * 4 == 59244544


def test_the_file_keeps_every_published_width_and_states_its_parameters():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the seven keys in ``reduced`` differ, each beside
    its published value, the group ``linear_attn_config`` differs in its head
    count alone, and the leaves add up to the count the file states."""
    config = loader.load_json("configs", "solar_open2_250b_ep40_tp8")
    ref = loader.load_module("references", "solar_open2")
    sizes = {k: math.prod(s) for k, (s, _) in ref.leaves(config).items()}
    layers = [sum(v for k, v in sizes.items() if k.startswith("l%d." % l))
              for l in range(4)]
    # a delta-attention mixer: q, k, v 3 x 4096 x 1024, o 4096 x 1024, the
    # decay's pair 4096 x 128 + 128 x 1024, the gate's the same and 1024 of
    # bias, beta 4096 x 8, the taps 3072 x 4, A_log 8, dt_bias 1024, the head
    # norm 128; the attention mixer: q, gate and o 4096 x 1024, k and v 4096 x 128
    kda = 12582912 + 4194304 + 655360 + 656384 + 32768 + 12288 + 8 + 1024 + 128
    gqa = 3 * 4194304 + 2 * 524288
    assert (kda, gqa) == (18135176, 13631488)
    # beside either: the shared expert, the router's 320 rows and its selection
    # bias (a leaf of the reference, a buffer of the program), 8 experts, two norms
    rest = 15728640 + 1310720 + 320 + 8 * 15728640 + 2 * 4096
    assert rest == 142876992
    assert layers == [gqa + rest] + [kda + rest] * 3 == [156508480] + [161012168] * 3
    total = sum(sizes.values())
    assert total == gqa + 3 * kda + 4 * rest + 2 * 24576 * 4096 + 4096 == 840875672
    assert "840,875,672 parameters" in config["deployment"]
    for key, want in (("hidden_size", 4096), ("head_dim", 128), ("moe_intermediate_size", 1280),
                      ("n_shared_experts", 1), ("num_experts_per_tok", 8),
                      ("routed_scaling_factor", 1), ("rms_norm_eps", 1e-5),
                      ("intermediate_size", 10240)):
        assert config[key] == want, key
    assert config["linear_attn_config"] == {"short_conv_kernel_size": 4, "head_dim": 128,
                                            "num_heads": 8, "num_kv_heads": None}
    assert config["published"]["n_routed_experts"] == 320
    entry = next(c for c in loader.bench_spec()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Solar-Open2-250B")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == [
        "gqa_layers", "linear_attn_config", "n_routed_experts", "num_attention_heads",
        "num_hidden_layers", "num_key_value_heads", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    assert dict(config["linear_attn_config"], num_heads=64) == row["config"]["linear_attn_config"]
    assert config["gqa_layers"] == [l for l in row["config"]["gqa_layers"] if l < 4]
    # an eighth of the heads and of the vocabulary, a fortieth of the experts
    assert config["num_attention_heads"] * 8 == row["config"]["num_attention_heads"]
    assert config["num_key_value_heads"] * 8 == row["config"]["num_key_value_heads"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["n_routed_experts"] * 40 == row["config"]["n_routed_experts"]
    assert entry["source"] == row["source_url"]

"""The FLOP functions of the ``granite_hybrid`` family against hand-worked
numbers, and the Granite configuration's file against the catalog row."""
import json
import math
import os

import pytest

from harness import loader


def _files():
    return (loader.load_json("configs", "granite4_h_micro_pp4"),
            loader.load_json("traffic", "train_b1_s8192"),
            loader.load_module("flops", "granite_hybrid"))


def test_granite_stage_by_hand_for_each_kind_of_layer_and_the_step():
    config, traffic, f = _files()
    t = 8192
    # multiply-adds a token. A Mamba layer: in_proj 2048 x (4096 + 4352 + 64)
    # and out_proj 4096 x 2048; the scan in chunks of 256, whose lower triangle
    # holds 256 x 257 / 2 pairs, 128.5 a token: scores 128 a pair (one group),
    # the masked product 64 heads x 64 a pair, a chunk's closing state and
    # the read of its opening state 64 x 64 x 128 a token each
    proj = 2048 * 8512 + 4096 * 2048
    assert proj == 25821184
    scan2 = 257 * 128 + 257 * 64 * 64 + 2 * 2 * 64 * 64 * 128  # twice the multiply-adds
    assert scan2 == 3182720 == f.scan_flops_per_token(config, traffic)
    # attention: q 2048 x 2048, k and v 2048 x 1024, o 2048 x 2048 and 32
    # heads x (64 + 64) a pair of the causal half; every layer's SwiGLU 3 x
    # 2048 x 8192; the tied head 2048 x 25088, once
    attn = 2048 * 2048 + 2048 * 1024 + 2048 * 2048
    pairs = t * (t + 1) // 2
    assert pairs == 33558528
    mlp = 3 * 2048 * 8192
    assert mlp == 50331648
    head = 2048 * 25088
    token = 2 * (9 * proj + attn + 10 * mlp + head) + 9 * scan2
    assert f.forward_flops_per_token(config, traffic) * t == token * t + 2 * 32 * 128 * pairs
    by_hand = 3 * (token * t + 2 * 32 * 128 * pairs)
    assert by_hand == 40731015118848 == f.train_flops_per_sample(config, traffic)
    assert abs(by_hand - 41e12) / 41e12 < 1e-2  # ISSUE 43's "some 41 TFLOP a sample"
    # the model's own proportions: the SwiGLU two thirds of a Mamba layer's
    # projections and its own, the scan 1.7 % of the step by operations
    assert abs(mlp / (mlp + proj) - 2 / 3) < 0.01
    assert 0.017 < 3 * 9 * scan2 * t / by_hand < 0.018


def test_ssd_op_counts_the_lower_triangle_and_each_operand_once():
    config, traffic, f = _files()
    rows = 8192
    ops, nbytes = f.ssd_op(config, traffic, backward=False)
    assert ops == 3182720 * rows == 26072842240
    # x and y (64 x 64 a token), dt (64), B and C (128 each), two bytes each,
    # and the three vectors of 64
    assert nbytes == rows * (4096 + 64 + 256) * 2 + 3 * 64 * 2 + rows * 4096 * 2 == 139460992
    ops_b, bytes_b = f.ssd_op(config, traffic, backward=True)
    assert ops_b == 2 * ops  # two transposes of each of the four products
    assert bytes_b == 2 * (rows * 4416 * 2 + 384) + rows * 4096 * 2 == 211813120
    # the forward is bound by HBM, the backward by the MXU, each narrowly: 0.17
    # and 0.26 ms a layer, 0.43 ms both ways
    assert nbytes / 819e9 > ops / 197e12 and ops_b / 197e12 > bytes_b / 819e9
    floor = nbytes / 819e9 + ops_b / 197e12
    assert abs(floor - 0.435e-3) < 1e-6
    # full squares, as a formula without the triangle would count them, are a
    # third more: a kernel that skips the masked half cannot read over 100 %
    full = rows * (2 * 256 * 128 + 2 * 256 * 64 * 64 + 4 * 64 * 64 * 128)
    assert abs(full / ops - 1.338) < 1e-3 and abs(full - 34.9e9) / 34.9e9 < 1e-2


def test_causal_conv_op_moves_data_and_result_once_each_way():
    config, traffic, f = _files()
    rows = 8192 * 4352
    ops, nbytes = f.causal_conv_op(config, traffic, backward=False)
    assert ops == 13 * rows  # 4 multiply-adds, the bias, the SiLU as 4
    assert nbytes == 2 * rows * 2 + 4352 * 5 * 2 == 142649856  # in, out, taps and bias
    ops_b, bytes_b = f.causal_conv_op(config, traffic, backward=True)
    assert ops_b == 34 * rows
    assert bytes_b == 3 * rows * 2 + 2 * 4352 * 5 * 2 == 213996544  # data and dy in, ddata out
    # an elementwise op: the bytes decide its roofline, not the operations
    assert nbytes / 819e9 > 50 * ops / 197e12 and bytes_b / 819e9 > 40 * ops_b / 197e12
    assert abs((nbytes + bytes_b) / 819e9 - 0.435e-3) < 1e-6  # 0.17 + 0.26 ms a layer


def test_attention_kernel_counts_the_causal_half_and_kv_once_a_kv_head():
    config, traffic, f = _files()
    t, pairs = 8192, 8192 * 8193 // 2
    ops, nbytes = f.attention_kernel(config, traffic, backward=False)
    assert ops == 2 * 32 * pairs * 128 == 274911461376
    assert nbytes == 2 * 32 * t * 64 * 2 + 2 * 8 * t * 64 * 2 + 32 * t * 4 == 84934656
    ops_b, bytes_b = f.attention_kernel(config, traffic, backward=True)
    assert ops_b == 2 * 32 * pairs * 5 * 64 == 687278653440
    assert bytes_b == 3 * 32 * t * 64 * 2 + 4 * 8 * t * 64 * 2 + 2 * 32 * t * 4 == 136314880
    # LFM2's call, shape for shape: its rooflines stand beside this cell's
    lfm2 = loader.load_module("flops", "lfm2_moe").attention_kernel(
        loader.load_json("configs", "lfm2_24b_a2b_ep4"), traffic, False)
    assert (ops, nbytes) == lfm2


def test_granite_file_keeps_every_published_width_and_states_its_parameters():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the three keys in ``reduced`` differ, the layers
    held are published layers 0-9, and the leaves add up to the count the file
    states."""
    config = loader.load_json("configs", "granite4_h_micro_pp4")
    ref = loader.load_module("references", "granite_hybrid")
    sizes = {k: math.prod(s) for k, (s, _) in ref.leaves(config).items()}
    layers = [sum(v for k, v in sizes.items() if k.startswith("l%d." % l))
              for l in range(10)]
    # a Mamba layer: W_in 2048 x 8512, taps 4352 x 4 and bias 4352, dt_bias,
    # A_log, D 3 x 64, the gated norm 4096, W_out 4096 x 2048; the SwiGLU 3 x
    # 2048 x 8192; two norms of 2048
    mamba = 17432576 + 17408 + 4352 + 192 + 4096 + 8388608 + 50331648 + 4096
    attention = 2 * 4194304 + 2 * 1048576 + 50331648 + 4096
    assert (mamba, attention) == (76182976, 60821504)
    assert layers == [mamba] * 5 + [attention] + [mamba] * 4
    assert sum(sizes.values()) == 9 * mamba + attention + 25088 * 2048 + 2048 == 797850560
    assert "797,850,560 parameters" in config["deployment"]
    for key, want in (("hidden_size", 2048), ("shared_intermediate_size", 8192),
                      ("mamba_n_heads", 64), ("mamba_d_head", 64), ("mamba_d_state", 128),
                      ("mamba_n_groups", 1), ("mamba_chunk_size", 256), ("mamba_d_conv", 4),
                      ("num_attention_heads", 32), ("num_key_value_heads", 8),
                      ("embedding_multiplier", 12), ("attention_multiplier", 0.015625),
                      ("residual_multiplier", 0.22), ("logits_scaling", 8)):
        assert config[key] == want, key
    entry = next(c for c in loader.bench_spec()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "granite-4.0-h-micro")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == [
        "layer_types", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    assert config["layer_types"] == row["config"]["layer_types"][:10]
    assert config["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert entry["source"] == row["source_url"]

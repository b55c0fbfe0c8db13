"""The FLOP functions of the ``lfm2_moe`` family against hand-worked numbers,
and the LFM2 configuration's file against the catalog row."""
import json
import math
import os

import pytest

from harness import loader


def _files():
    return (loader.load_json("configs", "lfm2_24b_a2b_ep4"),
            loader.load_json("traffic", "train_b1_s8192"),
            loader.load_module("flops", "lfm2_moe"))


def test_lfm2_share_by_hand_for_each_kind_of_layer_and_the_step():
    config, traffic, f = _files()
    t = 8192
    # multiply-adds a token: a conv mixer's in_proj 2048 x 6144 and out_proj
    # 2048 x 2048; attention's q 2048 x 2048, k and v 2048 x 1024, o 2048 x
    # 2048 and 32 heads x (64 + 64) a pair of the causal half; the dense
    # SwiGLU 3 x 2048 x 11776; an expert layer's router 2048 x 64 and 4 x 16 /
    # 64 = 1 routed slot of 3 x 2048 x 1536; the tied head 2048 x 16384
    conv = 2048 * 6144 + 2048 * 2048
    assert conv == 16777216
    proj = 2048 * 2048 + 2048 * 1024 + 2048 * 2048
    pairs = t * (t + 1) // 2
    assert pairs == 33558528
    dense = 3 * 2048 * 11776
    assert dense == 72351744
    moe = 2048 * 64 + 3 * 2048 * 1536
    assert moe == 9568256
    head = 2048 * 16384
    by_hand = 3 * 2 * (t * (4 * conv + proj + dense + 4 * moe + head) + 32 * 128 * pairs)
    assert f.train_flops_per_sample(config, traffic) == by_hand
    assert abs(by_hand - 11.73e12) / 11.73e12 < 1e-3  # ISSUE 37's 11.7 TFLOP a step
    # the new stack (conv mixers, the 64-wide attention, top-4 routing) is
    # over half of it
    new = 3 * 2 * (t * (4 * conv + proj + 4 * moe) + 32 * 128 * pairs)
    assert 0.5 < new / by_hand < 0.6


def test_attention_kernel_counts_the_causal_half_and_kv_once_a_kv_head():
    config, traffic, f = _files()
    t, pairs = 8192, 8192 * 8193 // 2
    ops, nbytes = f.attention_kernel(config, traffic, backward=False)
    assert ops == 2 * 32 * pairs * 128 == 274911461376
    # q and the output once a query head, k and v once a K/V head, lse a row
    assert nbytes == 2 * 32 * t * 64 * 2 + 2 * 8 * t * 64 * 2 + 32 * t * 4
    ops_b, bytes_b = f.attention_kernel(config, traffic, backward=True)
    assert ops_b == 2 * 32 * pairs * 5 * 64
    assert bytes_b == 3 * 32 * t * 64 * 2 + 4 * 8 * t * 64 * 2 + 2 * 32 * t * 4
    # both are bound by the MXU, not by HBM: operations over 197 TFLOP/s
    # against bytes over 819 GB/s
    assert ops / 197e12 > 10 * nbytes / 819e9 and ops_b / 197e12 > 10 * bytes_b / 819e9


def test_gated_conv_op_moves_bcx_and_y_once_each_way():
    config, traffic, f = _files()
    rows = 8192 * 2048
    ops, nbytes = f.gated_conv_op(config, traffic, backward=False)
    assert ops == 7 * rows
    assert nbytes == 4 * rows * 2 + 2048 * 3 * 2  # bcx in, y out, the taps
    assert round(nbytes / 1e6) == 134  # ISSUE 37's 134 MB, 0.16 ms at 819 GB/s
    assert abs(nbytes / 819e9 - 0.164e-3) < 1e-6
    ops_b, bytes_b = f.gated_conv_op(config, traffic, backward=True)
    assert ops_b == 21 * rows
    assert bytes_b == 7 * rows * 2 + 2 * 2048 * 3 * 2  # bcx and dy in, dbcx out
    assert round(bytes_b / 1e6) == 235  # 0.29 ms
    # an elementwise op: the bytes decide its roofline, not the operations
    assert bytes_b / 819e9 > 100 * ops_b / 197e12


def test_lfm2_file_keeps_every_published_width_and_states_its_parameters():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the five keys in ``reduced`` differ, the layers
    held are published layers 1-5, and the leaves add up to the count the file
    states."""
    config = loader.load_json("configs", "lfm2_24b_a2b_ep4")
    ref = loader.load_module("references", "lfm2_moe")
    sizes = {k: math.prod(s) for k, (s, _) in ref.leaves(config).items()}
    layers = [sum(v for k, v in sizes.items()
                  if k.startswith("l%d." % l) and not k.endswith("router.bias"))
              for l in range(5)]
    assert layers == [89139200, 161616000, 167913472, 167913472, 167913472]
    buffers = sum(v for k, v in sizes.items() if k.endswith("router.bias"))
    assert buffers == 4 * 64
    assert sum(sizes.values()) - buffers == 788052096
    assert "788,052,096 parameters" in config["deployment"]
    entry = next(c for c in loader.bench_spec()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "LFM2-24B-A2B")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    assert config["layer_types"] == row["config"]["layer_types"][1:6]
    assert entry["source"] == row["source_url"]

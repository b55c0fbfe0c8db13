"""The FLOP functions of the ``smallthinker`` family against a brute-force
count of pairs and slots at a small size and hand-worked numbers at the
cell's, and the configuration's file against the catalog row."""
import json
import math
import os

import pytest

from harness import loader


def _files():
    return (loader.load_json("configs", "smallthinker_21b_a3b_ep4"),
            loader.load_json("traffic", "train_b1_s8192"),
            loader.load_module("flops", "smallthinker"))


def test_window_pairs_against_a_brute_force_count():
    f = loader.load_module("flops", "smallthinker")
    for t, window in ((1, 1), (7, 3), (32, 12), (32, 32), (32, 100), (50, 1), (64, 63)):
        brute = sum(1 for i in range(t) for j in range(t) if j <= i and i - j < window)
        assert f.window_pairs(t, window) == brute, (t, window)
    assert f.window_pairs(32, None) == 32 * 33 // 2
    # the cell's: 25.2M of the 33.6M causal pairs a head, 75.0 %
    assert f.window_pairs(8192, 4096) == 25167872
    assert f.window_pairs(8192, None) == 33558528
    assert abs(f.window_pairs(8192, 4096) / f.window_pairs(8192, None) - 0.75) < 1e-3


def test_a_small_step_against_a_brute_force_count_of_pairs_and_slots():
    """Every product of a tiny configuration counted one multiply-add at a
    time: the projections a token, the pairs a layer's mask leaves, the slots
    an even routing sends to the experts held, the head."""
    f = loader.load_module("flops", "smallthinker")
    config = loader.load_json("configs", "rehearse_smallthinker")
    traffic = {"batch": 1, "sequence": 32}
    t, h, heads, kv, d = 32, 64, 7, 1, 16
    macs = 0
    for window in (None, 12, 12, 12):
        macs += t * (h * heads * d + h * 2 * kv * d + heads * d * h)
        pairs = sum(1 for i in range(t) for j in range(t)
                    if j <= i and (window is None or i - j < window))
        macs += heads * pairs * 2 * d
        slots = t * 2 * 4 // 8  # 2 of 8 a token, 4 held: one slot a token
        macs += t * h * 8 + slots * 3 * h * 32
    macs += t * h * 300
    assert f.train_flops_per_sample(config, traffic) == 3 * 2 * macs


def test_the_cells_share_by_hand_for_each_kind_of_layer_and_the_step():
    config, traffic, f = _files()
    t = 8192
    # multiply-adds a token: q 2560 x 3584, k and v 2560 x 512 each, o 3584 x
    # 2560; the router 2560 x 64 and 6 x 16 / 64 = 1.5 routed slots of 3 x 2560
    # x 768; the untied head 2560 x 37984; 28 heads x (128 + 128) a pair
    proj = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    assert proj == 20971520
    moe = 2560 * 64 + 1.5 * 3 * 2560 * 768
    assert moe == 9011200
    head = 2560 * 37984
    pairs = 33558528 + 3 * 25167872
    by_hand = 3 * 2 * (t * (4 * (proj + moe) + head) + 28 * 256 * pairs)
    assert f.train_flops_per_sample(config, traffic) == by_hand
    assert abs(by_hand - 15.36e12) / 15.36e12 < 1e-3
    products = 3 * 2 * t * (4 * (proj + moe) + head)
    assert abs(products - 10.67e12) / 10.67e12 < 1e-3  # ISSUE 41's 10.7 TFLOP
    assert abs(3 * 2 * t * head / products - 0.448) < 1e-3  # 45 % of them the head
    # attention's two products are 31 % of the step, three quarters of them
    # in the window layers
    assert 0.30 < (by_hand - products) / by_hand < 0.31


def test_both_kernels_counts_and_kv_once_a_kv_head():
    config, traffic, f = _files()
    t = 8192
    for kernel, pairs in ((f.attention_kernel, 33558528),
                          (f.window_attention_kernel, 25167872)):
        ops, nbytes = kernel(config, traffic, backward=False)
        assert ops == 2 * 28 * pairs * 2 * 128
        # q and the output once a query head, k and v once a K/V head, lse a
        # row; nothing for the window
        assert nbytes == 2 * 28 * t * 128 * 2 + 2 * 4 * t * 128 * 2 + 28 * t * 4
        ops_b, bytes_b = kernel(config, traffic, backward=True)
        assert ops_b == 2 * 28 * pairs * 5 * 128
        assert bytes_b == 3 * 28 * t * 128 * 2 + 4 * 4 * t * 128 * 2 + 2 * 28 * t * 4
        # both are bound by the MXU, not by HBM
        assert ops / 197e12 > 10 * nbytes / 819e9 and ops_b / 197e12 > 10 * bytes_b / 819e9
    full, window = (k(config, traffic, False)[0]
                    for k in (f.attention_kernel, f.window_attention_kernel))
    assert abs(window / full - 0.75) < 1e-3


def test_the_file_keeps_every_published_width_and_states_its_parameters():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the five keys in ``reduced`` differ, the layers
    held are published layers 0-3, and the leaves add up to the count the file
    states."""
    config = loader.load_json("configs", "smallthinker_21b_a3b_ep4")
    ref = loader.load_module("references", "smallthinker")
    sizes = {k: math.prod(s) for k, (s, _) in ref.leaves(config).items()}
    layers = [sum(v for k, v in sizes.items() if k.startswith("l%d." % l))
              for l in range(4)]
    assert layers == [115512320] * 4
    assert sizes["embed.w"] == sizes["head.w"] == 37984 * 2560
    assert sum(sizes.values()) == 656529920
    assert "656,529,920 parameters" in config["deployment"]
    entry = next(c for c in loader.bench_spec()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "rope_layout",
        "sliding_window_layout", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    assert config["rope_layout"] == row["config"]["rope_layout"][:4] == [0, 1, 1, 1]
    assert config["sliding_window_layout"] == row["config"]["sliding_window_layout"][:4]
    assert entry["source"] == row["source_url"]
    cell = next(w for w in loader.bench_spec()["workloads"]
                if w["name"] == "smallthinker_a3b_train_s8192")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "train_b1_s8192", 1)

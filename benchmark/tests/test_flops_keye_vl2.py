"""The FLOP functions of the ``keye_vl2`` family against hand-worked numbers
for one layer, and the Keye configuration's file against the catalog row."""
from harness import loader


def _files():
    return (loader.load_json("configs", "keye_vl2_30b_a3b_ep8"),
            loader.load_json("traffic", "train_b1_s8192"),
            loader.load_module("flops", "keye_vl2"))


def test_pairs_selected_and_searched_at_8192_under_a_top_2048():
    _, _, f = _files()
    # rows 0..2047 see all of their t + 1 keys, rows 2048..8191 see 2048
    assert f.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 == 14681088
    assert 8192 * 8193 // 2 == 33558528
    assert abs(100.0 * 14681088 / 33558528 - 43.75) < 0.01
    assert f.selected_pairs(2048, 2048) == 2048 * 2049 // 2  # nothing to select
    assert f.searched_pairs(2048, 2048) == 0
    assert f.searched_pairs(8192, 2048) == 33558528 - 2048 * 2049 // 2 == 31460352


def test_keye_share_by_hand_for_one_layer_and_the_step():
    config, traffic, f = _files()
    t = 8192
    # multiply-adds a token in one layer: q 2048 x 4096, k and v 2048 x 1024,
    # o 4096 x 2048; the router 2048 x 128 and 8 x 16 / 128 = 1 routed slot of
    # 3 x 2048 x 768
    proj = 2048 * 4096 + 2048 * 1024 + 4096 * 2048
    assert proj == 18874368
    moe = 2048 * 128 + 3 * 2048 * 768
    assert moe == 4980736
    # attention a sequence a layer: 32 heads x (128 + 128) a selected pair
    attn = 32 * 256 * 14681088
    # the indexer, forward only: its projections 2048 x (1024 + 64 + 16) a
    # token, its scores 16 x 64 a pair of the rows searched
    index = t * 2048 * 1104 + 16 * 64 * 31460352
    head = 2048 * 18992
    trained = 2 * (t * (6 * (proj + moe) + head) + 6 * attn)
    by_hand = 3 * trained + 2 * 6 * index
    assert f.train_flops_per_sample(config, traffic) == by_hand
    assert abs(by_hand - 13.885e12) / 13.885e12 < 1e-3  # a step of one sequence
    # one call of each kernel: selected pairs only, so a share cannot pass
    # 100 % by counting pairs the mask removes
    ops, nbytes = f.attention_kernel(config, traffic, backward=False)
    assert ops == 2 * 32 * 14681088 * 256
    assert nbytes == (2 * 32 * t * 128 * 2 + 2 * 4 * t * 128 * 2 + t * t + 32 * t * 4)
    ops_b, _ = f.attention_kernel(config, traffic, backward=True)
    assert ops_b == 2 * 32 * 14681088 * 5 * 128
    ops_i, bytes_i = f.indexer_kernel(config, traffic)
    assert ops_i == 2 * 16 * 64 * 31460352
    assert bytes_i == t * 1104 * 2 + t * t


def test_keye_file_keeps_every_published_width():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the four keys in ``reduced`` differ."""
    import json
    import math
    import os

    import pytest

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh) if r["name"] == "Keye-VL-2.0-30B-A3B")
    config = loader.load_json("configs", "keye_vl2_30b_a3b_ep8")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    entry = next(c for c in loader.bench_spec()["configs"] if c["name"] == config["name"])
    assert entry["source"] == row["source_url"] and entry["reduced"] == config["reduced"]
    ref = loader.load_module("references", "keye_vl2")
    sizes = {k: math.prod(s) for k, (s, _) in ref.leaves(config).items()}
    layer = sum(v for k, v in sizes.items() if k.startswith("l0."))
    assert layer == 96899456  # 96.9M a layer, 75.5M of it the 16 experts held
    assert sum(sizes.values()) == 6 * layer + 2 * 38895616 + 2048 == 659190016

"""The FLOP functions of the ``deepseek_v3`` family against hand-worked
numbers, and the Kanana configuration's file against the catalog row."""
from harness import loader


def test_kanana_share_is_841_mflop_a_token_forward_at_4096():
    config = loader.load_json("configs", "kanana2_30b_a3b_ep8")
    traffic = loader.load_json("traffic", "train_b2_s4096")
    f = loader.load_module("flops", "deepseek_v3")
    # by hand, multiply-adds a token: latent attention's four projections
    # 2048 x 6144 + 2048 x 576 + 512 x 8192 + 4096 x 2048 = 26,345,472; the two
    # attention products over the causal half, 32 heads x (192 + 128) x 4097 / 2;
    # the dense layer 3 x 2048 x 6144; an expert layer: the router 2048 x 128, two
    # shared experts and 6 x 16 / 128 = 0.75 routed slots of 3 x 2048 x 768 each;
    # the head 2048 x 16032
    proj = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert proj == 26345472
    attn = 32 * 320 * 4097 / 2.0
    moe = 2048 * 128 + 3 * 2048 * 768 * 2.75
    by_hand = 2 * (6 * (proj + attn) + 3 * 2048 * 6144 + 5 * moe + 2048 * 16032)
    assert f.forward_flops_per_token(config, traffic) == by_hand
    assert abs(by_hand - 841.4e6) / 841.4e6 < 1e-3
    step = f.train_flops_per_sample(config, traffic) * traffic["batch"]
    assert abs(step - 20.68e12) / 20.68e12 < 1e-3
    # one call of each kernel: 64 causal squares of 4096, keys 192, values 128
    ops, nbytes = f.attention_kernel(config, traffic, backward=False)
    assert ops == 2 * 64 * (4096 * 4097 / 2.0) * 320
    assert nbytes == 64 * 4096 * 2 * (192 + 192 + 128 + 128) + 64 * 4096 * 128 * 4
    ops_b, _ = f.attention_kernel(config, traffic, backward=True)
    assert ops_b == 2 * 64 * (4096 * 4097 / 2.0) * (3 * 192 + 2 * 128)


def test_kanana_file_keeps_every_published_width():
    """The configuration's file against the catalog row's numbers, where the
    catalog is installed: only the three keys in ``reduced`` differ."""
    import json
    import os

    import pytest

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    config = loader.load_json("configs", "kanana2_30b_a3b_ep8")
    differ = sorted(k for k, v in row["config"].items() if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                                  "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in config["reduced"]}
    ref = loader.load_module("references", "deepseek_v3")
    import math
    assert sum(math.prod(s) for s, kind in ref.leaves(config).values()
               if kind != "bias") == 687502336

"""The FLOP functions against hand-worked numbers."""
from harness import loader


def test_resnet50_forward_is_he_et_als_count():
    config = loader.load_json("configs", "resnet50_v1")
    f = loader.load_module("flops", "resnet")
    # by hand, from this repo's layer shapes: the stem 7x7x3x64 at 112^2 is
    # 118.0 M multiply-adds; stage 1's first block at 56^2: 64->64 (1x1) 12.8 M,
    # 3x3x64x64 115.6 M, 64->256 51.4 M, projection 64->256 51.4 M
    m = config["published"]
    assert f._out(m["image"], 7, 2, 3) == 112
    stem = 7 * 7 * 3 * 64 * 112 * 112
    assert stem == 118013952
    first_block = (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) * 56 * 56
    assert first_block == 231211008
    forward = f.forward_flops(config)
    assert abs(forward - 2 * 3.86e9) / (2 * 3.86e9) < 0.03
    assert f.train_flops_per_sample(config) == 3 * forward


def test_bert_base_is_237_mflop_a_token_forward_at_512():
    config = loader.load_json("configs", "bert_base_mlm")
    traffic = loader.load_json("traffic", "train_b32_s512")
    f = loader.load_module("flops", "bert")
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 512 * 768
    by_hand = 12 * per_layer + 2 * 768 * 768 + 2 * 768 * 30522
    assert f.forward_flops_per_token(config, traffic) == by_hand
    assert abs(by_hand - 237e6) / 237e6 < 0.005
    assert f.train_flops_per_sample(config, traffic) == 3 * by_hand * 512

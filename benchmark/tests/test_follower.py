"""The follower against the one it replaced (``old_follower.py``): the same
losses, first gradient, gradient norms and distances bit for bit, the delta
norms to a last place of float32. ``conftest.py`` takes fused multiply-add from
the CPU backend for this: with it, Adam's mean of a leaf whose gradient is
rounding noise (a bias in front of a batch norm, 1e-13) can part by one place
of bfloat16 from the second step on."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import old_follower
from harness import loader, train_reference

OPTIMIZERS = {
    "sgd": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9},
    "adam": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
             "epsilon": 1e-8},
}
FAMILIES = {"resnet": ("rehearse_resnet", "rehearse_train_images_dp4"),
            "bert": ("rehearse_bert", "rehearse_train_tokens")}


@pytest.mark.parametrize("quant", [None, "fp8"], ids=["sound", "fp8_control"])
@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_follower_reads_what_the_old_one_read(family, optimizer, chips, quant):
    config = loader.load_json("configs", FAMILIES[family][0])
    traffic = loader.load_json("traffic", FAMILIES[family][1])
    ref = loader.load_module("references", family)
    opt = OPTIMIZERS[optimizer]
    params, pool = ref.init(config, 3), ref.batches(config, traffic, 3)
    # any gradient will do for the program's: both followers measure the same one
    rng = np.random.default_rng(3)
    theirs = {k: rng.standard_normal(v.shape).astype(np.float32) for k, v in params.items()}
    devices = jax.devices()[:chips]
    # the old one took it whole on every chip it used, as the program left it
    whole = NamedSharding(Mesh(devices, ("rows",)), PartitionSpec())
    old = old_follower.first_steps(ref, config, opt, params, pool, quant=quant,
                                   program_gradient=jax.device_put(theirs, whole),
                                   keep_gradient=True, devices=devices)
    new = train_reference.first_steps(ref, config, opt, params, pool, quant=quant,
                                      program_gradient=theirs, keep_gradient=True,
                                      devices=devices)
    assert not any(v.is_deleted() for v in params.values())  # the caller's, not donated
    for tree in (old, new):
        tree["first_gradient"] = {k: np.asarray(v) for k, v in tree["first_gradient"].items()}
    assert set(new) == set(old)
    assert new["losses"] == old["losses"]
    assert new["grad_rel_diff"] == old["grad_rel_diff"]
    for key in ("grad_norms", "grad_diff_norms"):
        assert new[key] == old[key], key
    # the change's norm is now a sum over bfloat16 leaves, which the CPU backend
    # vectorises in another order than one over float32 leaves: a last place of
    # float32 (the losses of steps two and three hold the weights themselves)
    assert new["delta_norms"] == pytest.approx(old["delta_norms"], rel=2.5e-7, abs=0.0)
    for k, v in old["first_gradient"].items():
        assert np.array_equal(new["first_gradient"][k], v), k
    assert any(v > 0.0 for v in new["delta_norms"].values())

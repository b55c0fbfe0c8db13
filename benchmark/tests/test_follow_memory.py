"""What the follower's step holds on the chip, by the chip's own compiler and
at no chip time: ``harness/train_reference.py:follower_step`` compiled for a
described v5e (not attached) around a synthetic plain reference, and the
compiler's ``memory_analysis()`` held to 12 bytes a parameter. The follower
as it stood before PR 27 reads 28 by the same count, and held 10 more outside
its step. A compile that passes is not a chip run.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from harness import train_reference

F32 = jnp.float32
ROWS, BLOCK = 32, 8


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip; the compilation cache
    off around the module (such a compile can be written to the persistent
    cache but never read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


class Chain:
    """A plain reference of ``layers`` square matrices and as many biases,
    float32 throughout, in either pattern the benchmark's references have:
    the whole batch at once (``references/resnet.py``), or, with ``scan``,
    blocks of rows under a scan that sums their gradients
    (``references/bert.py``)."""

    def __init__(self, layers, width, scan):
        self.layers, self.width, self.scan = layers, width, scan

    def shapes(self):
        out = {}
        for l in range(self.layers):
            out["l%d.w" % l], out["l%d.bias" % l] = (self.width, self.width), (self.width,)
        return out

    def loss_sum(self, params, x, y):
        h = x
        for l in range(self.layers):
            h = jnp.tanh(h @ params["l%d.w" % l] + params["l%d.bias" % l])
        return jnp.sum(jnp.square(h - y))

    def value_and_grad(self, config, params, x, y, quant=None):
        vg = jax.value_and_grad(self.loss_sum)
        if self.scan:
            xs, ys = (a.reshape(ROWS // BLOCK, BLOCK, self.width) for a in (x, y))

            def body(carry, xy):
                total, grads = carry
                v, g = vg(params, xy[0], xy[1])
                return (total + v, jax.tree.map(jnp.add, grads, g)), None

            zero = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), params)
            (total, grads), _ = jax.lax.scan(body, (jnp.zeros((), F32), zero), (xs, ys))
        else:
            total, grads = vg(params, x, y)
        return total / ROWS, jax.tree.map(lambda g: g / ROWS, grads)


ADAM = {"name": "adam", "learning_rate": 1e-4}
SGD = {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9}


@pytest.mark.parametrize("with_gradient", [True, False], ids=["step_one", "later_steps"])
@pytest.mark.parametrize("scan,limit", [(False, 12), (True, 15)], ids=["whole_batch", "scan"])
@pytest.mark.parametrize("layers,width,opt", [(58, 3456, ADAM), (30, 1920, SGD)],
                         ids=["0.7B_adam", "110M_sgd"])
def test_what_the_followers_step_holds_a_parameter(chip, layers, width, opt, scan, limit,
                                                   with_gradient):
    """12 bytes a parameter: weights and state in bfloat16, donated (6 under
    Adam), and the float32 gradient (4). A reference that scans over blocks of
    rows holds 4 more: the float32 weights are an operand of its loop, so the
    compiler keeps them whole for as long as it runs."""
    ref = Chain(layers, width, scan)
    step = train_reference.follower_step(ref, {"dtype": "bfloat16"}, opt)
    p = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
         for k, s in ref.shapes().items()}
    state = {k: (v,) * train_reference.state_slots(opt) for k, v in p.items()}
    x = jax.ShapeDtypeStruct((ROWS, width), F32, sharding=chip)
    m = step.lower(p, state, x, x, 1.0, with_gradient=with_gradient).compile().memory_analysis()
    n = sum(v.size for v in p.values())
    held = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    print("%.0fM parameters: arguments %.2f + outputs %.2f + temporaries %.2f - aliased %.2f "
          "= %.2f bytes a parameter" % (n / 1e6, m.argument_size_in_bytes / n,
                                        m.output_size_in_bytes / n, m.temp_size_in_bytes / n,
                                        m.alias_size_in_bytes / n, held / n))
    stored = 2 * (1 + train_reference.state_slots(opt))  # weights and state, bfloat16
    assert m.alias_size_in_bytes >= stored * n  # donated, all of them
    assert held <= limit * n

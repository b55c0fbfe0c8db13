"""Pallas fused BN backward — interpret-mode parity vs the XLA
custom-VJP formulas (ops/nn.py _bn_core_bwd). Interpret-green is not
compiler-green: tests/test_aot_tpu_compile.py compiles the kernel for
the described chip, chip_smoke.py and tests/test_tpu_smoke.py run it
there."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import bn_pallas
from mxnet_tpu.ops.nn import _bn_core


def _oracle(x2d, dy2d, g):
    """Gradients through the existing custom-VJP core (channel last).
    _bn_core returns (out, mean, var); only out carries a cotangent."""
    b = jnp.zeros_like(g)
    (out, mean, var), vjp = jax.vjp(
        lambda xx, gg, bb: _bn_core(1e-5, (0,), xx, gg, bb), x2d, g, b)
    return vjp((dy2d.astype(out.dtype), jnp.zeros_like(mean),
                jnp.zeros_like(var)))


def _stats(x2d):
    x32 = x2d.astype(jnp.float32)
    mean = jnp.mean(x32, axis=0)
    var = jnp.mean(jnp.square(x32 - mean), axis=0)
    inv = jax.lax.rsqrt(var + 1e-5)
    return mean, inv


@pytest.mark.parametrize("m,c,dtype", [
    (64, 32, jnp.float32),
    (200, 16, jnp.float32),      # m not a multiple of the block rows
    (1024, 8, jnp.bfloat16),
    (96, 128, jnp.bfloat16),
])
def test_bn_bwd_pallas_matches_xla_vjp(m, c, dtype):
    key = jax.random.PRNGKey(0)
    kx, kdy, kg = jax.random.split(key, 3)
    x = jax.random.normal(kx, (m, c), dtype)
    dy = jax.random.normal(kdy, (m, c), dtype)
    g = jax.random.normal(kg, (c,), jnp.float32) + 1.5

    mean, inv = _stats(x)
    dx, dg, db = bn_pallas.bn_bwd_pallas(x, dy, mean, inv, g,
                                         interpret=True)
    odx, odg, odb = _oracle(x, dy, g)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(db, np.float32),
                               np.asarray(odb, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dg, np.float32),
                               np.asarray(odg, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(odx, np.float32),
                               rtol=tol, atol=tol)
    assert dx.dtype == x.dtype


def test_bn_bwd_pallas_masking_exactness():
    """The remainder block's padding must not leak into the reductions:
    compare a padded-size run against a multiple-size run on the same
    data."""
    m, c = 72, 8  # 72 % block_rows != 0 for any pow2 block > 8
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (m, c), jnp.float32)
    dy = jnp.ones((m, c), jnp.float32)
    mean, inv = _stats(x)
    _, dg, db = bn_pallas.bn_bwd_pallas(x, dy, mean, inv,
                                        jnp.ones(c), interpret=True)
    np.testing.assert_allclose(np.asarray(db), np.full(c, float(m)),
                               rtol=1e-6)


def test_enabled_gating(monkeypatch):
    monkeypatch.delenv("MXT_BN_PALLAS", raising=False)
    assert not bn_pallas.enabled()  # default off
    monkeypatch.setenv("MXT_BN_PALLAS", "1")
    # off the TPU the compiled Mosaic path must stay off
    assert bn_pallas.enabled() == mx.context.on_tpu()

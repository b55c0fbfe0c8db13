"""The names the program writes at trace time: phases in the three fused
steps, a block's own name, the hand-written ops' scopes and the kernels'
names, as the compiled program's ``op_name``s carry them (profiler_trace.py
reads the same strings back from a device trace as ``tf_op``)."""
import ast
import os
import re

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, tuning
from mxnet_tpu.gluon import nn


class _ConvNet(nn.HybridBlock):
    """(conv -> batch norm -> relu) -> pool -> dense, channel-last."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.stem = nn.HybridSequential(prefix="stem_")
            with self.stem.name_scope():
                self.stem.add(nn.Conv2D(4, 3, padding=1, in_channels=3, layout="NHWC"),
                              nn.BatchNorm(axis=3, in_channels=4),
                              nn.Activation("relu"))
            self.pool = nn.GlobalAvgPool2D(layout="NHWC")
            self.out = nn.Dense(5, in_units=4)

    def hybrid_forward(self, F, x):
        return self.out(self.pool(self.stem(x)))


class _AttnNet(nn.HybridBlock):
    """layer norm -> one head of flash attention -> dense."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.ln = nn.LayerNorm(in_channels=8)
            self.out = nn.Dense(3, in_units=8, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.ln(x)
        q = h.reshape((0, 1, -1, 8))  # (B, H=1, T, D)
        return self.out(F.flash_attention(q, q, q).reshape((0, -1, 8)))


def _ce(out, y):
    return gluon.loss.SoftmaxCrossEntropyLoss()(out, y)


def _l2(out, y):
    return gluon.loss.L2Loss()(out, y)


def _conv_case():
    rng = np.random.RandomState(0)
    return (_ConvNet(prefix="net_"), _ce,
            nd.array(rng.uniform(-1, 1, (4, 8, 8, 3)).astype(np.float32)),
            nd.array(rng.randint(0, 5, (4,)).astype(np.float32)))


def _attn_case():
    rng = np.random.RandomState(0)
    return (_AttnNet(prefix="net_"), _l2,
            nd.array(rng.uniform(-1, 1, (2, 16, 8)).astype(np.float32)),
            nd.array(rng.uniform(-1, 1, (2, 16, 3)).astype(np.float32)))


def _fuse_step(net, loss):
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    step = tr.fuse_step(net, loss)
    return step, lambda x, y: step(x, y, batch_size=x.shape[0])


def _sharded_step(net, loss):
    mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    step = parallel.ShardedTrainStep(net, loss, "adam", {"learning_rate": 1e-3},
                                     mesh=mesh)
    return step, step


def _second_call_names(step, call, x, y):
    """``op_name``s of the program the step's second call runs (lowered from
    that call's own arguments), and the compiles that call made."""
    call(x, y).wait_to_read()
    jitted, kept = step._jit, {}

    def recording(*args, **kwargs):
        kept["args"] = (args, kwargs)
        return jitted(*args, **kwargs)

    step._jit = recording
    c0 = tuning.compile_stats()["compiles"]
    try:
        call(x, y).wait_to_read()
    finally:
        step._jit = jitted
    compiles = tuning.compile_stats()["compiles"] - c0
    assert jitted._cache_size() == 1
    args, kwargs = kept["args"]
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return "\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', text)))), compiles


@pytest.mark.parametrize("entry", [_fuse_step, _sharded_step],
                         ids=["fuse_step", "sharded_step"])
@pytest.mark.parametrize("case", [_conv_case, _attn_case], ids=["conv_bn", "attention"])
def test_fused_steps_name_phases_blocks_and_ops(entry, case):
    mx.random.seed(0)
    net, loss, x, y = case()
    net.initialize()
    step, call = entry(net, loss)
    names, compiles = _second_call_names(step, call, x, y)
    assert compiles == 0, "a scope must not make the second call trace again"
    assert re.search(r"/optimizer/", names), names
    if case is _conv_case:
        # the block path, the parents' prefixes taken off: net_stem_conv2d0 -> net/stem/conv2d0
        assert re.search(r"jvp\(forward\)/net/stem/conv2d0/", names), names
        assert re.search(r"jvp\(forward\)/net/stem/batchnorm0/batchnorm/", names), names
        assert re.search(r"transpose\(jvp\(forward\)\)/net/stem/batchnorm0/batchnorm_bwd/",
                         names), names
        assert re.search(r"transpose\(jvp\(forward\)\)/net/stem/conv2d0/", names), names
    else:
        assert re.search(r"jvp\(forward\)/net/layernorm0/layernorm/", names), names
        assert re.search(r"transpose\(jvp\(forward\)\)/net/layernorm0/layernorm_bwd/",
                         names), names
        assert re.search(r"jvp\(forward\)/net/attention/", names), names
        assert re.search(r"transpose\(jvp\(forward\)\)/net/attention_bwd/", names), names
    # no operation of the update sits under forward, and none of forward under it
    assert not re.search(r"forward.*/optimizer/|/optimizer/.*forward", names), names


def test_trainer_step_names_its_fused_update():
    """``Trainer.step``'s one-launch update (``_FusedUpdate``) is ``optimizer``."""
    mx.random.seed(0)
    net, loss, x, y = _conv_case()
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    with mx.autograd.record():
        out = loss(net(x), y)
    out.backward()
    tr.step(4)
    fused = tr._fused
    assert fused is not None
    ws = tuple(p.data().data for p in tr._params if p.grad_req != "null")
    gs = tuple(p.grad().data for p in tr._params if p.grad_req != "null")
    text = fused._jit.lower(ws, gs, tuple(() for _ in ws), 1, 0.1, 0.0, 1.0).as_text(
        debug_info=True)
    assert "optimizer" in text


def test_eager_forward_enters_no_scope(monkeypatch):
    """Outside a trace a block's call pays one comparison, no named scope."""
    mx.random.seed(0)
    net, _, x, _ = _conv_case()
    net.initialize()
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda n: entered.append(n) or real(n))
    net(x).wait_to_read()
    assert entered == []
    net.hybridize()
    net(x).wait_to_read()  # the CachedOp's trace does enter them
    assert "stem" in entered and "conv2d0" in entered


# the names the trace's readers look kernels up by (profiler_trace.py, the
# benchmark's roofline metrics, tools/trace_cell.py), by the module that
# launches them; the in-place flash kernels go by the heads-major ones' names
_KERNEL_NAMES = {
    "attention": ("flash_attention_fwd", "flash_attention_bwd", "window_attention_fwd",
                  "window_attention_bwd", "paged_decode"),
    "causal_conv_pallas": ("causal_conv_silu_fwd", "causal_conv_silu_bwd"),
    "delta_rule_pallas": ("kda_chunk_fwd", "kda_chunk_bwd"),
    "grouped_matmul": ("grouped_matmul", "grouped_matmul_dw"),
    "indexer": ("indexer_select",),
    "row_gather": ("rows_as_words", "row_gather"),
    "ssd_pallas": ("ssd_chunk_fwd", "ssd_chunk_bwd"),
}
_OPS_DIR = os.path.join(os.path.dirname(mx.__file__), "ops")


def _kernel_modules():
    found = []
    for name in sorted(os.listdir(_OPS_DIR)):
        if name.endswith(".py"):
            with open(os.path.join(_OPS_DIR, name)) as f:
                if "pl.pallas_call(" in f.read():
                    found.append(name[:-3])
    return found


@pytest.mark.parametrize("module", _kernel_modules())
def test_kernels_have_names(module):
    """Every ``pallas_call`` of an ops/ module has a ``name=``: the trace then
    shows the kernel under its own name and not as ``custom-call.N``. A
    module that launches a kernel lists its names above."""
    with open(os.path.join(_OPS_DIR, module + ".py")) as f:
        src = f.read()
    calls = [n for n in ast.walk(ast.parse(src)) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute) and n.func.attr == "pallas_call"]
    assert len(calls) == src.count("pl.pallas_call(")
    for call in calls:
        assert any(k.arg == "name" for k in call.keywords), "line %d" % call.lineno
    quoted = set(re.findall(r'"(\w+)"', src))
    assert set(_KERNEL_NAMES[module]) <= quoted


def test_a_block_with_no_prefix_of_its_own_goes_by_its_type():
    """The model zoo nests ``HybridSequential(prefix="")`` bodies: their full
    name is the parent's, so the scope path would repeat it."""
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="zoo_")
    with net.name_scope():
        body = nn.HybridSequential(prefix="")
        with body.name_scope():
            body.add(nn.Dense(4, in_units=3))
        net.add(body)
    net.initialize()
    step, call = _fuse_step(net, _l2)
    x = nd.array(np.ones((2, 3), np.float32))
    y = nd.array(np.ones((2, 4), np.float32))
    names, _ = _second_call_names(step, call, x, y)
    assert re.search(r"jvp\(forward\)/zoo/hybridsequential/dense0/", names), names
    assert "zoo/zoo" not in names

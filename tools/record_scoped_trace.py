#!/usr/bin/env python3
"""Record ``tests/data/scoped_step.xplane.pb`` on the chip: a tiny train step
with ``value_and_grad``, the program's kinds of scope (a phase, a block, a
``custom_vjp`` op with a ``_bwd`` half, a loop) and one named Pallas kernel,
three launches with host spans around them. ``tests/test_profiler_trace.py``
holds ``profiler_trace.aggregate`` to this file's numbers.

    python3 tools/record_scoped_trace.py chiprun_out/scoped_trace

Run through the chip tool; the trace is then copied by hand (it is small).
"""
import functools
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

import mxnet_tpu  # noqa: F401 — the program's x64 setting, as every run has it


def _scale_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def scale_rows(x):
    z = np.int32(0)  # a literal 0 would trace as i64 under x64, which Mosaic refuses
    return pl.pallas_call(
        _scale_kernel, grid=(x.shape[0] // 256,),
        in_specs=[pl.BlockSpec((256, x.shape[1]), lambda i: (i, z))],
        out_specs=pl.BlockSpec((256, x.shape[1]), lambda i: (i, z)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="scale_rows")(x)


@jax.custom_vjp
def norm(x, g):
    return _norm_fwd(x, g)[0]


@jax.named_scope("layernorm")
def _norm_fwd(x, g):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-5)
    return (x32 * inv * g).astype(x.dtype), (x, g, inv)


@jax.named_scope("layernorm_bwd")
def _norm_bwd(res, ct):
    x, g, inv = res
    xhat = x.astype(jnp.float32) * inv
    ct32 = ct.astype(jnp.float32)
    dg = jnp.sum(ct32 * xhat, axis=0)
    dy = ct32 * g
    dx = inv * (dy - xhat * jnp.mean(dy * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dg


norm.defvjp(_norm_fwd, _norm_bwd)


def loss_fn(params, x, y):
    with jax.named_scope("forward"):
        with jax.named_scope("net"):
            with jax.named_scope("dense0"):
                h = jnp.tanh(x @ params["w0"])
            with jax.named_scope("layernorm0"):
                h = norm(h, params["g"])
            with jax.named_scope("kernel0"):
                h = h + scale_rows(jax.lax.stop_gradient(h))  # a kernel with no backward

            def body(_, c):  # a loop: its body's operations nest in the trace
                return jnp.tanh(c @ params["w1"])

            with jax.named_scope("loop0"):
                h = jax.lax.fori_loop(0, 4, body, h)
        return jnp.mean((h.astype(jnp.float32) - y) ** 2)


@jax.jit
def train_step(params, x, y):
    loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
    with jax.named_scope("optimizer"):
        params = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype), params, grads)
    return loss, params


def without_plane(pt, data, name):
    """The XSpace's bytes with one plane left out: ``/host:metadata`` holds the
    program's whole HLO proto (two thirds of the file) and nothing that
    ``aggregate`` reads. The other planes stay byte for byte as recorded."""
    out, view = bytearray(), memoryview(data)
    pos = 0
    while pos < len(view):
        start = pos
        key, pos = pt._varint(view, pos)
        if key & 7 != 2:
            raise ValueError("an XSpace holds length-delimited fields only")
        size, pos = pt._varint(view, pos)
        body = view[pos:pos + size]
        pos += size
        if key >> 3 == 1 and pt._Plane(body).name == name:
            continue
        out += view[start:pos]
    return bytes(out)


def main(out_dir):
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    key = jax.random.PRNGKey(0)
    n, d = 2048, 512
    params = {"w0": jax.random.normal(key, (d, d), jnp.bfloat16) * 0.05,
              "w1": jax.random.normal(key, (d, d), jnp.bfloat16) * 0.05,
              "g": jnp.ones((d,), jnp.float32)}
    x = jax.random.normal(key, (n, d), jnp.bfloat16)
    y = jnp.zeros((n, d), jnp.float32)
    loss, params = train_step(params, x, y)  # compile outside the trace
    jax.block_until_ready(loss)
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a million Python calls would not be small
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            for i in range(3):
                with jax.profiler.TraceAnnotation("mxt.step.dispatch", step=i):
                    loss, params = train_step(params, x, y)
                with jax.profiler.TraceAnnotation("mxt.window.retire"):
                    jax.block_until_ready(loss)
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    from mxnet_tpu import profiler_trace

    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
    final = os.path.join(out_dir, "scoped_step.xplane.pb")
    with open(path, "rb") as f:
        whole = f.read()
    with open(final, "wb") as f:
        f.write(without_plane(profiler_trace, whole, "/host:metadata"))
    print("wrote", final, os.path.getsize(final), "bytes of", len(whole), flush=True)

    agg = profiler_trace.aggregate(final)
    print(profiler_trace.format_table(agg), flush=True)
    import json

    print(json.dumps(agg), flush=True)
    # every operation's metadata, for the rule that PERF.md section 3 states
    for plane in profiler_trace.read_planes(final):
        if plane.name.startswith(profiler_trace.DEVICE_PREFIX):
            for _, events in plane.lines(wanted=(profiler_trace.OPS_LINE,)):
                for mid in sorted({e[0] for e in events}):
                    m = plane.metadata[mid]
                    print("OP", m.get("display_name"), "|", m.get("hlo_category"), "|",
                          m.get("tf_op"), "|", m["name"][:60], flush=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/scoped_trace")

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

One process, one TPU chip, the entry points a user would call, at published
widths with random weights made from ``--seed``:

1. ``sync``    — block_until_ready waits: ten chained 8192^2 bf16 matmuls,
                 time to ``wait_to_read`` against time to a host read.
2. ``kernels`` — every Pallas kernel in mxnet_tpu/ops, compiled (never
                 interpreted) against its plain reference: flash forward,
                 the expert layer's grouped matmuls (with ms a call beside
                 ``ragged_dot``'s), paged decode.
3. ``resnet50``— ResNet-50 v1, bf16, NHWC, batch 64, 224x224, through the
                 Gluon loop fused into one launch (``Trainer.fuse_step``).
4. ``bert``    — BERT-base MLM, vocabulary 30522, batch 32 x 128, bf16,
                 through ``parallel.ShardedTrainStep``; the flash kernel's
                 custom call must be in the compiled step.
5. ``serving`` — decode engine + PagedKVCache + TinyDecoder (12 layers,
                 12 heads x 64), 8 slots, prompts 16-200, 32 new tokens,
                 token for token against ``reference_decode``.

``--chips 4`` runs only the four-chip path: ResNet-50 data-parallel over
four devices against the same seed and global batch on one device.
``--rehearse`` is rehearsal 1 and 2 of the on-chip-measurement guide: the
same code at tiny shapes on the CPU, kernels in interpret mode, virtual
devices for ``--chips 4``. It never prints ``"ok": true``: the device is not
a TPU, and the library gets no fallback for that.

Per-phase results go on earlier lines (one JSON object each: loss values,
compile and step seconds as ONE COLD RUN, not a benchmark). The last line of
stdout is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": n}}``
and the exit code 0 only if every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import math
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


# bf16 peak of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_BF16_PEAK = 197e12


class Failed(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise Failed(msg)


def emit(**row):
    print(json.dumps(row, default=str), flush=True)


def rel_err(a, b):
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6))


def median_ms(fn, *args):
    """Median wall milliseconds of five ``fn(*args)`` to block_until_ready
    after one warm call (the median resists the one scheduling hiccup). One
    cold process on a shared host: a reading, not a benchmark."""
    import jax

    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return sorted(samples)[len(samples) // 2] * 1e3


def inflight_ms(fn, *args, calls=20, repeats=3):
    """Wall milliseconds a call of ``calls`` calls dispatched back to back and
    waited for once, the least of ``repeats``: for a kernel of a fraction of a
    millisecond, which one blocking call's dispatch would drown."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def device_ms(variants, calls=5):
    """Device milliseconds a call of each of ``variants`` ({name: (fn, args)}),
    read from one profiler trace of ``calls`` calls of each under a scope of
    its name, and the Pallas kernels' by kernel name: ({name: ms}, {kernel: ms
    a call of its variant}). Under the 0.2 ms a dispatch costs the host, where
    ``inflight_ms`` reads that cost and not the device's. {} where the trace
    holds no device line (the CPU)."""
    import shutil
    import tempfile

    import jax

    from mxnet_tpu import profiler_trace

    scoped = {}
    for name, (fn, args) in variants.items():
        def under(*a, fn=fn, name=name):
            with jax.named_scope(name):
                return fn(*a)
        scoped[name] = jax.jit(under)
        jax.block_until_ready(scoped[name](*args))
    path = tempfile.mkdtemp(prefix="mxt_device_ms_")
    try:
        jax.profiler.start_trace(path)
        for name, (_, args) in variants.items():
            for _ in range(calls):
                jax.block_until_ready(scoped[name](*args))
        jax.profiler.stop_trace()
        agg = profiler_trace.aggregate(path, top=0)
    finally:
        shutil.rmtree(path, ignore_errors=True)
    if agg is None:
        return {}, {}
    per = 1e3 / calls
    return ({name: agg["named_s"].get(name, 0.0) * per for name in variants},
            {name: s * per for name, s in agg["kernel_s"].items()})


def mem(dev):
    """(bytes in use now, peak bytes so far) as the backend reports them."""
    st = dev.memory_stats() or {}
    return st.get("bytes_in_use"), st.get("peak_bytes_in_use")


class Window:
    """Launches, compiles and host syncs between enter and exit."""

    def __enter__(self):
        from mxnet_tpu import profiler, tuning

        self._p, self._t = profiler, tuning
        self.c0 = tuning.compile_stats()
        self.l0 = profiler.launch_count()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        c1 = self._t.compile_stats()
        self.seconds = time.perf_counter() - self.t0
        self.launches = self._p.launch_count() - self.l0
        self.compiles = c1["compiles"] - self.c0["compiles"]
        self.compile_seconds = c1["compile_seconds"] - self.c0["compile_seconds"]
        self.cache_hits = c1["cache_hits"] - self.c0["cache_hits"]
        self.cache_misses = c1["cache_misses"] - self.c0["cache_misses"]
        return False


# ---------------------------------------------------------------------------
# phase: sync
# ---------------------------------------------------------------------------
def phase_sync(args, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import nd, profiler

    n = 256 if args.rehearse else 8192
    a = (jax.random.normal(jax.random.PRNGKey(args.seed), (n, n), jnp.float32)
         / math.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def chain(x):
        y = x
        for _ in range(10):
            y = jnp.dot(y, x, preferred_element_type=jnp.float32).astype(x.dtype)
        return y

    pick = jax.jit(lambda x: x[:1, :1])
    jax.block_until_ready(pick(chain(a)))  # compile both
    # A: dispatch, then the library's wait, then what a host read still costs
    t0 = time.perf_counter()
    y = nd.NDArray(chain(a))
    t_dispatch = time.perf_counter() - t0
    s0 = profiler.host_sync_count()
    y.wait_to_read()
    t_wait = time.perf_counter() - t0
    syncs = profiler.host_sync_count() - s0
    np.asarray(pick(y.data))
    t_after = time.perf_counter() - t0 - t_wait
    # B: dispatch, then a one-element host read (the wait that cannot lie)
    t0 = time.perf_counter()
    np.asarray(pick(chain(a)))
    t_read = time.perf_counter() - t0
    # the least the chip could take for ten n^3 matmuls at its bf16 peak
    floor = 10 * 2.0 * n ** 3 / V5E_BF16_PEAK
    row = dict(phase="sync", n=n, dispatch_s=t_dispatch, wait_to_read_s=t_wait,
               read_after_wait_s=t_after, host_read_s=t_read,
               v5e_peak_floor_s=floor, host_syncs_counted=syncs)
    check(syncs == 1, "wait_to_read must count exactly one host sync, got %d" % syncs)
    if not args.rehearse:
        check(t_wait >= 0.9 * floor,
              "wait_to_read returned in %.4fs, sooner than the chip's peak allows "
              "(%.4fs): block_until_ready does not wait" % (t_wait, floor))
        check(t_wait >= 0.5 * t_read,
              "wait_to_read %.4fs is far below a host read %.4fs" % (t_wait, t_read))
        check(t_after <= 0.5 * t_wait,
              "a host read after wait_to_read still took %.4fs of %.4fs: the "
              "wait returned early" % (t_after, t_wait))
    return row


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def phase_kernels(args, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import tuning
    from mxnet_tpu.ops import attention as A

    interp = bool(args.rehearse)
    key = jax.random.PRNGKey(args.seed)
    out = dict(phase="kernels", interpret=interp, flash={}, paged={})

    # -- flash forward, blocks as the tuning table's cost model picks them,
    #    and the backward kernel wherever _flash_bwd would take it, at the
    #    blocks it would take, against the reference's gradient
    if args.rehearse:
        flash_cases = [("bert_bias", (2, 2, 128, 64), False, True),
                       ("bert_s512", (1, 2, 256, 64), False, False),
                       ("causal_512", (1, 2, 256, 64), True, False),
                       ("mla_causal", (1, 2, 256, 48), True, False, 32)]
    else:
        flash_cases = [("bert_bias", (32, 12, 128, 64), False, True),
                       ("bert_s512", (32, 12, 512, 64), False, False),
                       ("causal_512", (8, 12, 512, 64), True, False),
                       # largest K/V residency _kv_fits_vmem admits (D=64, bf16)
                       ("causal_maxseq", (1, 2, 16384, 64), True, False),
                       # latent attention's head: keys 192, values 128 wide,
                       # the backward asking for its own scoped VMEM
                       ("mla_causal_4096", (1, 4, 4096, 192), True, False, 128)]
    for name, shape, causal, with_bias, *dv in flash_cases:
        ks = jax.random.split(jax.random.fold_in(key, len(name)), 4)
        wide = shape[:3] + tuple(dv or shape[3:])  # values and dO
        q, k, v, do = (jax.random.normal(s, sh, jnp.bfloat16)
                       for s, sh in zip(ks, (shape, shape, wide, wide)))
        check(A._kv_fits_vmem(k, v), "%s must take the whole-sequence K/V path" % name)
        bias = None
        if with_bias:
            lens = np.linspace(shape[2] // 4, shape[2], shape[0]).astype(np.int32)
            bias = A.make_padding_bias(jnp.asarray(lens), max_len=shape[2])
        cfg = tuning.heuristic_attention(shape, shape[2], "bfloat16", causal)
        sm = 1.0 / math.sqrt(shape[3])
        fwd = jax.jit(lambda q, k, v, b: A._flash_forward_pallas(
            q, k, v, b, causal, sm, cfg["block_q"], cfg["block_k"],
            interpret=interp))

        def kernel(q, k, v, b):
            return fwd(q, k, v, b)[0]

        xla = jax.jit(lambda q, k, v, b: A._attention_reference(
            q, k, v, b, causal, sm))
        t0 = time.perf_counter()
        got = kernel(q, k, v, bias).block_until_ready()
        dt = time.perf_counter() - t0
        err = rel_err(got, xla(q, k, v, bias))
        out["flash"][name] = dict(
            shape=shape, block_q=cfg["block_q"], block_k=cfg["block_k"],
            rel_err=err, first_call_s=dt,
            kernel_ms=median_ms(kernel, q, k, v, bias),
            xla_ms=median_ms(xla, q, k, v, bias))
        check(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name + ": not finite")
        check(err < 2e-2, "flash %s: rel err %.4f vs reference" % (name, err))
        if not A._qdo_fits_vmem(q, v):  # _flash_bwd keeps such a call in XLA
            continue
        bq, bk = A._bwd_blocks(shape[2], shape[2])
        o, lse = fwd(q, k, v, bias)
        bwd = jax.jit(lambda q, k, v, b, o, lse, do: A._flash_backward_pallas(
            q, k, v, b, o, lse, do, causal, sm, bq, bk, interpret=interp)[:3])
        chunk = A._bwd_chunk(shape[0], shape[1], shape[2], shape[2])
        bwd_xla = jax.jit(lambda q, k, v, b, o, lse, do: A._bwd_chunked(
            q, k, v, b, o, lse, do, causal, sm, chunk=chunk)[:3])
        f32 = jnp.float32
        ref = jax.jit(jax.grad(lambda q, k, v, b, do: jnp.sum(A._attention_reference(
            q.astype(f32), k.astype(f32), v.astype(f32), b, causal, sm)
            * do.astype(f32)), argnums=(0, 1, 2)))
        with jax.default_matmul_precision("highest"):
            want = ref(q, k, v, bias, do)
        errs = [rel_err(g, w) for g, w in zip(bwd(q, k, v, bias, o, lse, do), want)]
        out["flash"][name].update(
            bwd_block_q=bq, bwd_block_k=bk, bwd_rel_err=errs,
            bwd_kernel_ms=median_ms(bwd, q, k, v, bias, o, lse, do),
            bwd_xla_chunk=chunk,
            bwd_xla_ms=median_ms(bwd_xla, q, k, v, bias, o, lse, do))
        check(max(errs) < 2e-2, "flash backward %s: rel errs %s vs the reference's "
              "gradient" % (name, errs))

    # -- the fused projection in place (flash_attention_qkv's kernels): output
    #    and the one dqkv cotangent against the reference on turned operands,
    #    at the BERT cells' shapes and the rule's cut of a grid step
    out["flash_in_place"] = {}
    in_place_cases = [("s128_bias", (2, 128, 2, 64), True), ("s256", (1, 256, 2, 64), False)] \
        if args.rehearse else [("bert_s128_bias", (128, 128, 12, 64), True),
                               ("bert_s512", (32, 512, 12, 64), False)]
    for name, (B, T, H, D), with_bias in in_place_cases:
        ks = jax.random.split(jax.random.fold_in(key, 100 + len(name)), 2)
        qkv = jax.random.normal(ks[0], (B, T, 3 * H * D), jnp.bfloat16)
        do = jax.random.normal(ks[1], (B, T, H * D), jnp.bfloat16)
        bias = None
        if with_bias:
            lens = np.linspace(T // 4, T, B).astype(np.int32)
            bias = A.make_padding_bias(jnp.asarray(lens), max_len=T)
        sm = 1.0 / math.sqrt(D)
        fwd = jax.jit(lambda x, b: A._qkv_forward_pallas(x, b, H, sm, interpret=interp))
        bwd = jax.jit(lambda x, b, o, l, d: A._qkv_backward_pallas(
            x, b, o, l, d, H, sm, interpret=interp)[0])

        def turned(x, b):
            q, k, v = A._heads_major(x.astype(jnp.float32), H, D)
            o = A._attention_reference(q, k, v, b, False, sm)
            return jnp.reshape(jnp.transpose(o, (0, 2, 1, 3)), (B, T, -1))

        with jax.default_matmul_precision("highest"):
            want, vjp = jax.vjp(lambda x: turned(x, bias), qkv)
            want_g = vjp(do.astype(jnp.float32))[0]
        got, lse = fwd(qkv, bias)
        errs = [rel_err(got, want), rel_err(bwd(qkv, bias, got, lse, do), want_g)]
        out["flash_in_place"][name] = dict(
            shape=(B, T, H, D), rows=A._in_place_rows(B, T), rel_err=errs,
            kernel_ms=median_ms(lambda x, b: fwd(x, b)[0], qkv, bias),
            bwd_kernel_ms=median_ms(bwd, qkv, bias, got, lse, do))
        check(max(errs) < 2e-2, "in-place flash %s: rel errs %s vs the reference"
              % (name, errs))

    # -- the learned selection: the indexer's kernel against lax.top_k's set
    #    (the XLA path: the same float32 scores, a full sort), then grouped
    #    heads under that mask through both flash kernels against the
    #    reference's output and gradient; timed at the Keye cell's shape
    from mxnet_tpu.ops import indexer as X

    sizes = [(1, 2, 1, 256, 32, 2, 16, 64)] if args.rehearse else [
        (1, 8, 1, 2048, 128, 16, 64, 512), (1, 32, 4, 8192, 128, 16, 64, 2048)]
    for B, H, G, T, D, Hi, Di, topk in sizes:
        ks = jax.random.split(jax.random.fold_in(key, T), 7)
        qi, ki, wi = (jax.random.normal(s, sh, jnp.bfloat16) for s, sh in zip(
            ks, ((B, T, Hi, Di), (B, T, Di), (B, T, Hi))))
        if interp:  # the rehearsal: the kernel itself, in interpret mode
            def select(q, k, w, topk=topk):
                mask = X._select_pallas(jnp.transpose(q, (0, 2, 1, 3)), k,
                                        jnp.transpose(w, (0, 2, 1)), topk, True)
                return mask, jnp.sum(mask, dtype=jnp.int32)
        else:
            def select(q, k, w, topk=topk):
                return X.lightning_indexer(q, k, w, topk=topk)[:2]
        select = jax.jit(select)
        mask, selected = select(qi, ki, wi)
        row = dict(shape=(B, H, G, T, D), topk=topk, selected=int(selected),
                   select_ms=median_ms(lambda *a: select(*a)[0], qi, ki, wi))
        check(int(selected) == B * sum(min(t + 1, topk) for t in range(T)),
              "indexer T=%d: %d pairs selected" % (T, int(selected)))
        q, k, v, do = (jax.random.normal(s, sh, jnp.bfloat16) for s, sh in zip(
            ks[3:], ((B, H, T, D), (B, G, T, D), (B, G, T, D), (B, H, T, D))))
        sm = 1.0 / math.sqrt(D)
        cfg = tuning.heuristic_attention((B, H, T, D), T, "bfloat16", True)
        bq, bk = A._bwd_blocks(T, T)
        fwd = jax.jit(lambda q, k, v, m: A._flash_forward_pallas(
            q, k, v, None, True, sm, cfg["block_q"], cfg["block_k"],
            interpret=interp, mask=m))
        bwd = jax.jit(lambda q, k, v, m, o, lse, do: A._flash_backward_pallas(
            q, k, v, None, o, lse, do, True, sm, bq, bk, interpret=interp, mask=m)[:3])
        o, lse = fwd(q, k, v, mask)
        grads = bwd(q, k, v, mask, o, lse, do)
        row.update(fwd_ms=median_ms(lambda *a: fwd(*a)[0], q, k, v, mask),
                   bwd_ms=median_ms(bwd, q, k, v, mask, o, lse, do))
        if T <= 2048:  # sizes at which the plain forms fit
            want_mask = jax.jit(lambda q, k, w: X._select_xla(
                jnp.transpose(q, (0, 2, 1, 3)), k, jnp.transpose(w, (0, 2, 1)), topk))(
                    qi, ki, wi)
            row["selection_differs"] = int(jnp.sum(mask != want_mask))
            check(row["selection_differs"] == 0, "indexer kernel T=%d: %d pairs off "
                  "lax.top_k's set" % (T, row["selection_differs"]))
            f32 = jnp.float32
            ref = jax.jit(lambda q, k, v, m, do: jax.vjp(
                lambda q_, k_, v_: A._attention_reference(
                    q_.astype(f32), k_.astype(f32), v_.astype(f32), None, True, sm, m),
                q, k, v)[1](do.astype(f32)))
            with jax.default_matmul_precision("highest"):
                want = ref(q, k, v, mask, do)
                want_o = A._attention_reference(q.astype(f32), k.astype(f32),
                                                v.astype(f32), None, True, sm, mask)
            row["rel_err"] = rel_err(o, want_o)
            row["bwd_rel_err"] = [rel_err(g, w) for g, w in zip(grads, want)]
            check(max([row["rel_err"]] + row["bwd_rel_err"]) < 2e-2,
                  "masked grouped flash T=%d: %s" % (T, row))
        check(bool(jnp.all(jnp.isfinite(o.astype(jnp.float32)))), "selected flash: not finite")
        out["flash"]["selected_%d" % T] = row

    out["grouped_matmul"] = grouped_matmul_table(args, key, interp)
    out["row_movement"] = row_movement_table(args, interp)
    out["causal_conv"] = causal_conv_table(args, interp)
    out["ssd"] = ssd_table(args, interp)
    out["delta_rule"] = delta_rule_table(args, interp)
    out["embedding_grad"] = embedding_grad_table(args, interp)

    # -- paged decode: 12 heads x 64, page 16, 64 pages per sequence, bf16;
    #    every block the candidate generator offers, and the one it picks
    if args.rehearse:
        B, H, D, S, maxp = 2, 12, 64, 16, 16
    else:
        B, H, D, S, maxp = 8, 12, 64, 16, 64
    P = B * maxp + 1
    ks = jax.random.split(jax.random.fold_in(key, 7), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    k_pages = jax.random.normal(ks[1], (P, S, H, D), jnp.bfloat16)
    v_pages = jax.random.normal(ks[2], (P, S, H, D), jnp.bfloat16)
    rs = np.random.RandomState(args.seed)
    pt = jnp.asarray(rs.permutation(P - 1)[:B * maxp].reshape(B, maxp), jnp.int32)
    lens = rs.randint(1, S * maxp + 1, size=B)
    lens[0], lens[-1] = 1, S * maxp
    cl = jnp.asarray(lens, jnp.int32)
    sm = 1.0 / math.sqrt(D)
    ref = A._paged_gather_reference(q, k_pages, v_pages, pt, cl, sm)
    picked = tuning.heuristic_paged((B, H, D), S, maxp, "bfloat16")
    check(picked["backend"] == "pallas",
          "the cost model must pick the kernel at %d-token contexts" % (S * maxp))
    for bh in sorted(set(tuning.paged_candidates(H, D, S, "bfloat16")) | {H}):
        got = A._paged_decode_pallas(q, k_pages, v_pages, pt, cl, sm, bh,
                                     interpret=interp)
        err = rel_err(got, ref)
        out["paged"]["block_h=%d" % bh] = err
        check(err < 2e-2, "paged decode block_h=%d: rel err %.4f" % (bh, err))
    out["paged"]["picked_block_h"] = picked["block_h"]
    return out


def grouped_matmul_table(args, key, interp):
    """The expert layer's three grouped products (forward, input gradient,
    weight gradient; gate / up and down) at the three expert cells' shapes,
    16 groups holding about half the rows laid out: each against
    ``ragged_dot`` in float32, and ms a call for the kernel, for ``ragged_dot``
    on the rows laid out and on the rows cut to those held (PERF.md,
    Findings, PR 40: the stand-alone table)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import grouped_matmul as GM

    bf16, f32 = jnp.bfloat16, jnp.float32
    groups = 16
    cells = {"tiny": (1024, 256, 128, 520, 0.25)} if args.rehearse else {
        "kanana2_a3b_train_s4096": (12288, 2048, 768, 7400, 0.25),
        "keye_vl2_a3b_train_s8192": (16384, 2048, 768, 8000, 0.15),
        "lfm2_a2b_train_s8192": (16384, 2048, 1536, 8200, 0.10)}
    table = {}
    for cell, (rows, hidden, width, held, sigma) in cells.items():
        rs = np.random.RandomState(args.seed % (2 ** 31))
        share = np.exp(sigma * rs.normal(size=groups))
        sizes = jnp.asarray(rs.multinomial(held, share / share.sum()), jnp.int32)
        cut = -(-held // GM.ROW_TILE) * GM.ROW_TILE
        ks = jax.random.split(jax.random.fold_in(key, rows + width), 4)
        valid = (jnp.arange(rows) < held)[:, None]
        wide = jnp.where(valid, jax.random.normal(ks[0], (rows, hidden), f32), 0).astype(bf16)
        thin = jnp.where(valid, jax.random.normal(ks[1], (rows, width), f32), 0).astype(bf16)
        gate = (0.02 * jax.random.normal(ks[2], (groups, hidden, width), f32)).astype(bf16)
        down = (0.02 * jax.random.normal(ks[3], (groups, width, hidden), f32)).astype(bf16)

        for name, x, w, dy in (("gate", wide, gate, thin), ("down", thin, down, wide)):
            for product in ("fwd", "dx", "dw"):
                with jax.default_matmul_precision("highest"):
                    want = jax.jit(lambda x, w, dy, s, p=product: GM._ragged(
                        p, x.astype(f32), w.astype(f32), dy.astype(f32), s))(x, w, dy, sizes)
                if product != "dw":  # on the chip ragged_dot leaves those rows as they lay
                    want = jnp.where(valid, want, 0.0)
                ours = jax.jit(functools.partial(GM._kernel, product, interpret=interp))
                theirs = jax.jit(functools.partial(GM._ragged, product))
                got = ours(x, w, dy, sizes)
                row = dict(rel_err=rel_err(got, want),
                           kernel_ms=inflight_ms(ours, x, w, dy, sizes),
                           ragged_dot_ms=inflight_ms(theirs, x, w, dy, sizes),
                           ragged_dot_rows_held_ms=inflight_ms(
                               theirs, x[:cut], w, dy[:cut], sizes))
                table["%s.%s.%s" % (cell, name, product)] = row
                check(row["rel_err"] < 2e-2, "grouped matmul %s %s %s: rel err %.4f vs "
                      "float32" % (cell, name, product, row["rel_err"]))
                if product != "dw":
                    check(not bool(jnp.any(got[held:])), "grouped matmul %s %s %s: rows "
                          "past the groups are not zero" % (cell, name, product))
    return table


def row_movement_table(args, interp):
    """The expert layer's two movements of rows at the four expert cells'
    shapes, a seeded routing of 8192 tokens laid out as ``ops/moe.py`` lays
    block 0 out: ``take`` (XLA's gather of the rows laid out) and ``sum`` by
    XLA's gathers of every entry against ``ops/row_gather.py``'s kernels,
    which move the rows held; equality checked, device ms a call (one trace a
    cell: ``device_ms``), and of the kernels' sum the two kernels' own (what is
    left is the list of rows by token) (PERF.md, Findings, PR 42: the
    stand-alone table)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import moe as M
    from mxnet_tpu.ops import row_gather as RG

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    cells = {"tiny": (512, 256, 4, 8, 2)} if args.rehearse else {
        "smallthinker_a3b_train_s8192": (8192, 2560, 6, 64, 16),
        "keye_vl2_a3b_train_s8192": (8192, 2048, 8, 128, 16),
        "kanana2_a3b_train_s4096": (8192, 2048, 6, 128, 16),
        "lfm2_a2b_train_s8192": (8192, 2048, 4, 64, 16)}
    table = {}
    for cell, (tokens, hidden, k, n_routed, count) in cells.items():
        rs = np.random.RandomState(args.seed % (2 ** 31))
        chosen = np.argsort(rs.rand(tokens, n_routed), axis=1)[:, :k].reshape(-1)
        bound = M.default_slots_bound(tokens, k, n_routed, count)
        key_ = np.where(chosen < count, chosen, count)
        order = np.argsort(key_, kind="stable")
        held = int((chosen < count).sum())
        check(held <= bound, "row movements %s: the seeded routing overflows block 0" % cell)
        back = np.full(tokens * k, bound)
        back[order[:held]] = np.arange(held)
        slot = jnp.asarray(order[:bound], i32)
        valid = jnp.arange(bound) < held
        back = jnp.asarray(back.reshape(tokens, k), i32)
        ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 2)
        x = jax.random.normal(ks[0], (tokens, hidden), f32).astype(bf16)
        o = jnp.where(valid[:, None], jax.random.normal(ks[1], (bound, hidden), f32), 0).astype(bf16)

        take, gathers = jax.jit(M._take_rows), jax.jit(M._gathered_sum)  # what runs where the rule refuses

        kernels = functools.partial(RG._sum_pallas, tokens=tokens, k=k, interpret=interp)
        n_held = jnp.asarray(held, i32)
        got, want = kernels(o, slot, n_held), gathers(o, back)
        variants = {"take_xla": (take, (x, slot, valid, back)), "sum_xla": (gathers, (o, back)),
                    "sum_kernels": (kernels, (o, slot, n_held))}
        ms, parts = device_ms(variants)
        if not ms:  # no device line to read: the host's clock
            ms = {name: inflight_ms(fn, *a) for name, (fn, a) in variants.items()}
        row = dict(rows_laid_out=bound, rows_held=held, entries=tokens * k,
                   equal=bool(jnp.all(got == want)), clock="device" if parts else "host",
                   take_xla_ms=ms["take_xla"], sum_xla_ms=ms["sum_xla"],
                   sum_kernels_ms=ms["sum_kernels"], **{"%s_ms" % n: v for n, v in parts.items()})
        table[cell] = row
        check(row["equal"], "row movements %s: the kernels' sum is not XLA's" % cell)
    return table


def causal_conv_table(args, interp):
    """Mamba-2's filter under SiLU at the Granite cell's shape, (1, 8192, 4352)
    bfloat16 under 4 taps and a bias: ``ops/causal_conv_pallas.py``'s two
    kernels at the tiles the dispatch picks against the ``jax.numpy`` formula of
    ``ops/gated_conv.py`` (both float32 inside, rounded once), forward and all
    three gradients, and device ms a call of each, the kernels' operands turned
    to (B, C, T) beforehand as XLA lays them out in the cell (PERF.md, Findings,
    PR 44)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import causal_conv_pallas as CC
    from mxnet_tpu.ops import gated_conv as G

    bf16, f32 = jnp.bfloat16, jnp.float32
    b, t, c, k = (1, 512, 256, 4) if args.rehearse else (1, 8192, 4352, 4)
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 4)
    x, g = (jax.random.normal(s, (b, t, c), f32).astype(bf16) for s in ks[:2])
    w = jax.random.uniform(ks[2], (c, k), f32, -0.5, 0.5).astype(bf16)
    bias = (0.1 * jax.random.normal(ks[3], (c,), f32)).astype(bf16)
    fwd = functools.partial(CC._fwd_pallas, begin=0, tiles=CC._tiles(t, c, 0), interpret=interp)
    bwd = functools.partial(CC._bwd_pallas, begin=0, tiles=CC._tiles(t, c, 0), interpret=interp)
    turn = functools.partial(jnp.swapaxes, axis1=1, axis2=2)  # the kernels' tokens lie along the lanes
    xt, gt = turn(x), turn(g)
    variants = {"fwd_xla": (G._filtered_silu, (x, w, bias)),
                "bwd_xla": (G._filtered_silu_grads, (x, w, bias, g)),
                "fwd_kernel": (fwd, (xt, w, bias)), "bwd_kernel": (bwd, (xt, w, bias, gt))}
    dxt, dw, db = bwd(xt, w, bias, gt)
    got = (turn(fwd(xt, w, bias)), turn(dxt), dw, db)
    want = (jax.jit(G._filtered_silu)(x, w, bias),) + jax.jit(G._filtered_silu_grads)(x, w, bias, g)
    ms, parts = device_ms(variants)
    if not ms:  # no device line to read: the host's clock
        ms = {name: inflight_ms(fn, *a) for name, (fn, a) in variants.items()}
    row = dict(shape=[b, t, c, k], clock="device" if parts else "host",
               rel_err=[rel_err(a, b_) for a, b_ in zip(got, want)],
               **{"%s_ms" % n: v for n, v in ms.items()})
    check(max(row["rel_err"]) < 1e-2, "causal filter's kernels against the formula: %s" % row)
    return row


def ssd_table(args, interp):
    """Mamba-2's scan at the Granite cell's shape, (1, 8192) tokens of 64 heads
    of 64 in one group with a state of 128 in chunks of 256, bfloat16:
    ``ops/ssd_pallas.py``'s two kernels and the ``jax.numpy`` formula of
    ``ops/ssd.py`` (both float32 inside, their products' operands bfloat16),
    each against the formula on the same values in float32 at the highest
    matmul precision, the result and all seven gradients (the running sums'
    gradient is what is left of rows less columns, and on the chip the
    bfloat16 formula loses digits there that the kernels keep: PERF.md,
    Findings, PR 48), and device ms a call of each: the whole op on (B, T, H,
    P) operands both ways (stand-alone the kernels' ``swapaxes`` are transposes
    XLA runs; in the cell they are layouts) and the kernels alone on operands
    turned beforehand."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import ssd as S
    from mxnet_tpu.ops import ssd_pallas as K

    bf16, f32 = jnp.bfloat16, jnp.float32
    b, t, h, p, n, chunk = (1, 256, 8, 16, 128, 128) if args.rehearse \
        else (1, 8192, 64, 64, 128, 256)
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 8)
    x, dy = (jax.random.normal(s, (b, t, h, p), f32).astype(bf16) for s in ks[:2])
    B, C = (jax.random.normal(s, (b, t, 1, n), f32).astype(bf16) for s in ks[2:4])
    dt = jax.random.normal(ks[4], (b, t, h), f32).astype(bf16)
    A_log = jnp.log(jax.random.uniform(ks[5], (h,), f32, 1.0, 16.0)).astype(bf16)
    D = jax.random.normal(ks[6], (h,), f32).astype(bf16)
    dt_bias = (jax.random.normal(ks[7], (h,), f32) - 2.0).astype(bf16)
    args7 = (x, dt, A_log, B, C, D, dt_bias)
    dts, cum, _ = K._decays(dt, A_log, dt_bias, chunk)
    turned = tuple(K._turned(z, chunk) for z in (x, dy, B, C))
    fwd = functools.partial(K._fwd_pallas, groups=1, chunk=chunk, interpret=interp)
    bwd = functools.partial(K._bwd_pallas, groups=1, chunk=chunk, interpret=interp)
    formula, formula_grads = (functools.partial(f, chunk) for f in (S._scan, S._scan_grads))
    xla_y, opening = jax.jit(formula)(*args7)
    xla = (xla_y,) + jax.jit(formula_grads)(*args7, opening, dy)
    with jax.default_matmul_precision("highest"):
        exact7 = tuple(a.astype(f32) for a in args7)
        want_y, want_opening = jax.jit(formula)(*exact7)
        want = (want_y,) + jax.jit(formula_grads)(*exact7, want_opening, dy.astype(f32))
    del want_opening
    scan, grads = (functools.partial(f, chunk, interpret=interp) for f in (K.scan, K.scan_grads))
    got_y, got_opening = jax.jit(scan)(*args7)
    got = (got_y,) + jax.jit(grads)(*args7, got_opening, dy)
    variants = {"fwd_xla": (formula, args7), "bwd_xla": (formula_grads, args7 + (opening, dy)),
                "fwd_op": (scan, args7), "bwd_op": (grads, args7 + (got_opening, dy)),
                "fwd_kernel": (fwd, (turned[0],) + turned[2:] + (dts, cum, D.astype(f32))),
                "bwd_kernel": (bwd, turned + (dts, cum, got_opening.reshape(b, -1, h * p, n),
                                              D.astype(f32)))}
    ms, parts = device_ms(variants)
    if not ms:  # no device line to read: the host's clock
        ms = {name: inflight_ms(fn, *a) for name, (fn, a) in variants.items()}
    row = dict(shape=[b, t, h, p, n, chunk], clock="device" if parts else "host",
               rel_err=[rel_err(a, b_) for a, b_ in zip(got, want)],
               xla_rel_err=[rel_err(a, b_) for a, b_ in zip(xla, want)],
               opening_rel_err=rel_err(got_opening, opening),
               **{"%s_ms" % k: v for k, v in ms.items()})
    # no farther from the float32 formula than the bfloat16 formula itself is
    check(all(e < max(2e-2, 1.5 * x) for e, x in zip(row["rel_err"], row["xla_rel_err"])),
          "the scan's kernels against the float32 formula: %s" % row)
    return row


def delta_rule_table(args, interp):
    """The gated delta rule at the Solar Open 2 cell's shape, (1, 8192) tokens
    of 8 heads of 128 in chunks of 64, bfloat16 with float32 log-decays of up to
    1.6 a token: ``ops/delta_rule_pallas.py``'s two kernels and the
    ``jax.numpy`` formula of ``ops/delta_rule.py`` (both float32 in their sums,
    lifts, solve and states, their other products' operands bfloat16), each
    against the formula on the same values in float32 at the highest matmul
    precision, the result and all five gradients, and device ms a call of each:
    the whole op on (B, T, H, .) operands both ways and the kernels alone."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import delta_rule as D
    from mxnet_tpu.ops import delta_rule_pallas as K

    bf16, f32 = jnp.bfloat16, jnp.float32
    b, t, h, d, chunk = (1, 128, 8, 128, 64) if args.rehearse else (1, 8192, 8, 128, 64)
    ks = jax.random.split(jax.random.PRNGKey(args.seed % (2 ** 31)), 6)
    q, k, v, do = (jax.random.normal(s, (b, t, h, d), f32).astype(bf16) for s in ks[:4])
    g = -1.6 * jax.random.uniform(ks[4], (b, t, h, d), f32)
    beta = (2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, h), f32))).astype(bf16)
    args5 = (q, k, v, g, beta)
    formula, formula_grads = (functools.partial(f, chunk) for f in (D._forward, D._grads))
    xla_o, opening = jax.jit(formula)(*args5)
    xla = (xla_o,) + jax.jit(formula_grads)(*args5, opening, do)
    with jax.default_matmul_precision("highest"):
        exact5 = tuple(a.astype(f32) for a in args5)
        want_o, want_opening = jax.jit(formula)(*exact5)
        want = (want_o,) + jax.jit(formula_grads)(*exact5, want_opening, do.astype(f32))
    del want_opening
    rule, grads = (functools.partial(f, chunk, interpret=interp) for f in (K.rule, K.rule_grads))
    got_o, kept = jax.jit(rule)(*args5)
    got = (got_o,) + jax.jit(grads)(*args5, kept, do)
    flat = tuple(K._flat(z, chunk) for z in (q, k, v, g)) + (K._by_block(beta, chunk),)
    fwd = functools.partial(K._fwd_pallas, chunk=chunk, interpret=interp)
    bwd = functools.partial(K._bwd_pallas, chunk=chunk, interpret=interp)
    variants = {"fwd_xla": (formula, args5), "bwd_xla": (formula_grads, args5 + (opening, do)),
                "fwd_op": (rule, args5), "bwd_op": (grads, args5 + (kept, do)),
                "fwd_kernel": (fwd, flat),
                "bwd_kernel": (bwd, flat + kept + (K._flat(do, chunk),))}
    ms, parts = device_ms(variants)
    if not ms:  # no device line to read: the host's clock
        ms = {name: inflight_ms(fn, *a) for name, (fn, a) in variants.items()}
    row = dict(shape=[b, t, h, d, chunk], clock="device" if parts else "host",
               rel_err=[rel_err(a, b_) for a, b_ in zip(got, want)],
               xla_rel_err=[rel_err(a, b_) for a, b_ in zip(xla, want)],
               opening_rel_err=rel_err(jnp.swapaxes(kept[0], -1, -2), opening),
               **{"%s_ms" % n: v for n, v in ms.items()})
    # no farther from the float32 formula than the bfloat16 formula itself is
    check(all(e < max(2e-2, 1.5 * x) for e, x in zip(row["rel_err"], row["xla_rel_err"])),
          "the delta rule's kernels against the float32 formula: %s" % row)
    return row


def embedding_ids(draw, rs, vocab, tokens):
    """``tokens`` ids of a ``vocab``-row table: ``uniform``, ``zipf`` (exponent
    1.1: a few rows take most tokens) or ``equal`` (one row takes them all)."""
    import numpy as np

    if draw == "uniform":
        return rs.randint(0, vocab, tokens)
    if draw == "zipf":
        return np.minimum(rs.zipf(1.1, tokens) - 1, vocab - 1)
    return np.full(tokens, vocab // 3)


def embedding_grad_table(args, interp, shapes=None, draws=("uniform",), dtype="bfloat16"):
    """The gradient of an embedding table, (vocab, width) looked up by
    ``tokens`` ids, at the SmallThinker cell's shape (``shapes``: {name:
    (vocab, width, tokens)}): ``ops/embedding_grad.py``'s grouped product over
    the table's tiles against XLA's scatter-add, each against the scatter-add
    of the float32 cotangent (the kernel's equal to one rounding; XLA's own
    rounds at every repeat), and device ms a call of each, the kernel's own
    beside (what is left is the sort, the gather of the sorted rows, the walk
    and the cut to ``vocab`` rows) (PERF.md, Findings, PR 45: the stand-alone
    table)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import embedding_grad as EG

    f32, dt = jnp.float32, jnp.dtype(dtype)
    if shapes is None:
        shapes = {"tiny": (300, 256, 512)} if args.rehearse else {
            "smallthinker_a3b_train_s8192": (37984, 2560, 8192)}
    table = {}
    for name, (vocab, width, tokens) in shapes.items():
        ours = jax.jit(functools.partial(EG.table_grad, vocab=vocab, interpret=interp))

        @jax.jit
        def theirs(ids, dy, vocab=vocab):  # what jnp.take transposes to
            zeros = jnp.zeros((vocab, dy.shape[1]), dy.dtype)
            return jax.vjp(lambda w: jnp.take(w, ids, axis=0), zeros)[1](dy)[0]

        for draw in draws:
            rs = np.random.RandomState(args.seed % (2 ** 31))
            ids = jnp.asarray(embedding_ids(draw, rs, vocab, tokens), jnp.int32)
            dy = jax.random.normal(jax.random.PRNGKey(args.seed % (2 ** 31)),
                                   (tokens, width), f32).astype(dt)
            want = theirs(ids, dy.astype(f32))
            row = dict(shape=[vocab, width, tokens], rel_err=rel_err(ours(ids, dy), want),
                       xla_rel_err=rel_err(theirs(ids, dy), want))
            del want
            variants = {"xla": (theirs, (ids, dy)), "kernel": (ours, (ids, dy))}
            ms, parts = device_ms(variants)
            if not ms:  # no device line to read: the host's clock
                ms = {n: inflight_ms(fn, *a) for n, (fn, a) in variants.items()}
            row.update(clock="device" if parts else "host", xla_ms=ms["xla"],
                       kernel_ms=ms["kernel"], **{"%s_ms" % n: v for n, v in parts.items()})
            table["%s.%s" % (name, draw)] = row
            # float32 is read, not held: the op leaves it to XLA (the MXU rounds it)
            check(dt != jnp.bfloat16 or row["rel_err"] < 5e-3,
                  "embedding gradient %s %s: the kernel is not the float32 sum rounded "
                  "once: %s" % (name, draw, row))
    return table


# ---------------------------------------------------------------------------
# training phases
# ---------------------------------------------------------------------------
def build_resnet(args, dtype="bfloat16"):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import model_zoo, nn

    name, classes, hw = ("resnet18_v1", 10, 32) if args.rehearse \
        else ("resnet50_v1", 1000, 224)
    mx.random.seed(args.seed)
    with nn.layout_scope("NHWC"):
        net = model_zoo.get_model(name, classes=classes)
    net.initialize()
    net.cast(dtype)  # bf16 is MXU-native; BN statistics stay f32 in the op
    net.hybridize()
    net(nd.zeros((1, hw, hw, 3), dtype=dtype))  # resolve deferred shapes
    return net, classes, hw


def fixed_image_batch(args, batch, classes, hw, dtype="bfloat16"):
    import numpy as np

    from mxnet_tpu import nd

    rng = np.random.RandomState(args.seed)
    x = nd.array(rng.uniform(-1, 1, (batch, hw, hw, 3)).astype(np.float32))
    y = nd.array(rng.randint(0, classes, (batch,)).astype(np.float32))
    return x.astype(dtype), y


def run_steps(step, x, y, n, on):
    """n steps on one fixed batch. Step 1 is the warm-up (it compiles); the
    window after it must show one launch a step and no compile."""
    import numpy as np

    losses, secs = [], []
    with Window() as warm:
        loss = step(x, y)
        losses.append(float(np.mean(loss.asnumpy().astype(np.float64))))
    with Window() as hot:
        for _ in range(n - 1):
            t0 = time.perf_counter()
            loss = step(x, y)
            check(loss.data.devices() <= on, "loss left the device(s) it trained on")
            losses.append(float(np.mean(loss.asnumpy().astype(np.float64))))
            secs.append(time.perf_counter() - t0)
    row = dict(losses=losses, warmup_s=warm.seconds, warmup_compiles=warm.compiles,
               warmup_compile_s=warm.compile_seconds, step_s=secs,
               launches_per_step=hot.launches / (n - 1),
               compiles_after_warmup=hot.compiles)
    check(all(math.isfinite(v) for v in losses), "loss not finite: %s" % losses)
    check(hot.launches == n - 1,
          "%d launches in %d steps after warm-up" % (hot.launches, n - 1))
    check(hot.compiles == 0, "%d compiles after warm-up" % hot.compiles)
    return row


def snapshot(params, names):
    return {n: params[n].data().asnumpy().copy() for n in names}


def check_trained(row, losses, first_expected, before, after):
    import numpy as np

    row["ln_classes"] = first_expected
    # random weights: the first loss is of the order of ln(classes)
    check(0.5 * first_expected < losses[0] < 2.0 * first_expected,
          "first loss %.3f is far from ln(classes) = %.3f" % (losses[0], first_expected))
    check(losses[-1] < losses[0] - 0.02,
          "loss did not fall: %s" % losses)
    for n in before:
        check(np.all(np.isfinite(after[n].astype(np.float32))), n + " not finite")
        check(not np.array_equal(before[n], after[n]), "parameter %s did not change" % n)


def phase_resnet50(args, dev):
    from mxnet_tpu import gluon

    batch = 4 if args.rehearse else 64
    net, classes, hw = build_resnet(args)
    x, y = fixed_image_batch(args, batch, classes, hw)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.025, "momentum": 0.9})
    step = trainer.fuse_step(net, gluon.loss.SoftmaxCrossEntropyLoss())
    params = net.collect_params()
    watch = [n for n in params if n.endswith("weight")]
    watch = [watch[0], watch[-1]]
    before = snapshot(params, watch)
    row = dict(phase="resnet50", model=type(net).__name__, batch=batch, hw=hw,
               dtype="bfloat16", layout="NHWC")
    row.update(run_steps(step, x, y, 5, {dev}))
    # the fused path is eligibility-gated with a silent eager fallback: an
    # eager run at a dozen launches a step must not pass for it
    row["fused"] = step.fused
    row["fallback_reason"] = step.fallback_reason
    check(step.fused and step.fallback_reason is None,
          "fused step did not engage: %s" % step.fallback_reason)
    check_trained(row, row["losses"], math.log(classes), before, snapshot(params, watch))
    row["mem_in_use"], row["mem_peak"] = mem(dev)
    return row


def build_bert(args):
    """BERT-base MLM as the benchmark builds it (``benchmark/models/bert.py``):
    token ids in, vocabulary scores out for every position."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Block, model_zoo

    if args.rehearse:
        batch, seq, vocab = 4, 32, 1000
        bert = model_zoo.bert.bert_3_64_2(use_classifier=False, dropout=0.0)
    else:
        batch, seq, vocab = 32, 128, 30522
        bert = model_zoo.bert.bert_12_768_12(use_classifier=False, dropout=0.0,
                                             max_length=seq)

    class MLMNet(Block):
        def __init__(self, bert_model):
            super().__init__(prefix="smoke_mlm_")
            with self.name_scope():
                self.bert = bert_model

        def forward(self, x):
            seq_out, _ = self.bert(x, nd.zeros_like(x))
            return self.bert.decode_mlm(seq_out)

    mx.random.seed(args.seed)
    net = MLMNet(bert)
    net.initialize()
    net.cast("bfloat16")
    return net, batch, seq, vocab


def phase_bert(args, dev):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel, tuning

    net, batch, seq, vocab = build_bert(args)
    rng = np.random.RandomState(args.seed)
    x = nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.float32))
    y = nd.array(rng.randint(0, vocab, (batch, seq)).astype(np.float32))
    net(x)  # resolve deferred shapes
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-4})
    params = net.collect_params()
    watch = [n for n in params if n.endswith("weight")]
    watch = [watch[0], watch[-1]]
    before = snapshot(params, watch)
    row = dict(phase="bert", batch=batch, seq=seq, vocab=vocab, dtype="bfloat16",
               mesh=dict(step.mesh.shape))
    row.update(run_steps(step, x, y, 5, set(step.mesh.devices.flat)))
    check_trained(row, row["losses"], math.log(vocab), before, snapshot(params, watch))
    # which attention the table chose, and whether the compiled step holds it
    heads, dim = (12, 64) if not args.rehearse else (2, 32)
    ent = tuning.resolve_attention((batch, heads, seq, dim), seq, "bfloat16", False)
    with Window() as w:
        text = step._compile(x, y).as_text()
    row["attention_entry"] = ent
    row["flash_custom_calls"] = text.count("tpu_custom_call")
    row["aot_compile_s"] = w.seconds
    row["aot_cache_hits"] = w.cache_hits
    if not args.rehearse:
        check(ent["backend"] == "pallas", "the table chose %s, not the kernel" % ent)
        check(row["flash_custom_calls"] >= 12,
              "the compiled BERT step holds %d flash custom calls, expected one a "
              "layer" % row["flash_custom_calls"])
    row["mem_in_use"], row["mem_peak"] = mem(dev)
    return row


# ---------------------------------------------------------------------------
# phase: serving
# ---------------------------------------------------------------------------
def phase_serving(args, dev):
    import jax

    # the oracle is token-for-token, so both sides compute their float32
    # matmuls at full precision (as the CPU tests do): a one-pass bf16 dot
    # in the reference against the kernel's f32 dot would test rounding
    with jax.default_matmul_precision("highest"):
        return _serving(args, dev)


def _serving(args, dev):
    import numpy as np

    from mxnet_tpu import tuning
    from mxnet_tpu.serving import (ContinuousBatcher, DecodeEngine, PagedKVCache,
                                   Request, TinyDecoder)

    if args.rehearse:
        layers, heads, hdim, vocab, max_len = 2, 12, 64, 512, 256
        slots, new, plens = 4, 4, [16, 40, 70]
    else:
        # GPT-2-small geometry: the widest the adapter takes (ROADMAP R1)
        layers, heads, hdim, vocab, max_len = 12, 12, 64, 50257, 1024
        slots, new, plens = 8, 32, [16, 30, 60, 90, 120, 150, 180, 200]
    S = 16
    model = TinyDecoder(vocab=vocab, num_layers=layers, num_heads=heads,
                        head_dim=hdim, max_len=max_len)
    params = model.init_params(args.seed)
    table_width = max_len // S
    cache = PagedKVCache(layers, heads, hdim, num_pages=slots * table_width,
                         page_size=S)
    eng = DecodeEngine(model, params=params, slots=slots, cache=cache,
                       prefill_buckets=(64, 256), max_context=max_len)
    picked = tuning.resolve_paged((slots, heads, hdim), S, eng.table_width, "float32")
    check(picked["backend"] == "pallas",
          "a %d-token context must pick the paged kernel, got %s" % (max_len, picked))
    sched = ContinuousBatcher(eng)
    rng = np.random.RandomState(args.seed)
    reqs = [sched.submit(Request(rng.randint(1, vocab, n).tolist(), max_new_tokens=new))
            for n in plens]
    with Window() as w:
        done = sched.run()
    row = dict(phase="serving", matmul_precision="highest", layers=layers, heads=heads, head_dim=hdim, vocab=vocab,
               slots=slots, prompts=plens, new_tokens=new, decode_steps=sched.steps,
               seconds=w.seconds, compiles=w.compiles, compile_s=w.compile_seconds,
               paged_entry=picked)
    check(len(done) == len(reqs), "%d of %d requests finished" % (len(done), len(reqs)))
    mismatches = 0
    with Window() as w:
        for r in reqs:
            check(r.state == "completed", "request %s ended %s" % (r.id, r.state))
            ref = model.reference_decode(params, r.prompt, r.max_new_tokens)
            mismatches += sum(a != b for a, b in zip(r.output_tokens, ref)) \
                + abs(len(ref) - len(r.output_tokens))
    row["reference_s"] = w.seconds
    row["token_mismatches"] = mismatches
    check(mismatches == 0, "%d tokens differ from reference_decode" % mismatches)
    if not args.rehearse:
        text = eng._jit_step.lower(eng.params, eng.cache.state(), eng._ctx,
                                   eng._tokens, eng._pt,
                                   eng._active_arr()).compile().as_text()
        row["paged_custom_calls"] = text.count("tpu_custom_call")
        check(row["paged_custom_calls"] >= layers,
              "the decode step holds %d paged-kernel custom calls"
              % row["paged_custom_calls"])
    row["mem_in_use"], row["mem_peak"] = mem(dev)
    return row


# ---------------------------------------------------------------------------
# phase: four chips (run by hand: --chips 4)
# ---------------------------------------------------------------------------
def phase_multichip(args, devs):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    n = args.chips
    per = 2 if args.rehearse else 64
    gbatch = per * n
    # the rehearsal is there to find a wrong mesh or sharding rule, and
    # float32 makes that check sharp; the chip runs the published bf16
    dtype = "float32" if args.rehearse else "bfloat16"
    row = dict(phase="multichip", chips=n, global_batch=gbatch, dtype=dtype)
    losses = {}
    for name, mesh_devs in (("dp%d" % n, devs[:n]), ("one", devs[:1])):
        net, classes, hw = build_resnet(args, dtype)  # same seed: same weights
        x, y = fixed_image_batch(args, gbatch, classes, hw, dtype)
        mesh = parallel.make_mesh((len(mesh_devs),), ("data",), devices=mesh_devs)
        step = parallel.ShardedTrainStep(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.025, "momentum": 0.9}, mesh=mesh)
        r = run_steps(step, x, y, 3, set(mesh_devs))
        losses[name] = r["losses"]
        row[name] = r
        if len(mesh_devs) > 1:
            # placement: every parameter on every device, the batch split
            for pname, p in net.collect_params().items():
                on = {s.device for s in p.data().data.addressable_shards}
                check(on == set(mesh_devs), "%s lives on %s" % (pname, on))
            xs = step._shard_batch(x)
            rows = sorted((s.device.id, s.data.shape[0]) for s in xs.addressable_shards)
            row["batch_rows_per_device"] = rows
            check(len(rows) == n and all(b == per for _, b in rows),
                  "batch rows per device: %s" % rows)
            text = step._compile(x, y).as_text()
            row["all_reduces"] = text.count("all-reduce(") + text.count("all-reduce-start(")
            check(row["all_reduces"] > 0, "no all-reduce in the data-parallel step")
        row["mem_%s" % name] = {d.id: mem(d) for d in devs[:n]}
        del step, net, x, y
        gc.collect()
    a, b = np.array(losses["dp%d" % n]), np.array(losses["one"])
    row["loss_abs_diff"] = np.abs(a - b).tolist()
    # the same program and weights, reductions in another order on four
    # chips: float32 agrees closely; in bf16 step 1 differs by rounding and
    # the updates then carry the difference forward
    tol = np.array([1e-3] * 3) if args.rehearse else np.array([0.01, 0.05, 0.05])
    check(np.all(np.abs(a - b) <= tol * np.abs(b)),
          "losses part: %s on %d chips, %s on one" % (a.tolist(), n, b.tolist()))
    return row


# ---------------------------------------------------------------------------
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip data-parallel path")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU, kernels interpreted; never ok")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset of the one-chip phases")
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1 and "xla_force_host_platform_device_count" \
                not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=%d" % args.chips).strip()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearse:
        print("chip_smoke: JAX found no TPU (%s); nothing was run" % (device,),
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print("chip_smoke: --chips %d needs %d devices, JAX has %d"
              % (args.chips, args.chips, len(devs)), file=sys.stderr)
        return 2

    from mxnet_tpu import config, native, tuning

    # kernel choices from the table and the cost model: the default, stated
    config.set_default("MXT_TUNE_MODE", "heuristic")
    cache_dir = tuning.setup_compile_cache(os.path.join(HERE, ".jax_cache"))
    emit(phase="start", device=device, seed=args.seed, rehearse=args.rehearse,
         jax=jax.__version__, compile_cache=cache_dir,
         compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         native_record_reader=native.status())

    if args.chips > 1:
        plan = [("multichip", functools.partial(phase_multichip, args, devs))]
    else:
        want = [p for p in args.phases.split(",") if p]
        plan = [(f.__name__[len("phase_"):], functools.partial(f, args, devs[0]))
                for f in (phase_sync, phase_kernels, phase_resnet50, phase_bert,
                          phase_serving)]
        plan = [(n, f) for n, f in plan if not want or n in want]
    failed = []
    t_all = time.perf_counter()
    for name, fn in plan:
        t0 = time.perf_counter()
        try:
            row = fn()
            row["ok"] = True
        except Exception as e:  # noqa: BLE001 — every phase reports, then the run fails
            traceback.print_exc()
            row = dict(phase=name, ok=False, error="%s: %s" % (type(e).__name__, e))
            failed.append(name)
        row["phase_s"] = time.perf_counter() - t0
        emit(**row)
        gc.collect()
    stats = tuning.compile_stats()
    emit(phase="end", seconds=time.perf_counter() - t_all, failed=failed,
         compiles=stats["compiles"], compile_seconds=stats["compile_seconds"],
         compile_cache_hits=stats["cache_hits"],
         compile_cache_misses=stats["cache_misses"])
    if failed or device["platform"] != "tpu" or (args.phases and args.chips == 1):
        # a partial or rehearsed run is never a pass
        emit(ok=False, failed=failed, device=device)
        return 1 if failed else 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

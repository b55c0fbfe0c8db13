"""Every Pallas kernel in mxnet_tpu/ops compiled for the chip, without the chip.

The TPU's compiler is installed here and compiles for a device that is
described, not attached (``v5e:2x2``, device kind ``TPU v5 lite``). It
refuses what interpret mode lets through — a block the tiling rules
forbid, a 64-bit index map, more fast memory than a kernel may use — so
these cases guard every later PR at no chip time. A compile that passes is
not a chip run: ``chip_smoke.py`` runs the same kernels against their
references on the device.

The file's name sorts early on purpose: the tier-1 window reaches it.
"""
import functools
import hashlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import tuning
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import causal_conv_pallas as CC
from mxnet_tpu.ops import delta_rule_pallas as DP
from mxnet_tpu.ops import embedding_grad as EG
from mxnet_tpu.ops import grouped_matmul as GM
from mxnet_tpu.ops import indexer as X
from mxnet_tpu.ops import row_gather as RG
from mxnet_tpu.ops import ssd_pallas as SP


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip; compilation cache
    off around the module (a described-device compile can be written to
    the persistent cache but never read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip("cannot describe a v5e topology here: %r" % (e,))
    assert topo.devices[0].device_kind == "TPU v5 lite"
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(chip, fn, *shapes):
    """Compile ``fn`` for the described chip at the matmul precision the
    chip runs by default (conftest pins 'highest' for the CPU numerics);
    returns the optimized HLO text. Raises what the chip's compiler
    would raise."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text()


def _flash_selected(backward, shape=(1, 32, 8192, 128), kv_heads=4, masked=True,
                    window=None):
    """Either kernel as the Keye cell runs it: grouped heads under a
    selection mask, causal, at the blocks the dispatch picks; ``masked=False``
    is the same call without the mask operand (the LFM2 cell's), ``window``
    the same with a static window (the SmallThinker cell's window layers)."""
    windowed = {} if window is None else {"window": window}

    def case(chip):
        B, H, T, D = shape
        dt, sm = jnp.dtype("bfloat16"), D ** -0.5
        q, kv = (shape, dt), ((B, kv_heads, T, D), dt)
        mask = (((B, T, T), jnp.int8),) if masked else ()
        if not backward:
            cfg = tuning.heuristic_attention(shape, T, "bfloat16", True)
            return _compile(
                chip, lambda q, k, v, *m: A._flash_forward_pallas(
                    q, k, v, None, True, sm, cfg["block_q"], cfg["block_k"], False,
                    mask=m[0] if m else None, **windowed), q, kv, kv, *mask)
        bq, bk = A._bwd_blocks(T, T)
        return _compile(
            chip, lambda q, k, v, out, lse, do, *m: A._flash_backward_pallas(
                q, k, v, None, out, lse, do, True, sm, bq, bk, False,
                mask=m[0] if m else None, **windowed),
            q, kv, kv, q, ((B, H, T), jnp.float32), q, *mask)
    return case


def _indexer(batch, t, heads=16, dim=64, topk=2048):
    def case(chip):
        dt = jnp.dtype("bfloat16")
        return _compile(
            chip, lambda q, k, w: X._select_pallas(q, k, w, topk, False),
            ((batch, heads, t, dim), dt), ((batch, t, dim), dt), ((batch, heads, t), dt))
    return case


def _flash(shape, dtype, causal, bias_shape=None, blocks=None, dv=None):
    """The forward kernel; ``dv`` is the values' width where it is not the
    keys' (latent attention)."""
    def case(chip):
        if blocks is None:
            cfg = tuning.heuristic_attention(shape, shape[2], dtype, causal)
            bq, bk = cfg["block_q"], cfg["block_k"]
        else:
            bq, bk = blocks
        sm = shape[3] ** -0.5
        qkv = [(shape, jnp.dtype(dtype))] * 2 + [
            (shape[:3] + (dv or shape[3],), jnp.dtype(dtype))]
        if bias_shape is None:
            return _compile(
                chip, lambda q, k, v: A._flash_forward_pallas(
                    q, k, v, None, causal, sm, bq, bk, False), *qkv)
        return _compile(
            chip, lambda q, k, v, b: A._flash_forward_pallas(
                q, k, v, b, causal, sm, bq, bk, False),
            *qkv, (bias_shape, jnp.float32))
    return case


def _flash_bwd(shape, dtype, causal, bias_shape=None, tk=None, blocks=None,
               dv=None):
    """The backward kernel at the blocks the dispatch picks for the shape."""
    def case(chip):
        B, H, Tq, D = shape
        Tk = tk or Tq
        bq, bk = blocks or A._bwd_blocks(Tq, Tk)
        sm = D ** -0.5
        dt = jnp.dtype(dtype)
        qs, ks = (shape, dt), ((B, H, Tk, D), dt)
        vs, os_ = ((B, H, Tk, dv or D), dt), ((B, H, Tq, dv or D), dt)
        args = [qs, ks, vs, os_, ((B, H, Tq), jnp.float32), os_]
        if bias_shape is not None:
            args.append((bias_shape, jnp.float32))
        return _compile(
            chip, lambda q, k, v, out, lse, do, b=None:
            A._flash_backward_pallas(q, k, v, b, out, lse, do, causal, sm,
                                     bq, bk, False), *args)
    return case


def _in_place(batch, tokens, heads, dim, backward, bias=False):
    """An in-place flash kernel (``flash_attention_qkv``'s) at the cut of a
    grid step the rule gives the shape."""
    def case(chip):
        dt, width = jnp.dtype("bfloat16"), heads * dim
        qkv, out = ((batch, tokens, 3 * width), dt), ((batch, tokens, width), dt)
        b = (((batch, 1, 1, tokens), jnp.float32),) if bias else ()
        if not backward:
            return _compile(chip, lambda x, *b: A._qkv_forward_pallas(
                x, b[0] if b else None, heads, dim ** -0.5, False), qkv, *b)
        return _compile(
            chip, lambda x, o, l, do, *b: A._qkv_backward_pallas(
                x, b[0] if b else None, o, l, do, heads, dim ** -0.5, False),
            qkv, out, ((batch * heads, 1, tokens), jnp.float32), out, *b)
    return case


def _paged(batch, heads, dim, block_h=None, dtype="bfloat16", page=16,
           max_pages=64):
    def case(chip):
        bh = block_h or tuning.heuristic_paged(
            (batch, heads, dim), page, max_pages, dtype)["block_h"]
        dt = jnp.dtype(dtype)
        pool = ((batch * max_pages + 1, page, heads, dim), dt)
        return _compile(
            chip, lambda q, k, v, pt, cl: A._paged_decode_pallas(
                q, k, v, pt, cl, dim ** -0.5, bh, False),
            ((batch, heads, dim), dt), pool, pool,
            ((batch, max_pages), jnp.int32), ((batch,), jnp.int32))
    return case


def _grouped(product, rows, k, n, dtype="bfloat16"):
    """One of the expert layer's three grouped products, 16 groups, at the
    tiles the module gives every shape: the forward (rows, k) x (16, k, n),
    its input gradient, its weight gradient."""
    def case(chip):
        dt = jnp.dtype(dtype)
        return _compile(chip, functools.partial(GM._kernel, product),
                        ((rows, k), dt), ((16, k, n), dt), ((rows, n), dt),
                        ((16,), jnp.int32))
    return case


def _row_sum(rows, k, hidden, dtype="bfloat16"):
    """The expert layer's sum of ``rows`` rows laid out back to 8192 tokens of
    k slots, the rows that exist read on the device: both kernels."""
    def case(chip):
        return _compile(chip, functools.partial(RG.sum_rows, tokens=8192, k=k),
                        ((rows, hidden), jnp.dtype(dtype)), ((rows,), jnp.int32),
                        ((), jnp.int32))
    return case


def _embedding_grad(vocab, width, tokens, dtype="bfloat16"):
    """The gradient of a (vocab, width) embedding table looked up by ``tokens``
    ids: the sort, the gather of the sorted rows, the walk over the table's
    128-row tiles and ``grouped_matmul_dw`` over them, cut to ``vocab`` rows."""
    def case(chip):
        return _compile(chip, functools.partial(EG.table_grad, vocab=vocab),
                        ((tokens,), jnp.int32), ((tokens, width), jnp.dtype(dtype)))
    return case


_CASES = {
    # flash forward: BERT-base (batch 32 x 128) without and with a
    # padding bias, longer and ragged sequences, explicit big blocks, and
    # the longest sequence whose whole K/V _kv_fits_vmem still admits
    "flash_bert": _flash((32, 12, 128, 64), "bfloat16", False),
    "flash_bert_bias": _flash((32, 12, 128, 64), "bfloat16", False,
                              bias_shape=(32, 1, 1, 128)),
    "flash_512_causal": _flash((8, 12, 512, 64), "bfloat16", True),
    "flash_384": _flash((2, 4, 384, 64), "bfloat16", False),
    "flash_2048_causal_256x512": _flash((4, 12, 2048, 64), "bfloat16", True,
                                        blocks=(256, 512)),
    "flash_maxseq_16384_bias": _flash((1, 2, 16384, 64), "bfloat16", True,
                                      bias_shape=(1, 1, 1, 16384)),
    # the benchmark's two BERT cells, (384, 512, 64) and (1536, 128, 64) a
    # call, at the tiles the table gives them (the Kanana cell's
    # (64, 4096, 192 / 128) is flash_mla_4096_k192_v128 below), and a length
    # that pads Q and K/V under a bias: the diagonal and the tail block
    "flash_bert_cell_s512": _flash((32, 12, 512, 64), "bfloat16", False),
    "flash_bert_cell_s128": _flash((128, 12, 128, 64), "bfloat16", False),
    "flash_300_ragged_bias": _flash((2, 4, 300, 64), "bfloat16", True,
                                    bias_shape=(2, 1, 1, 300),
                                    blocks=(128, 128)),
    # the deferred-shape forward of the BERT cells, batch 1: 12 grid steps,
    # one head a step, and the 128-token cell's backward (16 heads a step)
    "flash_bert_eager_s128": _flash((1, 12, 128, 64), "bfloat16", False),
    "flash_bwd_bert_cell_s128": _flash_bwd((128, 12, 128, 64), "bfloat16", False),
    # flash backward: the benchmark's BERT cell (32 x 512), causal, a
    # padding bias (dbias comes out of the kernel), a longer causal
    # sequence, a ragged one, and the corner of what _flash_bwd sends it:
    # the longest Q _qdo_fits_vmem admits against the longest K/V
    # _kv_fits_vmem admits, in bf16 and in float32
    "flash_bwd_bert_s512": _flash_bwd((32, 12, 512, 64), "bfloat16", False),
    "flash_bwd_512_causal": _flash_bwd((8, 12, 512, 64), "bfloat16", True),
    "flash_bwd_bert_bias": _flash_bwd((32, 12, 128, 64), "bfloat16", False,
                                      bias_shape=(32, 1, 1, 128)),
    "flash_bwd_2048_causal": _flash_bwd((4, 12, 2048, 64), "bfloat16", True),
    "flash_bwd_300_ragged_bias": _flash_bwd((2, 4, 300, 64), "bfloat16",
                                            True, bias_shape=(2, 4, 1, 300)),
    "flash_bwd_maxq_4096_maxkv_16384_bias": _flash_bwd(
        (1, 2, 4096, 64), "bfloat16", True, bias_shape=(1, 1, 1, 16384),
        tk=16384),
    "flash_bwd_f32_maxq_2048_maxkv_8192": _flash_bwd(
        (1, 2, 2048, 64), "float32", True, tk=8192),
    # latent attention as the Kanana cell runs it: 2 x 32 heads of 4096
    # rows, keys 192 and values 128 wide, causal; the backward holds 2.5 MB
    # of Q + dO a head and asks for its own scoped VMEM (_bwd_vmem_limit)
    "flash_mla_4096_k192_v128": _flash((2, 32, 4096, 192), "bfloat16", True,
                                       dv=128),
    "flash_bwd_mla_4096_k192_v128": _flash_bwd((2, 32, 4096, 192), "bfloat16",
                                               True, dv=128),
    # and the corner of the gate since it admits 4 MB of Q + dO
    "flash_bwd_maxq_16384_maxkv_16384": _flash_bwd(
        (1, 2, 16384, 64), "bfloat16", True, tk=16384),
    "flash_bwd_f32_maxq_8192_maxkv_8192": _flash_bwd(
        (1, 2, 8192, 64), "float32", True, tk=8192),
    # the Keye cell: one sequence of 8192, 32 query heads on 4 K/V heads of
    # 128, under the indexer's selection mask (both kernels ask for their
    # own scoped VMEM: 30.5 and 41.9 MB), the same heads dense, and the
    # indexer's kernel (16 heads of 64, top-2048) there and at a ragged
    # length and a batch
    "flash_selected_8192_gqa": _flash_selected(False),
    "flash_bwd_selected_8192_gqa": _flash_selected(True),
    "flash_bwd_selected_2x3000_gqa": _flash_selected(True, (2, 8, 3000, 128), 2),
    # the LFM2 cell: 32 query heads on 8 K/V heads of 64 at 8192 rows, no mask
    # operand (a 64-wide head fills half the lanes: VMEM holds it at 128)
    "flash_lfm2_8192_gqa_d64": _flash_selected(False, (1, 32, 8192, 64), 8, False),
    "flash_bwd_lfm2_8192_gqa_d64": _flash_selected(True, (1, 32, 8192, 64), 8, False),
    # the SmallThinker cell: 28 query heads on 4 K/V heads of 128 at 8192 rows
    # (7 a group: no power of two), the full layer's kernels and the window
    # layers' under their own names, a 4096-token window as a static argument
    "flash_smallthinker_8192_gqa7": _flash_selected(False, (1, 28, 8192, 128), 4, False),
    "flash_bwd_smallthinker_8192_gqa7": _flash_selected(True, (1, 28, 8192, 128), 4,
                                                        False),
    "window_smallthinker_8192_gqa7_w4096": _flash_selected(
        False, (1, 28, 8192, 128), 4, False, window=4096),
    "window_bwd_smallthinker_8192_gqa7_w4096": _flash_selected(
        True, (1, 28, 8192, 128), 4, False, window=4096),
    # a window no multiple of the blocks, on a ragged length and a batch
    "window_bwd_2x3000_gqa_w1000": _flash_selected(True, (2, 8, 3000, 128), 2, False,
                                                   window=1000),
    "window_2x3000_gqa_w1000": _flash_selected(False, (2, 8, 3000, 128), 2, False,
                                               window=1000),
    # the BERT cells through flash_attention_qkv: the fused projection read
    # in place, whole rows of (128, 128, 2304) two batch elements a grid step
    # and of (32, 512, 2304) one (the backward asks for 24 MB of scoped VMEM),
    # a padding bias, a length between them, a 128-wide head
    "flash_in_place_s128": _in_place(128, 128, 12, 64, False),
    "flash_bwd_in_place_s128": _in_place(128, 128, 12, 64, True),
    "flash_in_place_s512": _in_place(32, 512, 12, 64, False),
    "flash_bwd_in_place_s512": _in_place(32, 512, 12, 64, True),
    "flash_in_place_s128_bias": _in_place(128, 128, 12, 64, False, bias=True),
    "flash_bwd_in_place_s512_bias": _in_place(32, 512, 12, 64, True, bias=True),
    "flash_bwd_in_place_s256": _in_place(64, 256, 12, 64, True),
    "flash_bwd_in_place_d128": _in_place(64, 128, 6, 128, True, bias=True),
    # the expert layer's grouped matmuls at the three expert cells' shapes:
    # 12288 rows laid out at width 768 (Kanana), 16384 at 768 (Keye) and at
    # 1536 (LFM2), gate / up and down, each product; a weight block whole in
    # VMEM, the weight gradient's float32 accumulator beside its blocks
    **{"grouped_%s_%dx%dx%d" % (p, r, k, n): _grouped(p, r, k, n)
       for p in ("fwd", "dx", "dw")
       for r, k, n in ((12288, 2048, 768), (12288, 768, 2048),
                       (16384, 2048, 1536), (16384, 1536, 2048))},
    "grouped_fwd_16384x2048x768": _grouped("fwd", 16384, 2048, 768),
    "grouped_dw_f32_1024x2048x1536": _grouped("dw", 1024, 2048, 1536, "float32"),
    # the expert layer's sum of rows at the four expert cells' shapes (rows
    # laid out, slots a token, hidden): a row is a DMA's unit only as a tile
    # of its own, so bfloat16 rows as (H / 256, 128) words
    "row_sum_smallthinker": _row_sum(24576, 6, 2560),
    "row_sum_keye": _row_sum(16384, 8, 2048),
    "row_sum_kanana": _row_sum(12288, 6, 2048),
    "row_sum_lfm2": _row_sum(16384, 4, 2048),
    "row_sum_f32_12288x6x2048": _row_sum(12288, 6, 2048, "float32"),
    # the embedding's gradient at the tables tools/embedding_grad_table.py times
    # (the language-model cells' and SmallThinker's published vocabulary; the
    # op's rule takes the two over 128 MiB): 1 to 1187 groups of one walk
    "embedding_grad_16032x2048_8192": _embedding_grad(16032, 2048, 8192),
    "embedding_grad_16384x2048_8192": _embedding_grad(16384, 2048, 8192),
    "embedding_grad_18992x2048_8192": _embedding_grad(18992, 2048, 8192),
    "embedding_grad_25088x2048_8192": _embedding_grad(25088, 2048, 8192),
    "embedding_grad_30522x768_16384": _embedding_grad(30522, 768, 16384),
    "embedding_grad_2x768_16384": _embedding_grad(2, 768, 16384),
    "embedding_grad_37984x2560_8192": _embedding_grad(37984, 2560, 8192),
    "embedding_grad_151936x2560_8192": _embedding_grad(151936, 2560, 8192),
    "indexer_select_8192": _indexer(1, 8192),
    "indexer_select_2x3000_top512": _indexer(2, 3000, topk=512),
    # paged decode at the BERT-base/GPT-2 geometry, block as the
    # repaired generator picks it
    "paged_8x12x64": _paged(8, 12, 64),
    "paged_16x12x64": _paged(16, 12, 64),
}


# the forward kernel's lse in the compiled program, (B*H, padded Tq): a row
# of 4 bytes a query, and no (B*H, Tq, 128) lane-broadcast copy of it
_LSE_ROWS = {"flash_bert_cell_s512": (384, 512),
             "flash_bert_cell_s128": (1536, 128),
             "flash_mla_4096_k192_v128": (64, 4096),
             "flash_300_ragged_bias": (8, 384)}


# heads a grid step that A._heads_per_step gives the benchmark's BERT cells
# (both kernels at 128 tokens, forward and backward at 512): these cases
# compile the grouped kernels, inside the scoped VMEM the compiler gives
# unasked (they pass no limit: tests/test_attention_bert.py reads that from
# their jaxprs; one that held more would be refused here)
_HEADS = {"flash_bert_cell_s128": ("fwd", "16"), "flash_bwd_bert_cell_s128": ("bwd", "16"),
          "flash_bert_cell_s512": ("fwd", "4"), "flash_bwd_bert_s512": ("bwd", "2"),
          "flash_bert_bias": ("fwd", "16"), "flash_bwd_bert_bias": ("bwd", "16"),
          # the deferred-shape forward at batch 1 and the latent cell: one head
          "flash_bert_eager_s128": ("fwd", "1"), "flash_mla_4096_k192_v128": ("fwd", "1"),
          "flash_bwd_mla_4096_k192_v128": ("bwd", "1"),
          "flash_lfm2_8192_gqa_d64": ("fwd", "1"),
          "flash_bwd_lfm2_8192_gqa_d64": ("bwd", "1"),
          # in place a step holds whole batch elements: 2 x 12 heads, 1 x 12
          "flash_in_place_s128": ("fwd", "24"), "flash_bwd_in_place_s128": ("bwd", "24"),
          "flash_in_place_s512": ("fwd", "12"), "flash_bwd_in_place_s512": ("bwd", "12")}


# the name each window case's kernel goes by in the compiled program (the
# roofline readers divide a name's device time by its calls), and the full
# layer's beside them
_KERNEL_NAMES = {"window_smallthinker_8192_gqa7_w4096": "window_attention_fwd",
                 "window_bwd_smallthinker_8192_gqa7_w4096": "window_attention_bwd",
                 "window_2x3000_gqa_w1000": "window_attention_fwd",
                 "window_bwd_2x3000_gqa_w1000": "window_attention_bwd",
                 "flash_smallthinker_8192_gqa7": "flash_attention_fwd",
                 "flash_bwd_smallthinker_8192_gqa7": "flash_attention_bwd"}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(chip, name):
    from mxnet_tpu import telemetry

    before = telemetry.flash_heads_per_step()
    text = _CASES[name](chip)
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    if name in _KERNEL_NAMES:
        names = set(re.findall(r"(?:window|flash)_attention_(?:fwd|bwd)", text))
        assert names == {_KERNEL_NAMES[name]}
    if name in _LSE_ROWS:
        assert "f32[%d,1,%d]" % _LSE_ROWS[name] in text
        assert "f32[%d,%d,128]" % _LSE_ROWS[name] not in text
    if name in _HEADS:
        kernel, heads = _HEADS[name]
        after = telemetry.flash_heads_per_step()[kernel]
        assert after[heads] == before.get(kernel, {}).get(heads, 0) + 1


def test_gated_short_conv_compiles_to_fusions_without_a_convolution(chip):
    """``gated_short_conv`` with its hand-written backward at a conv layer of
    the LFM2 cell (1 x 8192 tokens, 2048 channels, 3 taps): shifted
    multiply-adds that XLA fuses, no convolution program and no kernel."""
    from mxnet_tpu.ops.gated_conv import gated_short_conv

    dt = jnp.dtype("bfloat16")
    text = _compile(
        chip, jax.grad(lambda bcx, w, g: jnp.sum(
            gated_short_conv(bcx, w).astype(jnp.float32) * g), argnums=(0, 1)),
        ((1, 8192, 6144), dt), ((2048, 3), dt), ((1, 8192, 2048), jnp.float32))
    assert " convolution(" not in text and "tpu_custom_call" not in text
    assert "bf16[1,8192,6144]" in text  # the gradient of bcx, written once


def test_flash_bwd_corner_is_what_the_dispatch_admits():
    """The corner cases above sit on _flash_bwd's gates: one more tile of
    Q, or of K/V, and the call stays in XLA."""
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")

    def fits(t, dt, gate):
        return gate(jax.ShapeDtypeStruct((1, 2, t, 64), dt))

    assert fits(16384, bf16, A._qdo_fits_vmem)
    assert not fits(16384 + 128, bf16, A._qdo_fits_vmem)
    assert fits(16384, bf16, A._kv_fits_vmem)
    assert not fits(16384 + 128, bf16, A._kv_fits_vmem)
    assert fits(8192, f32, A._qdo_fits_vmem)
    assert not fits(8192 + 128, f32, A._qdo_fits_vmem)
    assert fits(8192, f32, A._kv_fits_vmem)
    assert not fits(8192 + 128, f32, A._kv_fits_vmem)


def test_every_paged_candidate_compiles(chip):
    """paged_candidates and the kernel must agree: whatever the generator
    calls legal, the chip's compiler takes (12 heads used to get only
    blocks it refuses)."""
    for heads in (2, 4, 12, 16, 32):
        cands = tuning.paged_candidates(heads, 64, 16, "bfloat16")
        assert cands and all(heads % bh == 0 for bh in cands)
        for bh in cands:
            _paged(8, heads, 64, block_h=bh, max_pages=16)(chip)


@pytest.mark.parametrize("shape,causal,dv", [
    ((32, 12, 512, 64), False, None), ((128, 12, 128, 64), False, None),
    ((2, 32, 4096, 192), True, 128), ((2, 4, 300, 64), True, None)])
def test_every_attention_candidate_compiles(chip, shape, causal, dv):
    """Whatever attention_candidates calls legal the cost model may pick:
    at the three cells' shapes and a ragged one, the chip's compiler takes
    every candidate."""
    cands = tuning.attention_candidates(shape[2], shape[2], shape[3],
                                        "bfloat16")
    assert len(cands) >= 3
    for blocks in cands:
        _flash(shape, "bfloat16", causal, blocks=blocks, dv=dv)(chip)


def test_extreme_attention_candidates_compile(chip):
    """The corners of what attention_candidates emits under _VMEM_BUDGET at
    the longest resident sequence: smallest and largest tiles."""
    shape = (1, 2, 16384, 64)
    cands = tuning.attention_candidates(shape[2], shape[2], shape[3],
                                        "bfloat16")
    for blocks in (min(cands), max(cands), (min(cands)[0], max(cands)[1]),
                   (max(cands)[0], min(cands)[1])):
        assert blocks in cands
        _flash(shape, "bfloat16", True, blocks=blocks)(chip)


# -- a call without ``window`` is the call it was ----------------------------------
def _heads_major_calls(shape, kv_heads, dv=None, masked=False, window=None,
                       sm_scale=None):
    """(forward, backward) jaxprs of ``flash_attention``'s two kernels at a
    decoder cell's shapes and the blocks the dispatch picks, causal, under a
    static ``window`` where the cell's layer has one."""
    B, H, T, D = shape
    dt, sm = jnp.dtype("bfloat16"), sm_scale or D ** -0.5
    windowed = {} if window is None else {"window": window}
    S = jax.ShapeDtypeStruct
    q, k, v = S(shape, dt), S((B, kv_heads, T, D), dt), S((B, kv_heads, T, dv or D), dt)
    o = S((B, H, T, dv or D), dt)
    mask = (S((B, T, T), jnp.int8),) if masked else ()
    cfg = tuning.heuristic_attention(shape, T, "bfloat16", True)
    fwd = jax.make_jaxpr(lambda q, k, v, *m: A._flash_forward_pallas(
        q, k, v, None, True, sm, cfg["block_q"], cfg["block_k"], False,
        mask=m[0] if m else None, **windowed))(q, k, v, *mask)
    bq, bk = A._bwd_blocks(T, T)
    bwd = jax.make_jaxpr(lambda q, k, v, out, lse, do, *m: A._flash_backward_pallas(
        q, k, v, None, out, lse, do, True, sm, bq, bk, False,
        mask=m[0] if m else None, **windowed))(
            q, k, v, o, S((B, H, T), jnp.float32), o, *mask)
    return fwd, bwd


def _in_place_calls(batch, tokens, heads, dim):
    """(forward, backward) jaxprs of ``flash_attention_qkv``'s two kernels at
    a BERT cell's shapes."""
    dt, width, S = jnp.dtype("bfloat16"), heads * dim, jax.ShapeDtypeStruct
    qkv, out = S((batch, tokens, 3 * width), dt), S((batch, tokens, width), dt)
    fwd = jax.make_jaxpr(lambda x: A._qkv_forward_pallas(
        x, None, heads, dim ** -0.5, False))(qkv)
    bwd = jax.make_jaxpr(lambda x, o, l, do: A._qkv_backward_pallas(
        x, None, o, l, do, heads, dim ** -0.5, False))(
            qkv, out, S((batch * heads, 1, tokens), jnp.float32), out)
    return fwd, bwd


_OLDER_CELLS = {
    "bert_base_train_s512": lambda: _in_place_calls(32, 512, 12, 64),
    "bert_base_train_s128": lambda: _in_place_calls(128, 128, 12, 64),
    "kanana2_a3b_train_s4096": lambda: _heads_major_calls((2, 32, 4096, 192), 32, dv=128),
    "keye_vl2_a3b_train_s8192": lambda: _heads_major_calls((1, 32, 8192, 128), 4,
                                                           masked=True),
    "lfm2_a2b_train_s8192": lambda: _heads_major_calls((1, 32, 8192, 64), 8),
    # SmallThinker's full layer and its window layers (28 heads on 4), and
    # Granite's one attention layer: LFM2's shapes at the file's own score scale
    "smallthinker_a3b_train_s8192": lambda: _heads_major_calls((1, 28, 8192, 128), 4),
    "smallthinker_a3b_train_s8192_w4096": lambda: _heads_major_calls(
        (1, 28, 8192, 128), 4, window=4096),
    "granite4_h_micro_train_s8192": lambda: _heads_major_calls(
        (1, 32, 8192, 64), 8, sm_scale=0.015625),
}

# sha256 (16 hex digits) of the traced call, kernel body, grid, blocks, names
# and compiler parameters, as the tree BEFORE the window was built (PR 40's,
# e2462b4) traces it; the SmallThinker and Granite cells' as PR 45's tree
# (08bf814) does. A PR that means to change one of these kernels changes
# its line here, and says so; one that does not, cannot.
_OLDER_CELLS_DIGESTS = {
    ("bert_base_train_s128", "fwd"): "c6312a91c046b45d",
    ("bert_base_train_s128", "bwd"): "0800496a05a8d2f5",
    ("bert_base_train_s512", "fwd"): "bd12583bbea5aea3",
    ("bert_base_train_s512", "bwd"): "9d6747e86d107358",
    ("kanana2_a3b_train_s4096", "fwd"): "1b7cbbe3b89410df",
    ("kanana2_a3b_train_s4096", "bwd"): "684e127c486a639f",
    ("keye_vl2_a3b_train_s8192", "fwd"): "6fac6b2ecfdb5b58",
    ("keye_vl2_a3b_train_s8192", "bwd"): "134eae970437245c",
    ("lfm2_a2b_train_s8192", "fwd"): "83ad77ac89dac598",
    ("lfm2_a2b_train_s8192", "bwd"): "4c8e31996160df79",
    ("smallthinker_a3b_train_s8192", "fwd"): "05af1c83b22b7139",
    ("smallthinker_a3b_train_s8192", "bwd"): "ae3b854965ac224a",
    ("smallthinker_a3b_train_s8192_w4096", "fwd"): "8cd051788ec9c8fa",
    ("smallthinker_a3b_train_s8192_w4096", "bwd"): "dd4e994c317cd9eb",
    ("granite4_h_micro_train_s8192", "fwd"): "e1e75faff236e998",
    ("granite4_h_micro_train_s8192", "bwd"): "bd1205ee3fd7785b",
}


def _digest(jaxpr):
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr)).encode()
                          ).hexdigest()[:16]


@pytest.mark.parametrize("half", ["fwd", "bwd"])
@pytest.mark.parametrize("cell", sorted(_OLDER_CELLS))
def test_a_cells_attention_lowers_to_the_kernels_it_was(cell, half):
    """The attention calls of the seven cells that have any (both BERT cells
    in place, Kanana's latent heads, Keye's under a selection mask, LFM2's
    64-wide grouped heads, SmallThinker's full and window layers, Granite's at
    its own score scale) trace to the programs they traced to, letter for
    letter; only a call with a window goes by the window kernels' names."""
    jaxpr = _OLDER_CELLS[cell]()[half == "bwd"]
    assert ("window_attention" in str(jaxpr)) == cell.endswith("_w4096")
    assert _digest(jaxpr) == _OLDER_CELLS_DIGESTS[(cell, half)]


# -- the Mamba-2 mixer's ops and the Granite cell's whole step -------------------------
_SSD_SHAPES = (((1, 8192, 64, 64), "x"), ((1, 8192, 64), "dt"), ((64,), "A_log"),
               ((1, 8192, 1, 128), "B"), ((1, 8192, 1, 128), "C"), ((64,), "D"),
               ((64,), "dt_bias"))


def _entry_results(text):
    """(type, dims) of every result of the optimized module's ENTRY
    computation: what is written to the chip's memory, fusions' insides not."""
    entry = text[text.index("\nENTRY "):]
    out = []
    for line in entry.split("\n")[1:]:
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) [a-z\-]+\(", line[:2000])
        if m:
            out += [(t, tuple(int(d) for d in dims.split(",") if d))
                    for t, dims in re.findall(r"([a-z]+[0-9]+)\[([0-9,]*)\]", m.group(1))]
    return out


def _copies(text, elements):
    """(type, dims) of every copy or transpose of ``elements`` elements or more
    that the optimized module's ENTRY computation writes to memory, a fusion
    whose root is one among them."""
    roots = dict(re.findall(r"\n%?([\w.\-]+) \([^\n]*\{\n(?:[^}][^\n]*\n)*?\s+ROOT [^\n]*? = \S+ "
                            r"([a-z\-]+)\(", text))
    out = []
    for line in text[text.index("\nENTRY "):].split("\n")[1:]:
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = ([a-z]+[0-9]+)\[([0-9,]*)\]\S* ([a-z\-]+)\(",
                     line[:2000])
        if not m:
            continue
        op = m.group(3)
        if op == "fusion":
            op = roots.get(re.search(r"calls=%?([\w.\-]+)", line).group(1))
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if op in ("copy", "transpose") and functools.reduce(lambda a, b: a * b, dims, 1) >= elements:
            out.append((m.group(1), dims))
    return out


@pytest.mark.parametrize("dispatch", ["chip", "off_chip"])
@pytest.mark.parametrize("half", ["fwd", "bwd"])
def test_ssd_scan_compiles_and_no_float32_mask_leaves_its_fusion(chip, monkeypatch, half,
                                                                 dispatch):
    """``ssd_scan`` at a Mamba layer of the Granite cell (1 x 8192 tokens, 64
    heads of 64, one group, state 128, chunks of 256), forward alone and with
    its hand-written backward. Off the chip: XLA's products and fusions, no
    kernel, and no float32 tensor of (chunks x heads x 256 x 256) or more among
    the results written to memory (0.54 GB each; autodiff through the formula
    keeps one a layer). Dispatched as on the chip: the two kernels of
    ``ops/ssd_pallas.py`` by their names under the op's two scopes, and no
    tensor of that many elements of ANY type written to memory: scores, mask
    and masked scores stay in VMEM."""
    from mxnet_tpu.ops.ssd import ssd_scan

    monkeypatch.setattr(SP, "on_tpu", lambda: dispatch == "chip")
    dt = jnp.dtype("bfloat16")
    shapes = tuple((s, dt) for s, _ in _SSD_SHAPES)
    if half == "fwd":
        text = _compile(chip, lambda *a: ssd_scan(*a, chunk=256), *shapes)
    else:
        text = _compile(
            chip, jax.grad(lambda g, *a: jnp.sum(
                ssd_scan(*a, chunk=256).astype(jnp.float32) * g), argnums=tuple(range(1, 8))),
            ((1, 8192, 64, 64), jnp.float32), *shapes)
    mask = 32 * 64 * 256 * 256
    sizes = [(t, d) for t, d in _entry_results(text)
             if functools.reduce(lambda a, b: a * b, d, 1) >= mask]
    if dispatch == "off_chip":
        assert "tpu_custom_call" not in text
        large = [(t, d) for t, d in sizes if t == "f32"]
        assert not large, large
        assert "bf16[1,8192,64,64]" in text  # y, or the gradient of x, written once
        return
    assert not sizes, sizes
    # the backward's program runs the forward too: its opening states are the residual
    assert text.count("tpu_custom_call") == (1 if half == "fwd" else 2)
    assert "ssd%s/jit(_fwd_pallas)/ssd_chunk_fwd/" % ("" if half == "fwd" else ")") in text  # the scope
    assert "f32[1,32,4096,128]" in text  # a (P, N) float32 state a head a chunk
    if half == "bwd":
        assert "(ssd_bwd))/jit(_bwd_pallas)/ssd_chunk_bwd/" in text
    assert re.search(r"bf16\[1,(8192,64,64|4096,8192)\]", text)  # y or dx, written once


@pytest.mark.parametrize("dispatch", ["chip", "off_chip"])
def test_causal_conv_silu_compiles_to_its_kernels_on_the_chip_and_to_fusions_off_it(
        chip, monkeypatch, dispatch):
    """``causal_conv_silu`` with its hand-written backward at a Mamba layer of
    the Granite cell (1 x 8192 tokens, columns 4096 to 8448 of ``in_proj``'s
    8512, 4 taps and a bias). Dispatched as on the chip: the two kernels of
    ``ops/causal_conv_pallas.py`` by their names, under the op's two scopes,
    reading the columns where they lie (no slice of them is written), and no
    float32 copy of the operand among what the program writes to memory. Off
    it: shifted multiply-adds that XLA fuses, no kernel. Either way no
    convolution program."""
    from mxnet_tpu.ops.gated_conv import causal_conv_silu

    monkeypatch.setattr(CC, "on_tpu", lambda: dispatch == "chip")
    dt = jnp.dtype("bfloat16")
    op = functools.partial(causal_conv_silu, columns=(4096, 8448))
    shapes = (((1, 8192, 8512), dt), ((4352, 4), dt), ((4352,), dt))
    text = _compile(
        chip, jax.grad(lambda x, w, b, g: jnp.sum(op(x, w, b).astype(jnp.float32) * g),
                       argnums=(0, 1, 2)), *shapes, ((1, 8192, 4352), jnp.float32))
    assert " convolution(" not in text
    assert "bf16[1,8192,8512]" in text  # the gradient of data, zeros around its columns
    copies = sum(_entry_results(text).count(("f32", shape))  # either way round, less g itself
                 for shape in ((1, 8192, 4352), (1, 4352, 8192))) - 1
    if dispatch == "off_chip":
        assert "tpu_custom_call" not in text and copies >= 2  # shifted operands
        return
    # the forward's result feeds nothing here: the gradient needs data alone
    assert text.count("tpu_custom_call") == 1 and "causal_conv_silu_bwd" in text
    assert "causal_conv_bwd))/jit(_bwd_pallas)/causal_conv_silu_bwd/" in text  # the scope
    assert copies == 0
    both = _compile(chip, lambda x, w, b: jax.vjp(op, x, w, b)[0], *shapes)
    assert both.count("tpu_custom_call") == 1 and "causal_conv_silu_fwd" in both
    assert "(causal_conv)/jit(_fwd_pallas)/causal_conv_silu_fwd/" in both
    assert "f32[1,8192,4352]" not in both and "f32[1,4352,8192]" not in both
    for program in (text, both):  # the columns are read in place: no slice is written
        assert not re.search(r"= bf16\[1,(8192,4352|4352,8192)\]\S* slice(-done)?\(", program)


@pytest.mark.parametrize("dispatch", ["chip", "off_chip"])
def test_the_embeddings_gradient_is_the_grouped_product_on_the_chip_and_xlas_off_it(
        chip, monkeypatch, dispatch):
    """``jax.grad`` through the ``Embedding`` op at the SmallThinker cell's
    table, (37984, 2560) bfloat16 looked up by 1 x 8192 ids. Dispatched as on
    the chip: no scatter in the compiled program, one sort, the one kernel
    (``grouped_matmul_dw``) under the scope ``embedding_bwd``, and the table's
    gradient among the results. Off it: XLA's sorted scatter-add, no kernel."""
    from mxnet_tpu.ops.indexing import embedding

    monkeypatch.setattr(EG, "on_tpu", lambda: dispatch == "chip")
    dt = jnp.dtype("bfloat16")
    text = _compile(
        chip, jax.grad(lambda w, ids, g: jnp.sum(embedding(ids, w).astype(jnp.float32) * g)),
        ((37984, 2560), dt), ((1, 8192), jnp.float32), ((1, 8192, 2560), jnp.float32))
    assert "bf16[37984,2560]" in text
    if dispatch == "off_chip":
        assert "tpu_custom_call" not in text and " scatter(" in text
        return
    assert not re.search(r"\bscatter\b", text) and len(re.findall(r" sort\(", text)) == 1
    assert text.count("tpu_custom_call") == 1 and "grouped_matmul_dw" in text
    assert "(embedding_bwd))/jit(_tgmm_pallas)/grouped_matmul_dw" in text  # the scope


def test_the_granite_cells_whole_step_fits_with_the_recomputation_its_file_names(
        chip, monkeypatch):
    """``granite4_h_micro_train_s8192``'s step, 797,850,560 parameters under
    Adam at (1, 8192) through ``ShardedTrainStep``, compiled for the described
    chip as the cell builds it (both flash kernels, the filter's two and the
    scan's two in, ``remat`` from the configuration's file): 11.11 GB live,
    6.32 GB of it temporaries (11.21 and 6.42 with XLA's scan, 11.40 and 6.61
    with XLA's filter too), under the 14 GB that leave room for the seeded copy
    (1.60 GB) and the batch pool, and not one copy of a mixer-sized array.
    Without recomputation it compiled to 16.51 GB (PERF.md section 4)."""
    import json

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import granite_hybrid as zoo

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", "granite4_h_micro_pp4.json")) as f:
        config = json.load(f)
    remat = config["assumed"]["recomputation"]["remat"]
    net = zoo.GraniteHybridModel(config)
    net.initialize(mx.init.Zero())  # shapes are what is compiled, not values
    net.cast(config["dtype"])
    opt = {k: v for k, v in config["optimizer"].items() if k != "name"}
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), config["optimizer"]["name"], opt,
        mesh=parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1]), remat=remat)
    one = Mesh([chip._device], ("data",))
    step.rebind_mesh(one, transfer=False)  # the shardings and the program, no value moved
    for module in (A, CC, SP):  # dispatch as on the chip
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    whole = NamedSharding(one, P())

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole)

    train, states, aux = step._gather()
    assert sum(v.size for v in train) == 797850560
    x = jax.ShapeDtypeStruct((1, 8192), jnp.float32, sharding=step._batch_sharding(2))
    with jax.default_matmul_precision("default"):
        compiled = step._jit.lower(
            jax.tree.map(sds, train), jax.tree.map(sds, states), jax.tree.map(sds, aux),
            x, x, sds(step._ensure_key()), sds(step._t_dev)).compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    print("arguments %.2f + outputs %.2f + temporaries %.2f - aliased %.2f = %.2f GB"
          % tuple(v / 1e9 for v in (m.argument_size_in_bytes, m.output_size_in_bytes,
                                    m.temp_size_in_bytes, m.alias_size_in_bytes, held)))
    assert m.alias_size_in_bytes >= 6 * 797850560 - 4096  # weights and state donated
    # no more than with the scan as XLA's formula (PR 47's tree: 11.208 GB, 6.421 of them
    # temporaries)
    assert held < 11.21e9 and m.temp_size_in_bytes < 6.43e9 and held + 2 * 797850560 < 14e9
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    # nine Mamba layers: the forward of the filter and of the scan, its second run, the backward
    for kernel in ("causal_conv_silu", "ssd_chunk"):
        calls = re.findall(r" custom-call\([^\n]*%s_(fwd|bwd)/pallas_call" % kernel, text)
        assert (calls.count("fwd"), calls.count("bwd")) == (18, 9), kernel
    # the mixer's arrays lie tokens-minor from in_proj to the gated norm and the kernels take
    # them so: no copy or transpose of a mixer-sized array (8192 x 4096 elements) is written
    # (with the formula 63 were: 27 f32[512,8,32,256], 18 of the scan's float32 result turned
    # for the gated norm's two passes, 18 bfloat16 ones)
    assert not _copies(text, 8192 * 4096), _copies(text, 8192 * 4096)


# -- Kimi's delta attention and the Solar Open 2 cell's whole step ---------------------
_KDA = (1, 8192, 8, 128)  # a delta-attention layer of the cell: 8 heads of 128 held


def _delta_rule_shapes():
    bf = jnp.dtype("bfloat16")
    return ((_KDA, bf), (_KDA, bf), (_KDA, bf), (_KDA, jnp.float32), (_KDA[:3], bf))


@pytest.mark.parametrize("dispatch", ["chip", "off_chip"])
@pytest.mark.parametrize("half", ["fwd", "bwd"])
def test_gated_delta_rule_compiles_and_keeps_no_pairwise_tensor(chip, monkeypatch, half,
                                                                dispatch):
    """``gated_delta_rule`` at a delta-attention layer of the Solar Open 2 cell
    (1 x 8192 tokens, 8 heads of 128, chunks of 64), forward alone and with
    its own backward. Off the chip: XLA's products and fusions under two
    ``while`` loops (the carry each way), no kernel, and 0.741 GB of temporaries
    (``jax.checkpoint`` of the forward under plain autodiff, the other way ISSUE
    47 named, compiled to 0.775 and is not built: PERF.md, Findings, PR 47).
    Dispatched as on the chip: the two kernels of ``ops/delta_rule_pallas.py``
    by their names under the op's two scopes, no loop, and under a sixth of
    those temporaries (the opening states and ``T`` the forward keeps, the
    gradients' pads). Either way no (chunks x heads x 64 x 64 x 128) tensor
    among the results written to memory (2.1 GB in float32)."""
    from mxnet_tpu.ops import delta_rule as D

    monkeypatch.setattr(DP, "on_tpu", lambda: dispatch == "chip")
    shapes = _delta_rule_shapes()
    own = functools.partial(D.gated_delta_rule, chunk=64)
    kernels = dispatch == "chip"
    if half == "fwd":
        text = _compile(chip, own, *shapes)
        assert text.count(" while(") == (0 if kernels else 1)
    else:
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip)
                for s, d in ((_KDA, jnp.float32),) + shapes]
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(jax.grad(
                lambda g, *a: jnp.sum(own(*a).astype(jnp.float32) * g),
                argnums=tuple(range(1, 6)))).lower(*args).compile()
        text = compiled.as_text()
        assert text.count(" while(") == (0 if kernels else 2)
        assert compiled.memory_analysis().temp_size_in_bytes < (0.12e9 if kernels else 0.76e9)
    pairwise = 128 * 8 * 64 * 64 * 128
    large = [(t, d) for t, d in _entry_results(text)
             if functools.reduce(lambda a, b: a * b, d, 1) >= pairwise // 4]
    assert not large, large
    assert re.search(r"bf16\[1,8192,(8,128|1024)\]", text)  # o, or a gradient, written once
    if not kernels:
        assert "tpu_custom_call" not in text
        return
    # the backward's program runs the forward too: its states and T are the residual
    assert text.count("tpu_custom_call") == (1 if half == "fwd" else 2)
    assert "delta_rule%s/jit(_fwd_pallas)/kda_chunk_fwd/" % ("" if half == "fwd" else ")") in text
    assert "f32[1,128,8,128,128]" in text  # a (V, K) float32 state a head a chunk
    if half == "bwd":
        assert "(delta_rule_bwd))/jit(_bwd_pallas)/kda_chunk_bwd/" in text
        assert "f32[1,128,8,64,64]" in text  # and its T


def test_the_solar_cells_filter_and_attention_take_their_kernel_branches(chip, monkeypatch):
    """``causal_conv_silu`` over all 3072 columns of the fused projection's
    result from column 0 (no other cell's call) compiles to the two kernels of
    ``ops/causal_conv_pallas.py``, and attention at (1, 8 query heads on ONE
    K/V head of 128, 8192 rows) to both flash kernels."""
    from mxnet_tpu.ops.gated_conv import causal_conv_silu

    monkeypatch.setattr(CC, "on_tpu", lambda: True)
    dt = jnp.dtype("bfloat16")
    assert CC.kernel_takes((1, 8192, 3072), 4, dt, 0)
    op = functools.partial(causal_conv_silu, columns=(0, 3072))
    text = _compile(
        chip, jax.grad(lambda x, w, b, g: jnp.sum(op(x, w, b).astype(jnp.float32) * g),
                       argnums=(0, 1)), ((1, 8192, 3072), dt), ((3072, 4), dt),
        ((3072,), dt), ((1, 8192, 3072), jnp.float32))
    assert text.count("tpu_custom_call") == 1 and "causal_conv_silu_bwd" in text
    both = _compile(chip, lambda x, w, b: jax.vjp(op, x, w, b)[0], ((1, 8192, 3072), dt),
                    ((3072, 4), dt), ((3072,), dt))
    assert both.count("tpu_custom_call") == 1 and "causal_conv_silu_fwd" in both
    fwd, bwd = _heads_major_calls((1, 8, 8192, 128), 1)
    assert "flash_attention_fwd" in str(fwd) and "flash_attention_bwd" in str(bwd)


def test_the_solar_cells_whole_step_fits_without_recomputation(chip, monkeypatch):
    """``solar_open2_train_s8192``'s step, 840,875,672 parameters (835,631,512
    of them trained: the routers are frozen) under Adam at (1, 8192) through
    ``ShardedTrainStep``, compiled for the described chip as the cell builds it
    (both flash kernels, the filter's two, the delta rule's two, the expert
    layer's and the embedding gradient's in, ``remat`` from the configuration's
    file: none): 9.19 GB live, 4.16 GB of it temporaries (11.21 and 6.19 with the
    delta rule as XLA's formula), under the 14 GB that leave room for the seeded
    copy (1.68 GB) and the batch pool, and 36 copies or transposes of a
    mixer-sized array (8192 x 1024 elements or more) where the formula's step
    wrote 60."""
    import json

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import solar_open2 as zoo

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs",
                           "solar_open2_250b_ep40_tp8.json")) as f:
        config = json.load(f)
    remat = config["assumed"]["recomputation"]["remat"]
    assert remat is None
    cfg = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
    net = zoo.SolarOpen2Model(cfg, experts_held=tuple(config["experts_held"]),
                              chunk=config["assumed"]["chunk"])
    net.initialize(mx.init.Zero())  # shapes are what is compiled, not values
    net.cast(config["dtype"])
    net.collect_params(".*router_weight").setattr("grad_req", "null")
    opt = {k: v for k, v in config["optimizer"].items() if k != "name"}
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), config["optimizer"]["name"], opt,
        mesh=parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1]), remat=remat)
    one = Mesh([chip._device], ("data",))
    step.rebind_mesh(one, transfer=False)  # the shardings and the program, no value moved
    for module in (A, CC, DP, EG, GM, RG):  # dispatch as on the chip
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    whole = NamedSharding(one, P())

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole)

    train, states, aux = step._gather()
    trained = sum(v.size for v in train)
    assert trained == 840875672 - 4 * (320 * 4096 + 320) == 835631512  # less routers, biases
    x = jax.ShapeDtypeStruct((1, 8192), jnp.float32, sharding=step._batch_sharding(2))
    with jax.default_matmul_precision("default"):
        compiled = step._jit.lower(
            jax.tree.map(sds, train), jax.tree.map(sds, states), jax.tree.map(sds, aux),
            x, x, sds(step._ensure_key()), sds(step._t_dev)).compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    print("arguments %.2f + outputs %.2f + temporaries %.2f - aliased %.2f = %.2f GB"
          % tuple(v / 1e9 for v in (m.argument_size_in_bytes, m.output_size_in_bytes,
                                    m.temp_size_in_bytes, m.alias_size_in_bytes, held)))
    assert m.alias_size_in_bytes >= 6 * trained - 4096  # weights and state donated
    # no more than with the delta rule as XLA's formula (PR 48's tree: 11.213 GB, 6.188 of
    # them temporaries)
    assert held < 9.4e9 and m.temp_size_in_bytes < 4.4e9 and held + 2 * 840875672 < 14e9
    text = compiled.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    # three delta-attention layers: the filter's forward and its backward, once each
    calls = re.findall(r"= [^=]*custom-call\([^\n]*causal_conv_silu_(fwd|bwd)/pallas_call", text)
    assert (calls.count("fwd"), calls.count("bwd")) == (3, 3)
    calls = re.findall(r"= [^=]*custom-call\([^\n]*kda_chunk_(fwd|bwd)/pallas_call", text)
    assert (calls.count("fwd"), calls.count("bwd")) == (3, 3)
    assert "grouped_matmul" in text and "delta_rule_bwd" in text
    # the kernels take rows (tokens along the sublanes) and the filter's kernels give tokens
    # along the lanes: what is left are the turns between them (a layer: the filter's result
    # once, dq, dk, dv once each) and the gated norm's float32 passes; the formula's step
    # wrote 60 such copies (48 under kda: heads_first, by_sub, the carry's operands)
    assert len(_copies(text, 8192 * 1024)) <= 36, _copies(text, 8192 * 1024)

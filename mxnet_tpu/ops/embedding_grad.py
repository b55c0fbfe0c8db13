"""The gradient of an embedding table as a grouped product over the table's
tiles: no row is read back, each tile of the table is written once.

``jnp.take(weight, ids, axis=0)`` transposes to a scatter-add: for every token
a row of the (V, D) gradient is read, added to and written back, in turn (the
compiler sorts the ids first). ``table_grad(ids, dy, vocab)`` is the same sum
done once a row:

1. one ``sort`` of the ids with their positions, and the cotangent's rows
   gathered into that order (XLA's gather of whole rows);
2. the table cut into tiles of ``TILE`` = 128 rows: the tokens of tile g are
   one contiguous stretch of the sorted rows, ``sizes[g]`` of them;
3. ``dW[g] = onehot(id - 128 g)^T @ dy_sorted[stretch of g]``, which is
   ``ops/grouped_matmul.py``'s weight-gradient kernel (``grouped_matmul_dw``:
   a float32 (128, D) accumulator in VMEM, the walk read on the device from
   ``sizes``, an empty tile stored as zeros, one rounding) with the (tokens,
   128) one-hot of ``id % 128`` as its left operand and the table's tiles as
   its groups. The (G, 128, D) result is the gradient, cut to V rows (a copy
   where V is no whole number of tiles).

Device ms a call, TPU v5e, from one profiler trace a row (my chip run, PR 45:
``tools/embedding_grad_table.py``), XLA's scatter-add / this form (of which
the kernel), ids uniform (a Zipf draw reads the same; every id equal in
brackets), bfloat16:
  (2, 768) by 16384 tokens: 0.329 / 0.113 (0.033)
  (16032, 2048) by 8192: 1.270 / 0.432 (0.205)  [0.877 / 0.315]
  (16384, 2048) by 8192: 1.271 / 0.256 (0.187)  [0.880 / 0.203]
  (18992, 2048) by 8192: 1.392 / 0.475 (0.232)  [0.935 / 0.338]
  (25088, 2048) by 8192: 1.673 / 0.336 (0.269)  [1.069 / 0.256]
  (30522, 768) by 16384: 0.667 / 0.381 (0.179)  [0.605 / 0.270]
  (37984, 2560) by 8192: 15.103 / 1.136 (0.468)  [5.818 / 1.001]
  (151936, 2560) by 8192: 3.402 / 1.858 (1.754)  [3.216 / 1.326]
XLA's form sums in float32 up to (37984, 2560) (its result equals this
form's) and in bfloat16 at 151936 rows, where every id equal reads 0.295 of
the float32 sum's largest entry off, a Zipf draw 0.115, and this form 0.003.
float32, (30522, 768) by 16384: 0.716 / 0.591, but 0.002 of the largest entry
off where XLA's scatter-add is exact (the chip's default product rounds the
cotangent to bfloat16), and 0.716 / 0.966 with the product at
``Precision.HIGHEST``: a float32 table stays XLA's.

In the STEP the tables under 128 MiB did not keep what the table promises (my
chip runs, PR 45, one seed a side, this form at every size, the cotangent's
layout left to the compiler): the compiler
fuses its own scatter-add into the tied head's weight gradient and lays the
residual stream out around it, and around a custom call it does neither.
``lfm2_a2b_train_s8192`` read 7.4091 -> 7.1590 samples/s/chip (-3.4 %: every
(8192, 2048) array of the residual stream laid out tokens-minor, 0.6 GB of
copies a step at the kernels' edges), ``bert_base_train_s512`` 370.16 ->
366.38 (-1.0 %), ``granite4_h_micro_train_s8192`` 2.38887 -> 2.38455 (-0.2
%), Keye and Kanana +0.9 and +0.3 %; ``smallthinker_a3b_train_s8192``, the one
table over 128 MiB, 5.5315 -> 6.0266 (+9.0 %; 6.0554 with the cotangent held
as rows, as ``table_grad`` now holds it). So the rule has ONE line on the
table's bytes: up to ``TABLE_BYTES`` (a v5e core's 128 MiB of VMEM, the one
boundary the readings bracket: 98 MiB reads 0.07-0.2 us a row by XLA, 185 MiB
1.84) the call is XLA's, whose step is then the step it was.

Where the call can see a TPU, a bfloat16 table over ``TABLE_BYTES``, D whole
128-lane lengths, tokens a multiple of the kernel's 256 rows a visit and
blocks inside the VMEM ceiling (``kernel_takes``), ``ops/indexing.py:embedding``
takes this form; every other call (the CPU, float32, a table XLA's form is
cheap at, odd widths, few tokens, the row-sparse variant) keeps ``jnp.take``'s
own transpose. Which a traced call takes is counted
(``telemetry.embedding_grad_branches()``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..context import on_tpu
from . import chip as _chip
from . import grouped_matmul as _gm

TILE = 128  # rows of the table a group of the product
TABLE_BYTES = _chip.VMEM_BYTES  # a core's VMEM: XLA's form is taken up to a table of this size


def kernel_takes(vocab, width, tokens, dtype):
    """Whether the gradient of a (vocab, width) table looked up by ``tokens``
    ids is the kernel's: everything the call can see. bfloat16 alone (the
    chip's product rounds a float32 cotangent to bfloat16, and XLA's
    scatter-add in float32 is exact), and a table over ``TABLE_BYTES``: under
    it XLA's scatter-add costs 0.3-1.7 ms and the compiler fuses it into the
    step around it (the module's docstring has both readings)."""
    if not on_tpu() or jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return False
    if 2 * vocab * width <= TABLE_BYTES or width % _chip.LANES or tokens == 0 or tokens % _gm._DW_ROWS:
        return False
    return _gm._tgmm_vmem(_gm._DW_ROWS, TILE, width, 2) <= _chip.VMEM_CEILING


def table_grad(ids, dy, vocab, interpret=False):
    """The same sum by the grouped product (the module's docstring): int32
    ids (tokens,) inside [0, vocab), cotangent (tokens, D) -> (vocab, D), each
    row the float32 sum of its tokens' cotangents rounded once."""
    tokens, width = dy.shape
    groups = -(-vocab // TILE)
    # held as rows: left to itself the compiler lays the whole residual stream
    # out tokens-minor around this call (LFM2's step, SmallThinker's) and pays
    # 0.6-0.75 GB of copies a step at the kernels' edges (PERF.md, PR 45)
    dy = with_layout_constraint(dy, Layout(major_to_minor=(0, 1)))
    ids, at = jax.lax.sort_key_val(ids, jnp.arange(tokens, dtype=jnp.int32))
    rows = dy.at[at].get(unique_indices=True, mode="promise_in_bounds")
    ends = jnp.searchsorted(ids, TILE * jnp.arange(1, groups + 1, dtype=jnp.int32),
                            side="left", method="compare_all").astype(jnp.int32)
    sizes = jnp.diff(ends, prepend=0)
    onehot = (ids[:, None] % TILE == jnp.arange(TILE, dtype=jnp.int32)[None, :]
              ).astype(dy.dtype)
    tiles = _gm._tgmm_pallas(onehot, rows, sizes, interpret=interpret)
    return tiles.reshape(groups * TILE, width)[:vocab]

"""The chip the kernels are written for, stated once: a TPU v5e core.

Constants only. Which kernel takes a call, and with which tiles, is each
kernel module's own rule (``kernel_takes``, attention's ``_kv_fits_vmem`` /
``_heads_per_step`` / ``_in_place``, the cost model of ``tuning/autotune.py``);
those rules read the facts of the chip from here by attribute, so that a test
which shrinks the chip does it in one place. What a kernel chooses to spend of
these (``_VMEM_KV_BYTES``, ``_HEAD_ROWS``, a row tile) is tuning and stays
with the kernel.
"""
from __future__ import annotations

VMEM_BYTES = 128 * 2 ** 20  # a core's VMEM
VMEM_CEILING = 96 * 2 ** 20  # the most of it a kernel may ask for
VMEM_SCOPED_DEFAULT = 16 * 1024 * 1024  # what Mosaic gives a call unasked
LANES = 128  # a vector register's minor dimension, and a tile's
SUBLANES = {4: 8, 2: 16, 1: 32}  # a tile's rows by itemsize: 32 bits a sublane

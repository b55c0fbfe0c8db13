#!/usr/bin/env python3
"""The stand-alone table of the embedding's gradient (PERF.md, Findings, PR 45).

    python3 tools/embedding_grad_table.py [--seed N] [--rehearse]

On the chip: device ms a call, from a profiler trace, of XLA's scatter-add and
of ``ops/embedding_grad.py``'s grouped product at the tables of the benchmark's
language-model cells and at SmallThinker's published vocabulary, ids uniform,
from a Zipf draw and all equal, and the float32 table of BERT's shape (which
the op's rule leaves to XLA: the table says why). One JSON line a row, and all
of them in ``chiprun_out/embedding_grad_table.json``.
``--rehearse``: tiny shapes on the CPU, the kernel interpreted (exits 3).
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402 — device_ms, embedding_grad_table

SHAPES = {"bert_token_types": (2, 768, 16384),
          "kanana2_a3b_train_s4096": (16032, 2048, 8192),
          "lfm2_a2b_train_s8192": (16384, 2048, 8192),
          "keye_vl2_a3b_train_s8192": (18992, 2048, 8192),
          "granite4_h_micro_train_s8192": (25088, 2048, 8192),
          "bert_base_train": (30522, 768, 16384),
          "smallthinker_a3b_train_s8192": (37984, 2560, 8192),
          "smallthinker_published": (151936, 2560, 8192)}
DRAWS = ("uniform", "zipf", "equal")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    shapes = {"tiny": (300, 256, 512), "one_tile": (2, 128, 256)} if args.rehearse else SHAPES
    out = {"device": jax.devices()[0].device_kind, "bfloat16": {}, "float32": {}}
    cases = [("bfloat16", name, DRAWS) for name in shapes]
    cases += [("float32", name, DRAWS[:1]) for name in ("tiny", "bert_base_train") if name in shapes]
    for dtype, name, draws in cases:  # a row printed as it is read: a later one may fail
        rows = chip_smoke.embedding_grad_table(args, args.rehearse, {name: shapes[name]}, draws, dtype)
        out[dtype].update(rows)
        for key, row in rows.items():
            print(json.dumps({dtype: key, **row}), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "embedding_grad_table.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())

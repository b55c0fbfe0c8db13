"""Share of the device's busy time, in the traced part of the window, that
went to the expert layers: operations under the scope ``moe`` (router,
dispatch, experts, combine, shared, both passes; the program's own names in
the trace, ``mxnet_tpu.profiler.aggregate``'s ``named_s``) and the compiler's
grouped-matmul kernels (``ragged-dot-*`` in ``kernel_s``), which
``jax.lax.ragged_dot`` becomes on the chip and which carry the compiler's
name and no scope. In percent. Nothing where the run was not traced, where the
program has no such reader, or where no operation ran under that scope; and
nothing on a TPU where no kernel of that name ran, so that a compiler which
renames or fuses its grouped matmul leaves the line without the metric (a
traced run is then refused) and not with a share that reads a fifth low (20 of
90 ms a step were those kernels: my chip run, PR 28)."""
NAME = "moe_share.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness import program_trace

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("busy_s") or not agg.get("named_s", {}).get("moe"):
        return None
    grouped = sum(v for k, v in agg.get("kernel_s", {}).items() if k.startswith("ragged-dot"))
    if not grouped and "TPU" in run.get("device_kind", ""):
        return None
    return 100.0 * (agg["named_s"]["moe"] + grouped) / agg["busy_s"]

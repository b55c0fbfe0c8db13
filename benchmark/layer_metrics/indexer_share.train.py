"""Share of the device's busy time, in the traced part of the window, that
went to the lightning indexers: operations under the scope ``indexer`` (its
three projections, the key's norm, rotary positions, and the scores and the
selection, which on the chip are one kernel, ``indexer_select``; the indexer
has no backward pass), by the program's own names in the trace
(``mxnet_tpu.profiler.aggregate``'s ``named_s``). In percent. Nothing where
the run was not traced, where the program has no such reader, or where no
operation ran under that scope."""
NAME = "indexer_share.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness import program_trace

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("busy_s") or not agg.get("named_s", {}).get("indexer"):
        return None
    return 100.0 * agg["named_s"]["indexer"] / agg["busy_s"]

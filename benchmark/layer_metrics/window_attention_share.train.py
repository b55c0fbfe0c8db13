"""Share of the device's busy time, in the traced part of the window, that
went to the window layers' attention: operations under the scope
``attn_window`` (the two projections in, rotary positions, the kernels
``window_attention_fwd`` / ``window_attention_bwd`` under ``attention`` /
``attention_bwd``, the projection out; both passes), by the program's own
names in the trace (``mxnet_tpu.profiler.aggregate``'s ``named_s``). In
percent. Nothing where the run was not traced, where the program has no such
reader, or where no operation ran under that scope."""
NAME = "window_attention_share.train"
UNIT = "%"
LAYER = "model step"
MOVES = "train_samples_per_s"
SOURCE = "device_trace"


def read(run):
    from harness import program_trace

    agg = program_trace.aggregate(run)
    if not agg or not agg.get("busy_s") or not agg.get("named_s", {}).get("attn_window"):
        return None
    return 100.0 * agg["named_s"]["attn_window"] / agg["busy_s"]

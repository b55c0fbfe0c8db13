"""``GraniteHybridModel`` of the program's model zoo, trained through
``parallel.ShardedTrainStep`` on a mesh of the cell's chips with the Gluon
softmax cross-entropy: the entry point the other language-model cells use.
Sizes come from the configuration's file, and so does the recomputation the
step runs with (``assumed.recomputation.remat``, the step's own ``remat``
argument): the first cell whose activations do not fit beside its weights
without one.

Nothing is routed, so there is no ``zero_counts``. The head's weight is the
embedding's: one leaf on both sides. After the window the program's own
counts are read once (``after_window``): the chunks a sequence's scan walks
and the branch each traced scan took.
"""
from __future__ import annotations

from harness.loader import load_module
# at import: a program without this model fails here, before any device work
from mxnet_tpu.gluon.model_zoo import granite_hybrid as zoo

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter."""
    ref = load_module("references", "granite_hybrid")
    part = {"in_norm.g": "input_norm_gamma", "in.w": "mamba_in_proj_weight",
            "conv.w": "mamba_conv_weight", "conv.bias": "mamba_conv_bias",
            "A_log": "mamba_A_log", "D": "mamba_D", "dt_bias": "mamba_dt_bias",
            "gate_norm.g": "mamba_norm_gamma", "out.w": "mamba_out_proj_weight",
            "q.w": "gqa_q_proj_weight", "kv.w": "gqa_kv_proj_weight",
            "o.w": "gqa_o_proj_weight", "post_norm.g": "post_norm_gamma",
            "gate.w": "mlp_gate_weight", "up.w": "mlp_up_weight",
            "down.w": "mlp_down_weight"}
    whole = {"embed.w": "embed_weight", "norm.g": "norm_gamma"}
    names = {}
    for leaf in ref.leaves(config):
        if leaf in whole:
            names[leaf] = prefix + whole[leaf]
        else:
            layer, _, rest = leaf.partition(".")
            names[leaf] = "%slayer%s_%s" % (prefix, layer[1:], part[rest])
    return names


def build(config, traffic, params, devices, opt):
    import mxnet_tpu as mx
    from mxnet_tpu import parallel, telemetry

    remat = ((config.get("assumed") or {}).get("recomputation") or {}).get("remat")
    net = zoo.GraniteHybridModel(config)
    net.initialize()
    net.cast(config["dtype"])
    net_params = net.collect_params()
    names = leaf_names(config, net.prefix)
    values = {leaf: params[leaf].astype(net_params[name].dtype)
              for leaf, name in names.items()}
    common.set_parameters(net_params, names, values)
    mesh = parallel.make_mesh((len(devices),), ("data",), devices=list(devices))
    hyper = {k: v for k, v in opt.items() if k != "name"}
    step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                     opt["name"], hyper, mesh=mesh, remat=remat)
    prog = common.TrainProgram(
        step, names, lambda name: net_params[name].data().data,
        lambda name: tuple(step._states[name]), opt, traffic["batch"],
        {"entry": "sharded_step", "net": "GraniteHybridModel",
         "parameters": len(net_params), "layer_types": list(config["layer_types"]),
         "remat": remat},
        step._shard_batch if len(devices) > 1 else None)
    chunks = -(-traffic["sequence"] // min(config["mamba_chunk_size"], traffic["sequence"]))
    prog.after_window = lambda: {"ssd_chunks": chunks,
                                 "ssd_branches": telemetry.ssd_branches()}
    return prog

"""``Lfm2MoeModel`` of the program's model zoo, given this chip's share of the
experts and trained through ``parallel.ShardedTrainStep`` on a mesh of the
cell's chips with the Gluon softmax cross-entropy: the entry point the BERT,
Kanana and Keye cells use. Sizes come from the configuration's file. Where
its ``assumed`` says ``router_trained: false`` (a share of the experts trained
alone, without the exchange that sums the shares' gradients), the routers'
weights are frozen here, by ``grad_req``, as the reference stops the chosen
weights' gradient; the model zoo's block already lets none of it through to
the layer's input on a strict share.

The selection bias of every expert layer is a leaf of the reference with a
gradient of zero; in the program it is a buffer the optimizer never sees, so
here its state reads zero, as the frozen routers' does. The head's weight is
the embedding's: one leaf on both sides. After the window the program's own
counts are read once: token-slots held and not computed (``zero_counts``,
compared with 0) and the slots each held expert of each layer got
(``after_window``).
"""
from __future__ import annotations

from harness.loader import load_module
# at import: a program without this model fails here, before any device work
from mxnet_tpu.gluon.model_zoo import lfm2 as zoo

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter."""
    ref = load_module("references", "lfm2_moe")
    part = {"op_norm.g": "operator_norm_gamma", "in.w": "short_conv_in_proj_weight",
            "conv.w": "short_conv_conv_weight", "out.w": "short_conv_out_proj_weight",
            "q.w": "gqa_q_proj_weight", "kv.w": "gqa_kv_proj_weight",
            "q_norm.g": "gqa_qk_norm_q_gamma", "k_norm.g": "gqa_qk_norm_k_gamma",
            "o.w": "gqa_o_proj_weight", "ffn_norm.g": "ffn_norm_gamma",
            "gate.w": "ffn_gate_weight", "up.w": "ffn_up_weight",
            "down.w": "ffn_down_weight", "router.w": "moe_router_weight",
            "router.bias": "moe_router_bias", "experts.gate": "moe_gate_weight",
            "experts.up": "moe_up_weight", "experts.down": "moe_down_weight"}
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
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    ref = load_module("references", "lfm2_moe")
    cfg = dict(config, num_experts=config["published"]["num_experts"])
    net = zoo.Lfm2MoeModel(cfg, experts_held=tuple(config["experts_held"]))
    net.initialize()
    net.cast(config["dtype"])
    if not ref.router_trained(config):
        net.collect_params(".*router_weight").setattr("grad_req", "null")
    net_params = net.collect_params()
    names = leaf_names(config, net.prefix)
    # the buffers keep their own type (the bias float32): the seeded values
    # are exact in it
    values = {leaf: params[leaf].astype(net_params[name].dtype)
              for leaf, name in names.items()}
    common.set_parameters(net_params, names, values)
    mesh = parallel.make_mesh((len(devices),), ("data",), devices=list(devices))
    hyper = {k: v for k, v in opt.items() if k != "name"}
    step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                     opt["name"], hyper, mesh=mesh)

    def state_of(name):
        if name in step._states:
            return tuple(step._states[name])
        # a buffer or a frozen router: the optimizer never saw it, so its
        # gradient was zero
        zero = jnp.zeros_like(net_params[name].data().data)
        return (zero,) * common.train_reference.state_slots(opt)

    prog = common.TrainProgram(
        step, names, lambda name: net_params[name].data().data, state_of, opt,
        traffic["batch"],
        {"entry": "sharded_step", "net": "Lfm2MoeModel",
         "parameters": len(net_params), "experts_held": list(config["experts_held"]),
         "layer_types": list(config["layer_types"])},
        step._shard_batch if len(devices) > 1 else None)
    start = zoo.moe_counts(net)  # what the eager shape pass counted, if any

    def counts():
        now = zoo.publish_moe_counts(net)
        load = [[b - a for a, b in zip(r0, r1)]
                for r0, r1 in zip(start["expert_load"], now["expert_load"])]
        return load, now["slots_lost"] - start["slots_lost"]

    prog.zero_counts = lambda: {"routed_slots_lost": counts()[1]}
    prog.after_window = lambda: {"expert_slots": counts()[0]}
    return prog

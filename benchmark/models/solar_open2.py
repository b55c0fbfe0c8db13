"""``SolarOpen2Model`` of the program's model zoo, given this chip's share of the
experts (and, by the file's own head counts, of the heads) and trained through
``parallel.ShardedTrainStep`` on a mesh of the cell's chips with the Gluon
softmax cross-entropy: the entry point the other language-model cells use.
Sizes come from the configuration's file, and so do the recomputation the step
runs with (``assumed.recomputation.remat``, the step's own ``remat`` argument)
and the delta rule's chunk (``assumed.chunk``: the program's choice, no part of
the model). Where ``assumed`` says ``router_trained: false`` the routers'
weights are frozen here, by ``grad_req``, as the reference stops the chosen
weights' gradient.

The selection bias of every expert layer is a leaf of the reference with a
gradient of zero; in the program it is a buffer the optimizer never sees, so
here its state reads zero, as the frozen routers' does. After the window the
program's own counts are read once: token-slots held and not computed
(``zero_counts``, compared with 0), the slots each held expert of each layer
got, the chunks a sequence's delta rule walks and the branch each traced delta
rule took (``after_window``).
"""
from __future__ import annotations

from harness.loader import load_module
# at import: a program without this model fails here, before any device work
from mxnet_tpu.gluon.model_zoo import solar_open2 as zoo

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter."""
    ref = load_module("references", "solar_open2")
    part = {"in_norm.g": "input_norm_gamma", "q.w": "gqa_q_proj_weight",
            "kv.w": "gqa_kv_proj_weight", "gate.w": "gqa_gate_proj_weight",
            "o.w": "gqa_o_proj_weight", "qkv.w": "kda_qkv_proj_weight",
            "conv.w": "kda_conv_weight", "fa.w": "kda_f_a_proj_weight",
            "fb.w": "kda_f_b_proj_weight", "A_log": "kda_A_log",
            "dt_bias": "kda_dt_bias", "b.w": "kda_b_proj_weight",
            "ga.w": "kda_g_a_proj_weight", "gb.w": "kda_g_b_proj_weight",
            "gb.bias": "kda_g_b_proj_bias", "o_norm.g": "kda_o_norm_gamma",
            "out.w": "kda_o_proj_weight", "post_norm.g": "post_norm_gamma",
            "router.w": "moe_router_weight", "router.bias": "moe_router_bias",
            "experts.gate": "moe_gate_weight", "experts.up": "moe_up_weight",
            "experts.down": "moe_down_weight",
            "shared.gate.w": "moe_shared_gate_weight",
            "shared.up.w": "moe_shared_up_weight",
            "shared.down.w": "moe_shared_down_weight"}
    whole = {"embed.w": "embed_weight", "norm.g": "norm_gamma", "head.w": "head_weight"}
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
    from mxnet_tpu import parallel, telemetry

    ref = load_module("references", "solar_open2")
    assumed = config.get("assumed") or {}
    remat = (assumed.get("recomputation") or {}).get("remat")
    chunk = assumed.get("chunk", zoo.CHUNK)
    cfg = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
    net = zoo.SolarOpen2Model(cfg, experts_held=tuple(config["experts_held"]),
                              chunk=chunk)
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
                                     opt["name"], hyper, mesh=mesh, remat=remat)

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
        {"entry": "sharded_step", "net": "SolarOpen2Model",
         "parameters": len(net_params), "experts_held": list(config["experts_held"]),
         "gqa_layers": list(config["gqa_layers"]), "remat": remat, "chunk": chunk},
        step._shard_batch if len(devices) > 1 else None)
    start = zoo.moe_counts(net)  # what the eager shape pass counted, if any

    def counts():
        now = zoo.publish_moe_counts(net)
        load = [[b - a for a, b in zip(r0, r1)]
                for r0, r1 in zip(start["expert_load"], now["expert_load"])]
        return load, now["slots_lost"] - start["slots_lost"]

    chunks = -(-traffic["sequence"] // min(chunk, traffic["sequence"]))
    prog.zero_counts = lambda: {"routed_slots_lost": counts()[1]}
    prog.after_window = lambda: {"expert_slots": counts()[0],
                                 "delta_rule_chunks": chunks,
                                 "delta_rule_branches": telemetry.delta_rule_branches()}
    return prog

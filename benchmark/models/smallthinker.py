"""``SmallThinkerModel`` of the program's model zoo, given this chip's share of
the experts and trained through ``parallel.ShardedTrainStep`` on a mesh of the
cell's chips with the Gluon softmax cross-entropy: the entry point the BERT,
Kanana, Keye and LFM2 cells use. Sizes come from the configuration's file.
Where its ``assumed`` says ``router_trained: false`` (a share of the experts
trained alone, without the exchange that sums the shares' gradients), the
routers' weights are frozen here, by ``grad_req``, as the reference stops the
chosen weights' gradient; the model zoo's block already lets none of it
through to the attention's input on a strict share.

After the window the program's own counts are read once: token-slots held and
not computed and, on a TPU, window calls of the attention that did not take
their kernel (``zero_counts``, each compared with 0); the slots each held
expert of each layer got, and the tiles the traced window kernels visit beside
those the causal call would (``after_window``).
"""
from __future__ import annotations

from harness.loader import load_module
# at import: a program without this model fails here, before any device work
from mxnet_tpu.gluon.model_zoo import smallthinker as zoo

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter. The attention's block goes by its
    kind (``attn_full`` / ``attn_window``: the scopes device time is read by)."""
    ref = load_module("references", "smallthinker")
    attn = {"q.w": "q_proj_weight", "kv.w": "kv_proj_weight", "o.w": "o_proj_weight"}
    part = {"attn_norm.g": "attn_norm_gamma", "ffn_norm.g": "ffn_norm_gamma",
            "router.w": "moe_router_weight", "experts.gate": "moe_gate_weight",
            "experts.up": "moe_up_weight", "experts.down": "moe_down_weight"}
    whole = {"embed.w": "embed_weight", "norm.g": "norm_gamma", "head.w": "head_weight"}
    names = {}
    for leaf in ref.leaves(config):
        if leaf in whole:
            names[leaf] = prefix + whole[leaf]
            continue
        layer, _, rest = leaf.partition(".")
        l = int(layer[1:])
        if rest in attn:
            kind = "attn_window_" if config["sliding_window_layout"][l] else "attn_full_"
            names[leaf] = "%slayer%d_%s%s" % (prefix, l, kind, attn[rest])
        else:
            names[leaf] = "%slayer%d_%s" % (prefix, l, part[rest])
    return names


def window_calls_off_kernel():
    """Traced window calls of the attention that took an XLA branch."""
    from mxnet_tpu import telemetry

    return sum(n for branches in (telemetry.flash_fwd_branches(),
                                  telemetry.flash_bwd_branches())
               for branch, n in branches.items()
               if branch.startswith("window_") and branch != "window_kernel")


def build(config, traffic, params, devices, opt):
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel, telemetry

    ref = load_module("references", "smallthinker")
    cfg = dict(config, moe_num_primary_experts=config["published"]["moe_num_primary_experts"])
    net = zoo.SmallThinkerModel(cfg, experts_held=tuple(config["experts_held"]))
    net.initialize()
    net.cast(config["dtype"])
    if not ref.router_trained(config):
        net.collect_params(".*router_weight").setattr("grad_req", "null")
    net_params = net.collect_params()
    names = leaf_names(config, net.prefix)
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
        # a frozen router: the optimizer never saw it, so its gradient was zero
        zero = jnp.zeros_like(net_params[name].data().data)
        return (zero,) * common.train_reference.state_slots(opt)

    prog = common.TrainProgram(
        step, names, lambda name: net_params[name].data().data, state_of, opt,
        traffic["batch"],
        {"entry": "sharded_step", "net": "SmallThinkerModel",
         "parameters": len(net_params), "experts_held": list(config["experts_held"]),
         "rope_layout": list(config["rope_layout"]),
         "sliding_window_layout": list(config["sliding_window_layout"])},
        step._shard_batch if len(devices) > 1 else None)
    start = zoo.moe_counts(net)  # what the eager shape pass counted, if any

    def counts():
        now = zoo.publish_moe_counts(net)
        load = [[b - a for a, b in zip(r0, r1)]
                for r0, r1 in zip(start["expert_load"], now["expert_load"])]
        return load, now["slots_lost"] - start["slots_lost"]

    def zero_counts():
        out = {"routed_slots_lost": counts()[1]}
        if devices[0].platform == "tpu":  # elsewhere the XLA branches are the path
            out["window_calls_off_kernel"] = window_calls_off_kernel()
        return out

    prog.zero_counts = zero_counts
    prog.after_window = lambda: {"expert_slots": counts()[0],
                                 "window_blocks": telemetry.flash_window_blocks()}
    return prog

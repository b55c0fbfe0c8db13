"""``KeyeVL2Model`` of the program's model zoo, given this chip's share of the
experts and trained through ``parallel.ShardedTrainStep`` on a mesh of the
cell's chips with the Gluon softmax cross-entropy: the entry point the BERT and
Kanana cells use. Sizes come from the configuration's file. Where its
``assumed`` says ``router_trained: false`` / ``indexer_trained: false``, the
routers' and the indexers' weights are frozen here, by ``grad_req``, as the
reference stops the former's gradient and has none for the latter: their
optimizer state then reads zero, as the reference's gradient of them does.

After the window the program's own counts are read once (``zero_counts``, each
compared with 0): token-slots held and not computed; the pairs the indexers
selected less the exact count the shapes give (``sum of min(t + 1, topk)`` a
sequence a layer a step); and how far ``selection_mismatch`` lies over its
limit. ``selection_mismatch`` is the share of the pairs that layer 0's indexer
selects for the first batch, run once more through the model's own blocks with
the weights as the window left them, that the reference's float32 selection
from the same weights does not hold: random weights make attention nearly
uniform, so a wrong selection hardly moves the loss, and this number holds the
selection itself. Its limit stands in the configuration's file
(``reference.selection_mismatch_limit``; the cell's ``limits`` are the
runner's). ``after_window`` hands the readers the slots each held expert got,
the pairs selected and the causal pairs of the steps counted.
"""
from __future__ import annotations

import json

from harness.loader import load_module
# at import: a program without this model fails here, before any device work
from mxnet_tpu.gluon.model_zoo import keye as zoo

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter."""
    ref = load_module("references", "keye_vl2")
    part = {"attn_norm.g": "attn_norm_gamma", "q.w": "gqa_q_proj_weight",
            "kv.w": "gqa_kv_proj_weight", "q_norm.g": "gqa_qk_norm_q_gamma",
            "k_norm.g": "gqa_qk_norm_k_gamma", "o.w": "gqa_o_proj_weight",
            "index_q.w": "indexer_q_proj_weight", "index_k.w": "indexer_k_proj_weight",
            "index_k_norm.g": "indexer_k_norm_gamma",
            "index_k_norm.b": "indexer_k_norm_beta",
            "index_w.w": "indexer_weights_weight", "ffn_norm.g": "ffn_norm_gamma",
            "router.w": "moe_router_weight", "experts.gate": "moe_gate_weight",
            "experts.up": "moe_up_weight", "experts.down": "moe_down_weight"}
    whole = {"embed.w": "embed_weight", "norm.g": "norm_gamma", "head.w": "head_weight"}
    names = {}
    for leaf in ref.leaves(config):
        if leaf in whole:
            names[leaf] = prefix + whole[leaf]
        else:
            layer, _, rest = leaf.partition(".")
            names[leaf] = "%slayer%s_%s" % (prefix, layer[1:], part[rest])
    return names


class _Program(common.TrainProgram):
    """Counts the steps it dispatched and keeps the first batch's ids."""

    steps, first_ids = 0, None

    def batch(self, x, y):
        if self.first_ids is None:
            self.first_ids = x
        return super().batch(x, y)

    def step(self, batch):
        self.steps += 1
        return super().step(batch)


def selection_mismatch(config, net, names, ids):
    """Share of the pairs layer 0's indexer selects for ``ids``, through the
    model's own blocks with its weights as they are, that the reference's
    float32 selection from the same weights does not hold."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import nd

    ref = load_module("references", "keye_vl2")
    blk = net.blocks[0]
    got = blk.indexer(blk.attn_norm(net.embed(nd.NDArray(ids)))).data
    params = net.collect_params()
    now = {leaf: params[name].data().data for leaf, name in names.items()
           if leaf == "embed.w" or leaf.startswith("l0.")}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.first_selection(config, p, x))(now, ids)
    stray = jnp.sum(jnp.logical_and(got != 0, jnp.logical_not(want)), dtype=jnp.int32)
    return float(stray) / float(jnp.sum(got != 0, dtype=jnp.int32))


def build(config, traffic, params, devices, opt):
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    ref = load_module("references", "keye_vl2")
    flops = load_module("flops", "keye_vl2")
    assumed = config.get("assumed") or {}
    cfg = dict(config, num_experts=config["published"]["num_experts"])
    net = zoo.KeyeVL2Model(cfg, experts_held=tuple(config["experts_held"]))
    net.initialize()
    net.cast(config["dtype"])
    if not ref.router_trained(config):
        net.collect_params(".*router_weight").setattr("grad_req", "null")
    if not assumed.get("indexer_trained", True):
        net.collect_params(".*indexer_.*(weight|gamma|beta)").setattr("grad_req", "null")
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
        # frozen: the optimizer never saw it, so its gradient was zero
        zero = jnp.zeros_like(net_params[name].data().data)
        return (zero,) * common.train_reference.state_slots(opt)

    prog = _Program(
        step, names, lambda name: net_params[name].data().data, state_of, opt,
        traffic["batch"],
        {"entry": "sharded_step", "net": "KeyeVL2Model",
         "parameters": len(net_params), "experts_held": list(config["experts_held"])},
        step._shard_batch if len(devices) > 1 else None)
    start = (zoo.moe_counts(net), zoo.selection_counts(net))  # the eager shape pass's
    t, topk = traffic["sequence"], config["sa_config"]["topk"]
    a_step = traffic["batch"] * config["num_hidden_layers"]
    read = {}

    def counts():
        """Once: the aux-state counters over the steps dispatched, then (it
        moves the counters) layer 0's selection against the reference's."""
        if read:
            return read
        moe, sel = zoo.publish_moe_counts(net), zoo.publish_selection_counts(net)
        read["expert_slots"] = [[b - a for a, b in zip(r0, r1)] for r0, r1 in zip(
            start[0]["expert_load"], moe["expert_load"])]
        read["slots_lost"] = moe["slots_lost"] - start[0]["slots_lost"]
        for key in ("selected_pairs", "rows_searched"):
            read[key] = sum(sel[key]) - sum(start[1][key])
        read["steps_counted"] = prog.steps
        read["causal_pairs"] = prog.steps * a_step * (t * (t + 1) // 2)
        read["selected_pairs_expected"] = (
            prog.steps * a_step * flops.selected_pairs(t, topk))
        read["selection_mismatch"] = selection_mismatch(config, net, names, prog.first_ids)
        read["selection_mismatch_limit"] = config["reference"]["selection_mismatch_limit"]
        print(json.dumps(dict(read, phase="selection", expert_slots=None)), flush=True)
        return read

    def zero_counts():
        c = counts()
        return {"routed_slots_lost": c["slots_lost"],
                "selected_pairs_off_the_shapes_count":
                    c["selected_pairs"] - c["selected_pairs_expected"],
                "selection_mismatch_over_its_limit":
                    max(0.0, c["selection_mismatch"] - c["selection_mismatch_limit"])}

    prog.zero_counts = zero_counts
    prog.after_window = lambda: dict(counts())
    return prog

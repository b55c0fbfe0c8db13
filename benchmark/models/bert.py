"""BERT of the program's model zoo with its masked-language-model head,
trained through ``parallel.ShardedTrainStep`` on a mesh of the cell's chips.
Copied from ``chip_smoke.py``'s ``build_bert``/``phase_bert``; sizes come
from the configuration and the cell.
"""
from __future__ import annotations

from harness.loader import load_module

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    m = config["published"]
    names = {"embed.word": "word_embed_weight", "embed.type": "token_type_embed_weight",
             "embed.position": "position_weight", "embed.ln.g": "embed_ln_gamma",
             "embed.ln.b": "embed_ln_beta", "mlm.dense.w": "mlm_d_weight",
             "mlm.dense.bias": "mlm_d_bias", "mlm.ln.g": "mlm_ln_gamma",
             "mlm.ln.b": "mlm_ln_beta", "mlm.out.bias": "word_embed_bias"}
    for l in range(m["num_hidden_layers"]):
        p, q = "l%d." % l, "encoder_layer%d_" % l
        for ref, prog in (("qkv", "attn_qkv"), ("proj", "attn_proj"),
                          ("ffn1", "ffn_ffn1"), ("ffn2", "ffn_ffn2")):
            names[p + ref + ".w"] = q + prog + "_weight"
            names[p + ref + ".bias"] = q + prog + "_bias"
        for ln in ("ln1", "ln2"):
            names[p + ln + ".g"] = q + ln + "_gamma"
            names[p + ln + ".b"] = q + ln + "_beta"
    return {k: prefix + v for k, v in names.items()}


def build(config, traffic, params, devices, opt):
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import Block
    from mxnet_tpu.gluon.model_zoo import bert as zoo

    m = config["published"]
    # what model_zoo.bert.bert_12_768_12(use_classifier=False, dropout=0.0)
    # builds, from the file's sizes
    bert = zoo.BERTModel(
        num_layers=m["num_hidden_layers"], units=m["hidden_size"],
        hidden_size=m["intermediate_size"], num_heads=m["num_attention_heads"],
        vocab_size=m["vocab_size"], token_type_vocab_size=m["type_vocab_size"],
        max_length=m["max_position_embeddings"], dropout=0.0,
        layer_norm_eps=m["layer_norm_eps"], use_classifier=False)

    class MLMNet(Block):
        """Token ids in, vocabulary scores out for every position."""

        def __init__(self, bert_model):
            super().__init__(prefix="bench_mlm_")
            with self.name_scope():
                self.bert = bert_model

        def forward(self, x):
            seq_out, _ = self.bert(x, nd.zeros_like(x))
            return self.bert.decode_mlm(seq_out)

    net = MLMNet(bert)
    net.initialize()
    net.cast(config["dtype"])
    net(nd.zeros((1, traffic["sequence"]), dtype="float32"))  # deferred shapes
    net_params = net.collect_params()
    names = leaf_names(config, bert.prefix)
    common.set_parameters(net_params, names, params)
    mesh = parallel.make_mesh((len(devices),), ("data",), devices=list(devices))
    hyper = {k: v for k, v in opt.items() if k != "name"}
    step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                     opt["name"], hyper, mesh=mesh)
    return common.TrainProgram(
        step, names, lambda name: net_params[name].data().data,
        lambda name: tuple(step._states[name]), opt, traffic["batch"],
        {"entry": "sharded_step", "net": "BERTModel+MLM", "parameters": len(net_params)},
        step._shard_batch if len(devices) > 1 else None)

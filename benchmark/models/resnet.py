"""ResNet v1 of the program's model zoo, trained through the loop a Gluon
user writes, fused into one launch a step (``Trainer.fuse_step``), or through
``parallel.ShardedTrainStep`` where the cell's traffic asks for a mesh.
Copied from ``chip_smoke.py``'s ``build_resnet``/``phase_resnet50``/
``phase_multichip``; sizes come from the configuration and the cell.
"""
from __future__ import annotations

from harness.loader import load_module

common = load_module("models", "gluon_common")


def leaf_names(config, prefix):
    """Reference leaf -> program parameter name. The zoo numbers a stage's
    convolutions and batch norms in construction order."""
    m = config["published"]
    names = {"stem.conv.w": "conv2d0_weight", "stem.bn.g": "batchnorm0_gamma",
             "stem.bn.b": "batchnorm0_beta", "fc.w": "dense0_weight",
             "fc.bias": "dense0_bias"}
    for s, n in enumerate(m["layers"], start=1):
        conv = bn = 0
        for b in range(n):
            p = "s%d.b%d." % (s, b)
            for i, bias in enumerate((True, False, True)):
                names[p + "conv%d.w" % i] = "stage%d_conv2d%d_weight" % (s, conv)
                if bias:
                    names[p + "conv%d.bias" % i] = "stage%d_conv2d%d_bias" % (s, conv)
                names[p + "bn%d.g" % i] = "stage%d_batchnorm%d_gamma" % (s, bn)
                names[p + "bn%d.b" % i] = "stage%d_batchnorm%d_beta" % (s, bn)
                conv, bn = conv + 1, bn + 1
            if b == 0:
                names[p + "down.w"] = "stage%d_conv2d%d_weight" % (s, conv)
                names[p + "downbn.g"] = "stage%d_batchnorm%d_gamma" % (s, bn)
                names[p + "downbn.b"] = "stage%d_batchnorm%d_beta" % (s, bn)
                conv, bn = conv + 1, bn + 1
    return {k: prefix + v for k, v in names.items()}


def build(config, traffic, params, devices, opt):
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo

    m, dtype = config["published"], config["dtype"]
    with nn.layout_scope(config["layout"]):
        # what model_zoo.get_model("resnet50_v1") builds, from the file's sizes
        net = zoo.ResNetV1(zoo.BottleneckV1, list(m["layers"]), list(m["channels"]),
                           classes=m["classes"])
    net.initialize()
    net.cast(dtype)  # BN statistics stay float32 in the op
    net.hybridize()
    net(nd.zeros((1, m["image"], m["image"], 3), dtype=dtype))  # deferred shapes
    net_params = net.collect_params()
    names = leaf_names(config, net.prefix)
    common.set_parameters(net_params, names, params)
    hyper = {"learning_rate": opt["learning_rate"], "momentum": opt["momentum"]}
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if traffic["entry"] == "fuse_step":
        place = None
        trainer = gluon.Trainer(net_params, opt["name"], hyper)
        step = trainer.fuse_step(net, loss_fn)

        def state_of(name):
            st = trainer._updaters[0].states[trainer._param2idx[name]]
            return tuple(s.data for s in (st if isinstance(st, (list, tuple)) else (st,)))
    elif traffic["entry"] == "sharded_step":
        mesh = parallel.make_mesh((len(devices),), ("data",), devices=list(devices))
        step = parallel.ShardedTrainStep(net, loss_fn, opt["name"], hyper, mesh=mesh)

        def state_of(name):
            return tuple(step._states[name])

        place = step._shard_batch if len(devices) > 1 else None
    else:
        raise ValueError("no entry point named %r" % (traffic["entry"],))
    return common.TrainProgram(
        step, names, lambda name: net_params[name].data().data, state_of, opt,
        traffic["batch"],
        {"entry": traffic["entry"], "net": type(net).__name__, "parameters": len(net_params)},
        place)

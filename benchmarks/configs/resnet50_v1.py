"""The `resnet50_v1` configuration through the program: ResNet v1
bottleneck network, NHWC, softmax cross-entropy, under
`parallel.DataParallelTrainer`, built from the sizes in
resnet50_v1.json.  mxnet_tpu is imported only inside `build`."""
from __future__ import annotations

import numpy as np


def make_batch(rng, config, traffic):
    """One seeded batch: float32 NHWC images in [0, 1) and float32 class
    labels, (x, y) for `trainer.step(x, y)`."""
    bs, size = traffic["batch"], config["image_size"]
    x = rng.random_sample(
        (bs, size, size, config["image_channels"])).astype(np.float32)
    y = rng.randint(0, config["num_classes"], bs).astype(np.float32)
    return x, y


def reference_batch(x, y):
    """What the reference's `follow` takes for this batch."""
    return x, y.astype(np.int32)


def units_per_step(config, traffic):
    """Images a step trains on."""
    return traffic["batch"]


def conv_shapes(config):
    """[(out_size, k, in_channels, out_channels)] of every convolution,
    the stride of a stage on its first block's first 1x1 convolution."""
    size = config["image_size"] // 2
    shapes = [(size, 7, config["image_channels"], config["stem_channels"])]
    size //= 2  # 3x3 max pooling, stride 2
    in_ch = config["stem_channels"]
    for i, (n, ch) in enumerate(zip(config["layers"], config["channels"])):
        for j in range(n):
            if j == 0 and i > 0:
                size //= 2
            mid = ch // 4
            shapes += [(size, 1, in_ch, mid), (size, 3, mid, mid),
                       (size, 1, mid, ch)]
            if j == 0 and (ch != in_ch or i > 0):
                shapes.append((size, 1, in_ch, ch))
            in_ch = ch
    return shapes


def model_flops_per_step(config, traffic):
    """FLOPs one training step needs by the published sizes: the
    convolutions' and the classifier's multiply-adds of the forward pass
    times 2, times 3 (forward, and the backward's two products for
    each); batch norm, ReLU, pooling and the residual sums not counted."""
    forward = sum(2 * size * size * k * k * cin * cout
                  for size, k, cin, cout in conv_shapes(config))
    forward += 2 * config["channels"][-1] * config["num_classes"]
    return 3 * forward * traffic["batch"]


def build(config, traffic, weights):
    """The trainer whose `step` the window drives."""
    del traffic
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.vision import resnet
    from mxnet_tpu.parallel import data_parallel

    from harness import gluon_program

    ctx = mx.xla(0)
    net = resnet.ResNetV1(
        resnet.BottleneckV1, config["layers"],
        [config["stem_channels"]] + config["channels"],
        classes=config["num_classes"], layout=config["assumed"]["layout"])
    net.initialize(mx.init.Zero(), ctx=ctx)
    gluon_program.fill(net, weights, ctx)
    optimizer = dict(config["assumed"]["optimizer"])
    return data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer.pop("name"),
        optimizer, compute_dtype=config["assumed"]["compute_dtype"])

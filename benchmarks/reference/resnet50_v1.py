"""Plain reference for the `resnet50_v1` configuration: ResNet v1 with
bottleneck blocks (He et al. 2015, arXiv:1512.03385, Table 1), NHWC,
forward, softmax cross-entropy, gradients and SGD with momentum in
straightforward float32 `jax.numpy`.  Imports nothing of mxnet_tpu (only the
benchmark's own rounding helper for the control).

As `gluon.model_zoo.vision.resnet50_v1` builds it: the stride of a
stage sits on the block's first 1x1 convolution, batch norm uses the
batch's biased variance (eps 1e-5) and moves its running statistics
with momentum 0.9, max pooling pads with -inf, weight decay applies to
every trainable leaf.

Parameters are a flat list in the order of the program's
`block._ordered_params()`: stem conv and batch norm, then per block the
three body convolutions each followed by its batch norm (gamma, beta,
running mean, running var) and, in a stage's first block, the
down-sampling convolution and its batch norm, then the classifier.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import lowprec


def _blocks(config):
    """[(channels, stride, downsample, in_channels)] in forward order."""
    out, in_ch = [], config["stem_channels"]
    for i, (n, ch) in enumerate(zip(config["layers"], config["channels"])):
        for j in range(n):
            stride = (1 if i == 0 else 2) if j == 0 else 1
            down = j == 0 and (ch != in_ch or stride != 1)
            out.append((ch, stride, down, in_ch))
            in_ch = ch
    return out


def _conv(o, k, i):
    # He et al. 2015b (arXiv:1502.01852) initialisation, as the paper says
    return ((o, k, k, i), "normal", math.sqrt(2.0 / (k * k * i)))


def _bn(c):
    return [((c,), "ones", 0.0), ((c,), "zeros", 0.0),
            ((c,), "zeros", 0.0), ((c,), "ones", 0.0)]


def param_specs(config):
    """[(shape, kind, scale)] in program order."""
    stem = config["stem_channels"]
    specs = [_conv(stem, 7, config["image_channels"])] + _bn(stem)
    for ch, _stride, down, in_ch in _blocks(config):
        mid = ch // 4
        specs += [_conv(mid, 1, in_ch)] + _bn(mid)
        specs += [_conv(mid, 3, mid)] + _bn(mid)
        specs += [_conv(ch, 1, mid)] + _bn(ch)
        if down:
            specs += [_conv(ch, 1, in_ch)] + _bn(ch)
    last = config["channels"][-1]
    specs += [((config["num_classes"], last), "normal", 0.01),
              ((config["num_classes"],), "zeros", 0.0)]
    return specs


def leaf_parts(config):
    """Every leaf is one tensor of the published model."""
    return [1] * len(param_specs(config))


def trainable(config):
    """False for the running statistics (third and fourth leaf of every
    batch norm)."""
    flags = []
    n_bn = 1 + sum(4 if down else 3 for _, _, down, _ in _blocks(config))
    for _ in range(n_bn):
        flags += [True, True, True, False, False]
    return flags + [True, True]


def _conv2d(x, w, stride, pad, q):
    q_in, q_out = q
    return q_out(jax.lax.conv_general_dilated(
        q_in(x), q_in(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision="highest"))


def _batch_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta, mean, var


def _conv_bn(x, leaves, stride, pad, relu, q, eps):
    """Convolution, batch norm over the batch's own statistics, ReLU.
    `leaves` = (weight, gamma, beta, running mean, running var); the
    running statistics are not read in training."""
    w, gamma, beta = leaves[:3]
    y, mean, var = _batch_norm(_conv2d(x, w, stride, pad, q), gamma, beta,
                               eps)
    return (jax.nn.relu(y) if relu else y), (mean, var)


def loss_and_stats(flat, images, labels, *, config, precision):
    """Mean softmax cross-entropy of the batch, and each batch norm's
    batch mean and variance in forward order."""
    q = lowprec.rounding(precision)
    eps = config["assumed"]["batch_norm_eps"]
    flat = list(flat)
    x, stem_stats = _conv_bn(images, flat[:5], 2, 3, True, q, eps)
    stats, at = [stem_stats], 5
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    for _ch, stride, down, _in in _blocks(config):
        n_leaves = 20 if down else 15

        # one block at a time is recomputed in the backward pass, so the
        # float32 activations of the whole batch fit one chip
        @jax.checkpoint
        def block(x, leaves, stride=stride, down=down):
            y, s1 = _conv_bn(x, leaves[0:5], stride, 0, True, q, eps)
            y, s2 = _conv_bn(y, leaves[5:10], 1, 1, True, q, eps)
            y, s3 = _conv_bn(y, leaves[10:15], 1, 0, False, q, eps)
            if not down:
                return jax.nn.relu(y + x), [s1, s2, s3]
            res, s4 = _conv_bn(x, leaves[15:20], stride, 0, False, q, eps)
            return jax.nn.relu(y + res), [s1, s2, s3, s4]

        x, block_stats = block(x, flat[at:at + n_leaves])
        stats += block_stats
        at += n_leaves
    w, b = flat[at:at + 2]
    logits = q[1](jnp.matmul(q[0](jnp.mean(x, (1, 2))), q[0](w).T,
                             precision="highest")) + b
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, stats


def sgd(p, g, m, opt):
    """The program's SGD: decay added to the gradient, momentum on the
    scaled step."""
    m = opt["momentum"] * m - opt["learning_rate"] * (g + opt["wd"] * p)
    return p + m, m


def follow(config, params0, batches, seed, *, precision="float32",
           rows=None):
    """Train `len(batches)` steps from `params0` on `batches` and return
    what the comparison reads: each step's loss, the per-leaf norm of
    the first gradient as the optimizer's momentum holds it (gradient
    plus decay), the per-leaf norm of the parameters' change after the
    last step.  `rows` keeps only the first `rows` rows of every batch
    (the half-batch fault).  `seed` is unused: the model draws nothing."""
    del seed
    opt = config["assumed"]["optimizer"]
    flags = trainable(config)
    momentum_bn = config["assumed"]["batch_norm_momentum"]
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        loss_and_stats, config=config, precision=precision), has_aux=True))

    @jax.jit
    def update(params, grads, moms, stats):
        stats = iter(stats)
        new_p, new_m, bn_leaf = [], [], 0
        for p, g, m, tr in zip(params, grads, moms, flags):
            if tr:
                p2, m2 = sgd(p, g, m, opt)
                bn_leaf = 0
            else:
                # running mean, then running var, of the batch norm whose
                # gamma and beta came just before
                if bn_leaf == 0:
                    mean_var = next(stats)
                p2 = p * momentum_bn + mean_var[bn_leaf] * (1 - momentum_bn)
                m2 = m
                bn_leaf += 1
            new_p.append(p2)
            new_m.append(m2)
        return new_p, new_m

    norms = jax.jit(lambda leaves: jnp.stack(
        [jnp.linalg.norm(x.ravel()) for x in leaves]))
    params = list(params0)
    moms = [jnp.zeros_like(p) for p in params]
    losses, grad_norms = [], None
    for t, (images, labels) in enumerate(batches):
        n = rows or images.shape[0]
        (loss, stats), grads = grad_fn(
            params, jnp.asarray(images[:n], jnp.float32),
            jnp.asarray(labels[:n]).astype(jnp.int32))
        losses.append(float(loss))
        params, moms = update(params, grads, moms, stats)
        if t == 0:
            # -m1 / lr = g1 + wd * w0 on the trainable leaves
            grad_norms = np.asarray(norms(moms)) / opt["learning_rate"]
    change = np.asarray(norms([a - c for a, c in zip(params, params0)]))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}

"""Fused conv1x1+BN+ReLU ops (the cuDNN fused-op era, tpu-style).

Ref: src/operator/nn/batch_norm.cu + cudnn
CUDNN_FUSED_SCALE_BIAS_ACTIVATION_CONV_BNSTATS — the reference's fused
scale-bias-act-conv-bnstats kernels.  XLA keeps BN's stats and
normalize passes as separate HBM round trips; these ops fuse them into
the 1x1 convolutions' matmuls via the Pallas kernels in
ops/pallas/conv_fused.py (never timed on a chip: ROADMAP S3).

Two ops, chained by the model block (gluon model_zoo BottleneckV1 under
``MXTPU_CONV_EPILOGUE=pallas``, NHWC only):

- ``_contrib_conv1x1_bn_act``: 1x1 conv (optionally consuming the
  previous BN's normalize+ReLU fused into its input read) whose
  epilogue computes THIS layer's BN statistics; outputs the RAW conv
  activation plus the folded (scale, shift) for the next consumer and
  the updated moving stats.
- ``_contrib_bn_fold``: stats + affine folding WITHOUT materializing a
  normalized activation (for 3x3 convs that stay on the XLA conv path
  but whose consumer is a fused 1x1).

Gradients flow through scale/shift back into the producing stats
(standard train-mode BN autodiff, composed from the kernels' custom
VJPs).  Off-TPU or on non-tiling shapes the kernels fall back to jnp
reference forms, so these ops are correct everywhere and fast where it
matters.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .registry import register


def _fold_stats(s, q, n, gamma, beta, moving_mean, moving_var, *, eps,
                momentum, fix_gamma, train):
    """(scale, shift, new_mm, new_mv) from epilogue sums (train) or the
    moving stats (eval).  Mirrors ops/nn._k_batch_norm's math."""
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if train:
        mean = (s / n).reshape(-1)
        var = jnp.maximum((q / n).reshape(-1) - jnp.square(mean), 0.0)
        new_mm = moving_mean * momentum \
            + mean.astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum \
            + var.astype(moving_var.dtype) * (1 - momentum)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
    scale = g.astype(jnp.float32) * lax.rsqrt(var + eps)
    shift = beta.astype(jnp.float32) - mean * scale
    return (scale, shift, lax.stop_gradient(new_mm),
            lax.stop_gradient(new_mv))


def _k_conv1x1_bn_act(data, weight, gamma, beta, moving_mean, moving_var,
                      in_scale=None, in_shift=None, *, stride=1, eps=1e-5,
                      momentum=0.9, fix_gamma=False, in_act=True,
                      _train=False):
    """data NHWC (N,H,W,Cin); weight OHWI (Cout,1,1,Cin).

    Returns (y_raw NHWC, scale (Cout,), shift (Cout,), new_moving_mean,
    new_moving_var): y_raw is the UN-normalized conv output; the caller
    (or the next fused op) applies ``y*scale+shift``.  With
    in_scale/in_shift the previous BN's normalize (+ReLU when in_act)
    rides inside this matmul's input read."""
    from .pallas import conv_fused as _cf

    s = int(stride)
    N, H, W, Cin = data.shape
    Cout = weight.shape[0]
    if weight.shape[1] != 1 or weight.shape[2] != 1:
        raise ValueError(
            f"conv1x1_bn_act needs a 1x1 OHWI weight, got {weight.shape}")
    if s > 1:
        data = data[:, ::s, ::s, :]
        H, W = data.shape[1], data.shape[2]
    x2d = data.reshape(N * H * W, Cin)
    w2d = weight.reshape(Cout, Cin).T
    n = x2d.shape[0]

    if _train:
        if in_scale is not None:
            y2d, ss, qq = _cf.bn_act_matmul_stats(
                x2d, in_scale.reshape(1, -1), in_shift.reshape(1, -1),
                w2d, bool(in_act))
        else:
            y2d, ss, qq = _cf.matmul_bn_stats(x2d, w2d)
    else:
        ss = qq = None
        if in_scale is not None:
            y2d = _cf.bn_act_matmul(
                x2d, in_scale.reshape(1, -1), in_shift.reshape(1, -1),
                w2d, bool(in_act))
        else:
            y2d = jnp.dot(x2d, w2d,
                          preferred_element_type=jnp.float32
                          ).astype(x2d.dtype)
    scale, shift, new_mm, new_mv = _fold_stats(
        ss, qq, n, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, fix_gamma=fix_gamma, train=bool(_train))
    return (y2d.reshape(N, H, W, Cout), scale, shift, new_mm, new_mv)


register("_contrib_conv1x1_bn_act", _k_conv1x1_bn_act,
         arg_names=("data", "weight", "gamma", "beta", "moving_mean",
                    "moving_var", "in_scale", "in_shift"),
         aliases=("conv1x1_bn_act",), train_aware=True, num_outputs=5,
         mutate_aux=((4, 3), (5, 4)),
         doc=_k_conv1x1_bn_act.__doc__)


def _k_bn_fold(data, gamma, beta, moving_mean, moving_var, *, eps=1e-5,
               momentum=0.9, fix_gamma=False, _train=False):
    """Fold BN into (scale, shift) WITHOUT writing a normalized copy of
    ``data`` (channel-last input).  Train mode computes batch stats in
    one pass (the pallas bn_stats kernel when shapes allow); the
    consumer applies ``data*scale+shift`` — typically fused into a 1x1
    conv's input read via _contrib_conv1x1_bn_act."""
    C = data.shape[-1]
    n = data.size // C
    if _train:
        x2d = data.reshape(n, C)
        from .pallas import batch_norm as _pbn
        from .pallas.conv_fused import _use_pallas

        # same gate as the sibling kernels: off-TPU the pallas stats
        # kernel fails at XLA lowering, so only dispatch it when the
        # backend gate and shape support both say yes
        if _use_pallas() and _pbn.stats_supported(n, C):
            ss, qq = _pbn.bn_stats(x2d)
        else:
            xf = x2d.astype(jnp.float32)
            ss = jnp.sum(xf, axis=0, keepdims=True)
            qq = jnp.sum(xf * xf, axis=0, keepdims=True)
    else:
        ss = qq = None
    return _fold_stats(ss, qq, n, gamma, beta, moving_mean, moving_var,
                       eps=eps, momentum=momentum, fix_gamma=fix_gamma,
                       train=bool(_train))


register("_contrib_bn_fold", _k_bn_fold,
         arg_names=("data", "gamma", "beta", "moving_mean", "moving_var"),
         aliases=("bn_fold",), train_aware=True, num_outputs=4,
         mutate_aux=((3, 2), (4, 3)),
         doc=_k_bn_fold.__doc__)

"""NN operator family: conv, pooling, norms, FC, activations, dropout.

Ref: src/operator/nn/ (convolution.*, fully_connected.*, batch_norm.*,
layer_norm.*, pooling.*, activation.*, dropout.*, softmax.*, lrn.*,
cudnn/*) — re-emitted as XLA HLO.  Convs lower to
``lax.conv_general_dilated`` (MXU systolic-array path — the cuDNN
equivalent is the XLA:TPU conv emitter), FC to ``dot``, norms to fused
elementwise chains XLA folds into neighbouring matmuls.

Layout note: MXNet is NCHW/OIHW.  We keep NCHW at the API boundary for
parity; XLA:TPU internally relayouts to its preferred tiling, so this
costs nothing at steady state.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import profiler
from ..base import getenv
from .registry import register

# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected.cc)


def _low_precision(dt):
    return dt in (jnp.bfloat16, jnp.float16)


def _amp_in(data, weight):
    # AMP cast insertion (ref: contrib/amp cast lists): low-precision
    # weights pull the activation down to the compute dtype
    if _low_precision(weight.dtype) and data.dtype != weight.dtype:
        return data.astype(weight.dtype)
    return data


def _k_fully_connected(data, weight, bias=None, *, num_hidden,
                       no_bias=False, flatten=True):
    data = _amp_in(data, weight)
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    out = jnp.dot(x, weight.T)
    if not no_bias and bias is not None:
        out = out + bias.astype(out.dtype)
    return out

register("FullyConnected", _k_fully_connected,
         arg_names=("data", "weight", "bias"), aliases=("fully_connected",))

# ---------------------------------------------------------------------------
# Convolution (ref: src/operator/nn/convolution.cc + cudnn_convolution)


_CONV_DIMS = {1: ("NCW", "OIW", "NCW"),
              2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}


def _conv_layouts(layout, nd):
    """(data_layout, weight_layout) for a layout string.

    Channel-last layouts (NHWC & co — the TPU-preferred form: channel
    minormost matches the MXU/VPU (8,128) tiling, so per-channel BN
    reductions and conv relayouts vanish) use OHWI weights, matching the
    reference's NHWC convention (src/operator/nn/convolution.cc layout
    param).
    """
    if not layout:
        layout = _CONV_DIMS[nd][0]
    spatial = layout.replace("N", "").replace("C", "")
    if layout.endswith("C"):
        return layout, "O" + spatial + "I"
    return layout, "OI" + spatial


def _k_convolution(data, weight, bias=None, *, kernel, stride=(), dilate=(),
                   pad=(), num_filter=0, num_group=1, no_bias=False,
                   layout=None, cudnn_tune=None, cudnn_off=False,
                   workspace=1024):
    nd = len(kernel)
    stride = stride or (1,) * nd
    dilate = dilate or (1,) * nd
    pad = pad or (0,) * nd
    data = _amp_in(data, weight)
    dl, wl = _conv_layouts(layout, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, (dl, wl, dl))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group,
        preferred_element_type=None)
    if not no_bias and bias is not None:
        bshape = [1] * (nd + 2)
        bshape[dl.index("C")] = -1
        out = out + bias.astype(out.dtype).reshape(bshape)
    return out

register("Convolution", _k_convolution,
         arg_names=("data", "weight", "bias"),
         aliases=("convolution", "Convolution_v1"))


def _k_deconvolution(data, weight, bias=None, *, kernel, stride=(),
                     dilate=(), pad=(), adj=(), num_filter=0, num_group=1,
                     no_bias=True, target_shape=(), layout=None,
                     cudnn_tune=None, cudnn_off=False, workspace=1024):
    nd = len(kernel)
    if layout and layout.endswith("C"):
        raise ValueError(
            "Deconvolution supports channel-first layouts only; "
            f"got layout={layout!r} (channel-last deconv weights/"
            "grouping are not implemented)")
    stride = stride or (1,) * nd
    dilate = dilate or (1,) * nd
    pad = pad or (0,) * nd
    adj = adj or (0,) * nd
    # Transposed conv = gradient of conv w.r.t. input.  weight layout is
    # (in_c, out_c/groups, *k) in MXNet deconv; lax.conv_transpose wants IO
    # swapped relative to conv.
    pads = [(k + (k - 1) * (d - 1) - 1 - p,
             k + (k - 1) * (d - 1) - 1 - p + a)
            for k, d, p, a in zip(kernel, dilate, pad, adj)]
    if num_group > 1:
        xs = jnp.split(data, num_group, axis=1)
        ws = jnp.split(weight, num_group, axis=0)
        outs = [_deconv1(x, w, stride, pads, dilate) for x, w in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = _deconv1(data, weight, stride, pads, dilate)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


def _deconv1(x, w, stride, pads, dilate):
    nd = w.ndim - 2
    dn = lax.conv_dimension_numbers(
        x.shape, (w.shape[1], w.shape[0]) + w.shape[2:], _CONV_DIMS[nd])
    # flip spatial dims and swap i/o channels: transpose conv as dilated conv
    wt = jnp.swapaxes(w, 0, 1)
    wt = jnp.flip(wt, axis=tuple(range(2, 2 + nd)))
    return lax.conv_general_dilated(
        x, wt, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn)

register("Deconvolution", _k_deconvolution,
         arg_names=("data", "weight", "bias"), aliases=("deconvolution",))

# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc)


def _pool_out_pad(in_size, k, s, p, convention):
    import math

    if convention == "full":
        out = int(math.ceil((in_size + 2 * p - k) / s)) + 1
        needed = (out - 1) * s + k - in_size - p
        return p, max(needed, p)
    return p, p


def _k_pooling(data, *, kernel=(), pool_type="max", stride=(), pad=(),
               global_pool=False, pooling_convention="valid",
               count_include_pad=True, cudnn_off=False, p_value=2,
               layout=None):
    nd = data.ndim - 2
    channel_last = bool(layout) and layout.endswith("C")
    sp0 = 1 if channel_last else 2  # first spatial dim index
    if global_pool:
        axes = tuple(range(sp0, sp0 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(data, axis=axes, keepdims=True)
        if pool_type == "lp":
            return jnp.sum(jnp.abs(data) ** p_value, axis=axes,
                           keepdims=True) ** (1.0 / p_value)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = tuple(kernel)
    stride = tuple(stride) or (1,) * nd
    pad = tuple(pad) or (0,) * nd
    sp_pads = [
        _pool_out_pad(data.shape[sp0 + i], kernel[i], stride[i], pad[i],
                      pooling_convention)
        for i in range(nd)
    ]
    if channel_last:
        pads = [(0, 0)] + sp_pads + [(0, 0)]
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        pads = [(0, 0), (0, 0)] + sp_pads
        window = (1, 1) + kernel
        strides = (1, 1) + stride

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        total = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return total
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return total / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return total / counts
    if pool_type == "lp":
        powed = jnp.abs(data) ** p_value
        total = lax.reduce_window(powed, 0.0, lax.add, window, strides, pads)
        return total ** (1.0 / p_value)
    raise ValueError(pool_type)

register("Pooling", _k_pooling, aliases=("pooling", "Pooling_v1"))

# ---------------------------------------------------------------------------
# Normalization (ref: batch_norm.cc, layer_norm.cc, instance_norm.cc,
# l2_normalization.cc, lrn.cc)


def _bn_stats_use_pallas():
    """Opt-in one-pass Pallas BN stats (MXTPU_BN_STATS=pallas).

    Measured on v5e: XLA's two reduce fusions beat the Pallas kernel
    for ResNet-50's many small-per-call BNs (pallas_call launch
    overhead x 106 calls/step outweighs the saved HBM pass), so the
    default stays jnp; the kernel remains available for workloads with
    few, huge BNs.
    """
    from ..base import getenv

    return getenv("BN_STATS", "jnp").lower() == "pallas"


def _bn_fused_enabled():
    """Hand-written BN train fwd/bwd (default on; MXTPU_BN_FUSED=0
    reverts to the autodiff path, and the explicit MXTPU_BN_STATS=pallas
    opt-in takes precedence so the Pallas stats kernel stays
    A/B-testable).

    Profiled on the real v5e (ResNet-50 bs=128 NHWC bf16): convolutions
    were only ~8ms of a 45ms step — the rest was BN activation traffic,
    XLA emitting SEPARATE reduce fusions for mean / E[x^2] forward and
    for each backward sum (multiply_reduce 14.6ms + convert_reduce
    8.1ms per step).  The fused path makes each direction read the big
    activation the minimum number of times: one variadic lax.reduce
    for (sum, sum_sq) forward, one for (sum_dy, sum_dy*(x-mean))
    backward, and the closed-form dx as a single elementwise pass.
    """
    from ..base import getenv

    return getenv("BN_FUSED", "1") != "0" and not _bn_stats_use_pallas()


def _bn_train_impl(x, g32, b32, eps, red, axis_name):
    n = 1.0
    for i in red:
        n *= x.shape[i]
    # forward stats: ONE variadic-reduce pass for both moments.  The
    # stats input is a materialized conv output with no elementwise
    # producer to fuse, so the variadic form only saves a pass here
    # (backward is different — see _bn_train_bwd).
    xf = x.astype(jnp.float32)
    s, q = lax.reduce((xf, xf * xf),
                      (jnp.float32(0), jnp.float32(0)),
                      lambda a, v: (a[0] + v[0], a[1] + v[1]),
                      dimensions=red)
    mean, sq = s / n, q / n
    if axis_name:
        mean, sq = lax.pmean((mean, sq), axis_name)
    var = jnp.maximum(sq - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + eps)
    scale = g32 * inv
    shift = b32 - mean * scale
    shape = [1 if i in red else d for i, d in enumerate(x.shape)]
    out = x * scale.astype(x.dtype).reshape(shape) \
        + shift.astype(x.dtype).reshape(shape)
    return out, mean, var, inv


def _bn_train_bwd(eps, red, axis_name, res, cts):
    x, g32, mean, inv = res
    dy = cts[0]  # mean/var outputs feed the stop-gradient'ed EMA only
    n = 1.0
    for i in red:
        n *= x.shape[i]
    shape = [1 if i in red else d for i, d in enumerate(x.shape)]
    dyf = dy.astype(jnp.float32)
    xm = x.astype(jnp.float32) - mean.reshape(shape)
    # the two backward sums as plain sibling reductions: XLA keeps its
    # normal producer fusion (ReLU-grad selects etc. fold into the
    # reduce inputs; a hand-forced variadic lax.reduce measurably broke
    # that fusion structure on the TPU backend — see git history)
    sum_dy = jnp.sum(dyf, axis=red)
    sum_dy_xm = jnp.sum(dyf * xm, axis=red)
    if axis_name:
        sum_dy, sum_dy_xm = lax.pmean((sum_dy, sum_dy_xm), axis_name)
    dbeta = sum_dy
    dgamma = inv * sum_dy_xm
    # dx = (g*inv) * (dy - sum_dy/n - (x-mean)*inv^2 * sum_dy_xm/n)
    k1 = (g32 * inv).reshape(shape)
    k2 = (sum_dy / n).reshape(shape)
    k3 = (inv * inv * sum_dy_xm / n).reshape(shape)
    dx = (k1 * (dyf - k2 - xm * k3)).astype(x.dtype)
    return dx, dgamma, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train_fused(x, g32, b32, eps, red, axis_name):
    """(out, mean, var) with the closed-form backward below.

    Autodiff of the stats graph emits one reduction per differentiated
    intermediate (~4 passes over the activation backward); the closed
    form needs exactly two (sum_dy, sum_dy*(x-mean)) plus one
    elementwise dx pass.  mean/var outputs carry no gradient — the
    caller stop_gradients them into the moving-stat EMA."""
    out, mean, var, _ = _bn_train_impl(x, g32, b32, eps, red, axis_name)
    return out, mean, var


def _bn_train_fused_fwd(x, g32, b32, eps, red, axis_name):
    out, mean, var, inv = _bn_train_impl(x, g32, b32, eps, red, axis_name)
    return (out, mean, var), (x, g32, mean, inv)


_bn_train_fused.defvjp(_bn_train_fused_fwd, _bn_train_bwd)


def _k_batch_norm(data, gamma, beta, moving_mean, moving_var, *,
                  eps=1e-3, momentum=0.9, fix_gamma=True,
                  use_global_stats=False, output_mean_var=False, axis=1,
                  cudnn_off=False, axis_name=None, _train=False):
    """Returns (out, new_moving_mean, new_moving_var).

    Functional form of the reference's stateful BatchNorm: the caller (nd
    wrapper or gluon layer) commits the updated moving stats.  Cross-
    replica sync-BN: pass ``axis_name`` to pmean the fp32 (mean, E[x^2])
    stats over a shard_map/pmap axis (_contrib_SyncBatchNorm wraps this);
    under GSPMD a sharded batch axis already reduces globally.
    """
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    axis = axis % data.ndim  # normalize negative axis (NHWC uses -1)
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]

    # stats math in fp32 even for bf16 activations (AMP-correct split;
    # the reference's cuDNN BN does the same).  The fp32 part touches
    # only per-channel [C] tensors: the big activation is read ONCE in
    # its own dtype by the stats reduction (XLA fuses the upcast into
    # the reduce) and the normalize is a per-channel scale/shift applied
    # in the data dtype, so it fuses with neighbouring bf16 ops instead
    # of materializing an fp32 copy of the activation.
    if _train and not use_global_stats and _bn_fused_enabled():
        out, mean, var = _bn_train_fused(
            data, g.astype(jnp.float32), beta.astype(jnp.float32),
            float(eps), red, axis_name)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) \
            * (1 - momentum)
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) \
            * (1 - momentum)
        return (out, lax.stop_gradient(new_mm),
                lax.stop_gradient(new_mv))
    if _train and not use_global_stats:
        n = 1.0
        for i in red:
            n *= data.shape[i]
        mean = sumsq_mean = None
        if axis == data.ndim - 1 and _bn_stats_use_pallas():
            from .pallas import batch_norm as _pbn

            M = int(n)
            C = data.shape[-1]
            if _pbn.stats_supported(M, C):
                # one-pass fused stats kernel: XLA's two separate
                # reduce fusions for mean / mean(x^2) were ~half the
                # ResNet-50 step (see ops/pallas/batch_norm.py)
                s, q = _pbn.bn_stats(data.reshape(M, C))
                mean, sumsq_mean = s / n, q / n
        if mean is None:
            mean = jnp.mean(data, axis=red, dtype=jnp.float32)
            sumsq_mean = jnp.mean(jnp.square(data), axis=red,
                                  dtype=jnp.float32)
        if axis_name:
            mean, sumsq_mean = lax.pmean((mean, sumsq_mean), axis_name)
        # E[x^2]-E[x]^2 can cancel slightly negative in fp32; clamp so
        # rsqrt(var+eps) can't NaN on near-constant channels
        var = jnp.maximum(sumsq_mean - jnp.square(mean), 0.0)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) \
            * (1 - momentum)
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) \
            * (1 - momentum)
    else:
        mean, var = (moving_mean.astype(jnp.float32),
                     moving_var.astype(jnp.float32))
        new_mm, new_mv = moving_mean, moving_var
    scale = g.astype(jnp.float32) * lax.rsqrt(var + eps)
    shift = beta.astype(jnp.float32) - mean * scale
    out = data * scale.astype(data.dtype).reshape(shape) \
        + shift.astype(data.dtype).reshape(shape)
    return (out, lax.stop_gradient(new_mm),
            lax.stop_gradient(new_mv))


register("BatchNorm", _k_batch_norm,
         arg_names=("data", "gamma", "beta", "moving_mean", "moving_var"),
         aliases=("batch_norm", "BatchNorm_v1"), train_aware=True,
         num_outputs=3, mutate_aux=((3, 1), (4, 2)))


def _k_layer_norm(data, gamma, beta, *, axis=-1, eps=1e-5,
                  output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)

register("LayerNorm", _k_layer_norm, arg_names=("data", "gamma", "beta"),
         aliases=("layer_norm",))


def _k_instance_norm(data, gamma, beta, *, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return ((data - mean) * lax.rsqrt(var + eps)) * gamma.reshape(shape) \
        + beta.reshape(shape)

register("InstanceNorm", _k_instance_norm,
         arg_names=("data", "gamma", "beta"), aliases=("instance_norm",))


def _k_group_norm(data, gamma, beta, *, num_groups=1, eps=1e-5):
    """gamma/beta are PER GROUP, shape (num_groups,) — the reference's
    group_norm.cc convention (not per channel)."""
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    gshape = (1, num_groups) + (1,) * (x.ndim - 2)
    x = x * gamma.reshape(gshape) + beta.reshape(gshape)
    return x.reshape(data.shape)

register("GroupNorm", _k_group_norm, arg_names=("data", "gamma", "beta"))


def _k_l2_normalization(data, *, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        keep = True
    elif mode == "channel":
        red, keep = (1,), True
    else:  # spatial
        red = tuple(range(2, data.ndim))
        keep = True
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=keep) + eps)
    return data / norm

register("L2Normalization", _k_l2_normalization)


def _k_lrn(data, *, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(padded[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + alpha * acc / nsize, beta)

register("LRN", _k_lrn)

# ---------------------------------------------------------------------------
# Activations (ref: activation.cc, leaky_relu.cc)


def _k_activation(data, *, act_type):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    raise ValueError(act_type)

register("Activation", _k_activation, aliases=("activation",))


def _k_leaky_relu(data, gamma=None, *, act_type="leaky", slope=0.25,
                  lower_bound=0.125, upper_bound=0.334, _train=False):
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data > 0, data, a * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) \
            if gamma.ndim == 1 and data.ndim > 2 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(act_type)

register("LeakyReLU", _k_leaky_relu, arg_names=("data", "gamma"),
         train_aware=True)

# ---------------------------------------------------------------------------
# Softmax family (ref: softmax.cc, softmax_output.cc)


def _k_softmax(data, *, axis=-1, temperature=None, length=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)

register("softmax", _k_softmax, aliases=("SoftmaxActivation",))


def _k_log_softmax(data, *, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)

register("log_softmax", _k_log_softmax)


def _k_softmin(data, *, axis=-1):
    return jax.nn.softmax(-data, axis=axis)

register("softmin", _k_softmin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_output_core(data, label, opts):
    return jax.nn.softmax(data, axis=opts[5])


def _smo_fwd(data, label, opts):
    p = jax.nn.softmax(data, axis=opts[5])
    return p, (p, label)


def _smo_bwd(opts, res, g):
    """MXNet loss-op semantics (ref softmax_output-inl.h): grad w.r.t.
    data is (p - onehot(label)) with grad_scale / ignore_label /
    normalization / label smoothing applied, independent of the
    incoming cotangent."""
    grad_scale, ignore_label, use_ignore, normalization, smooth_alpha, \
        axis = opts
    p, label = res
    C = p.shape[axis]
    lab_ids = None
    if label.ndim == p.ndim - 1:
        lab_ids = label.astype(jnp.int32)
        oh = jax.nn.one_hot(lab_ids, C, axis=axis, dtype=p.dtype)
    else:
        oh = label
    if smooth_alpha > 0:
        oh = oh * (1.0 - smooth_alpha) + (1.0 - oh) * \
            (smooth_alpha / max(C - 1, 1))
    grad = p - oh
    valid = None
    if use_ignore and lab_ids is not None:
        valid = (lab_ids != int(ignore_label)).astype(p.dtype)
        grad = grad * jnp.expand_dims(valid, axis=axis)
    if normalization == "batch":
        grad = grad / p.shape[0]
    elif normalization == "valid":
        n = valid.sum() if valid is not None else \
            float(lab_ids.size if lab_ids is not None else p.shape[0])
        grad = grad / jnp.maximum(n, 1.0)
    # 'null': no normalization (reference default; Module folds 1/batch
    # into the optimizer's rescale_grad instead)
    return grad * grad_scale, jnp.zeros_like(label)


_softmax_output_core.defvjp(_smo_fwd, _smo_bwd)


def _k_softmax_output(data, label, *, grad_scale=1.0, ignore_label=-1.0,
                      multi_output=False, use_ignore=False,
                      preserve_shape=False, normalization="null",
                      out_grad=False, smooth_alpha=0.0):
    if normalization not in ("null", "batch", "valid"):
        raise ValueError(f"SoftmaxOutput normalization must be one of "
                         f"null/batch/valid, got {normalization!r}")
    axis = -1 if preserve_shape else (1 if data.ndim > 1 else -1)
    opts = (float(grad_scale), float(ignore_label), bool(use_ignore),
            str(normalization), float(smooth_alpha), axis)
    return _softmax_output_core(data, label, opts)

register("SoftmaxOutput", _k_softmax_output, arg_names=("data", "label"),
         aliases=("softmax_output", "Softmax"))
# "Softmax" (capital S) is the reference's deprecated alias of
# SoftmaxOutput; the lowercase activation op keeps the name "softmax"


def _k_linear_regression_output(data, label, *, grad_scale=1.0):
    return _linreg_core(data, label, float(grad_scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _linreg_core(data, label, grad_scale):
    return data


def _linreg_fwd(data, label, grad_scale):
    return data, (data, label)


def _linreg_bwd(grad_scale, res, g):
    # per-example gradients * grad_scale (ref regression_output-inl.h);
    # the 1/batch mean lives in the optimizer's rescale_grad
    data, label = res
    return ((data - label.reshape(data.shape)) * grad_scale,
            jnp.zeros_like(label))


_linreg_core.defvjp(_linreg_fwd, _linreg_bwd)

register("LinearRegressionOutput", _k_linear_regression_output,
         arg_names=("data", "label"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _logreg_core(data, label, grad_scale):
    return jax.nn.sigmoid(data)


def _logreg_fwd(data, label, grad_scale):
    p = jax.nn.sigmoid(data)
    return p, (p, label)


def _logreg_bwd(grad_scale, res, g):
    p, label = res
    return ((p - label.reshape(p.shape)) * grad_scale,
            jnp.zeros_like(label))


_logreg_core.defvjp(_logreg_fwd, _logreg_bwd)


def _k_logistic_regression_output(data, label, *, grad_scale=1.0):
    return _logreg_core(data, label, float(grad_scale))

register("LogisticRegressionOutput", _k_logistic_regression_output,
         arg_names=("data", "label"))


def _k_mae_regression_output(data, label, *, grad_scale=1.0):
    return _mae_core(data, label, float(grad_scale))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mae_core(data, label, grad_scale):
    return data


def _mae_fwd(data, label, grad_scale):
    return data, (data, label)


def _mae_bwd(grad_scale, res, g):
    data, label = res
    return (jnp.sign(data - label.reshape(data.shape)) * grad_scale,
            jnp.zeros_like(label))


_mae_core.defvjp(_mae_fwd, _mae_bwd)

register("MAERegressionOutput", _k_mae_regression_output,
         arg_names=("data", "label"))

# ---------------------------------------------------------------------------
# Dropout (ref: dropout.cc) — needs_rng: wrapper passes a PRNG key.


def _k_dropout(data, key=None, *, p=0.5, mode="training", axes=(),
               _train=False, cudnn_off=False):
    # ref dropout.cc: mode='always' applies dropout regardless of
    # train/predict mode (MC-dropout); 'training' only under autograd.
    if not (_train or mode == "always"):
        return data
    if p <= 0 or key is None:
        return data
    shape = list(data.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep

register("Dropout", _k_dropout, arg_names=("data",), needs_rng=True,
         train_aware=True, aliases=("dropout",))

# ---------------------------------------------------------------------------
# Upsampling / resize (ref: upsampling.cc, bilinear_resize)


def _k_upsampling(data, *, scale, sample_type="nearest", num_args=1,
                  workspace=512):
    n, c, h, w = data.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return jax.image.resize(data, (n, c, h * scale, w * scale), "bilinear")

register("UpSampling", _k_upsampling, variadic=True)


def _k_bilinear_resize(data, *, height=0, width=0, scale_height=None,
                       scale_width=None, mode="size"):
    n, c, h, w = data.shape
    th = height or int(h * scale_height)
    tw = width or int(w * scale_width)
    return jax.image.resize(data, (n, c, th, tw), "bilinear")

register("_contrib_BilinearResize2D", _k_bilinear_resize,
         aliases=("bilinear_resize_2d",))


# ---------------------------------------------------------------------------
# SVMOutput (ref: src/operator/svm_output.cc): identity forward, hinge
# (or squared-hinge) gradient w.r.t. the scores


def _k_svm_output(data, label, *, margin=1.0, regularization_coefficient=1.0,
                  use_linear=False):
    return _svm_core(data, label, float(margin),
                     float(regularization_coefficient), bool(use_linear))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_core(data, label, margin, reg, linear):
    return data

def _svm_fwd(data, label, margin, reg, linear):
    return data, (data, label)

def _svm_bwd(margin, reg, linear, res, g):
    data, label = res
    k = data.shape[1]
    lab = label.astype(jnp.int32).reshape(-1)
    onehot = jax.nn.one_hot(lab, k, dtype=data.dtype)
    score_y = jnp.take_along_axis(data, lab[:, None], axis=1)
    viol = (margin - (score_y - data)) > 0  # margin violated per class
    viol = jnp.logical_and(viol, onehot == 0)
    if linear:
        gj = jnp.where(viol, reg, 0.0).astype(data.dtype)
    else:
        gj = jnp.where(viol, 2.0 * reg * (margin - (score_y - data)),
                       0.0).astype(data.dtype)
    gy = -gj.sum(axis=1, keepdims=True)
    grad = gj + onehot * gy
    return (grad * g, jnp.zeros_like(label))

_svm_core.defvjp(_svm_fwd, _svm_bwd)

register("SVMOutput", _k_svm_output, arg_names=("data", "label"),
         aliases=("svm_output",))


# ---------------------------------------------------------------------------
# decoder building blocks: RMS norm, rotary position embedding, SwiGLU,
# squared ReLU (each its own oracle: the XLA form below is their only
# form), and the two ops that lay a mixer's heads out around its
# kernel, `split_heads` and `merge_heads`, whose XLA form composes the
# others and is the oracle of the kernels in ops/pallas/head_layout.py.


def _rms_normed(data, eps):
    """x / sqrt(mean(x^2) + eps) over the last axis, in float32."""
    x = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * inv


def _k_rms_norm(data, gamma, *, eps=1e-6, zero_centered=False):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis; the
    statistics and the scaling in float32, the result in data's dtype.
    `zero_centered`: the gain is 1 + gamma (gamma starts at 0)."""
    normed = _rms_normed(data, eps)
    gain = gamma.astype(jnp.float32)
    if zero_centered:
        gain = 1.0 + gain
    return (normed * gain).astype(data.dtype)


register("rms_norm", _k_rms_norm, arg_names=("data", "gamma"),
         aliases=("RMSNorm",))


def _k_gated_rms_norm(data, gate, gamma, *, eps=1e-6, group_size=0,
                      gate_first=False):
    """rms_norm(data) * gamma * silu(gate) over the last axis, in
    float32; `gate` has data's shape.  The result in data's dtype.
    `gate_first`: rms_norm(data * silu(gate)) * gamma instead (a Mamba-2
    mixer's norm); `group_size`: the statistics over each group of that
    many channels of the last axis, not over the whole of it."""
    z = jax.nn.silu(gate.astype(jnp.float32))
    x = data.astype(jnp.float32) * z if gate_first else data
    shape = data.shape
    if group_size:
        x = x.reshape(shape[:-1] + (shape[-1] // group_size, group_size))
    out = _rms_normed(x, eps).reshape(shape) * gamma.astype(jnp.float32)
    if not gate_first:
        out = out * z
    return out.astype(data.dtype)


register("gated_rms_norm", _k_gated_rms_norm,
         arg_names=("data", "gate", "gamma"))


def rotary_frequencies(rotary_dim, *, rope_theta=10000.0,
                       rope_type="default", factor=1.0,
                       original_max_position_embeddings=None,
                       beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                       **_ignored):
    """(inverse frequencies, attention factor) of a rotary embedding
    over `rotary_dim` dimensions of a head, made once from a model
    config's rope parameters: `default`, or `yarn` as HF transformers'
    `_compute_yarn_parameters` (frequencies blended between the
    interpolated and the original ones by a linear ramp between the two
    correction dimensions; cos and sin scaled by `attention_factor`,
    0.1 ln(factor) + 1 when not given)."""
    half = rotary_dim // 2
    plain = [rope_theta ** (-2.0 * i / rotary_dim) for i in range(half)]
    if rope_type == "default":
        return tuple(plain), 1.0
    if rope_type != "yarn":
        raise ValueError(f"unknown rope_type {rope_type!r}")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0

    def correction_dim(rotations):
        return rotary_dim * math.log(
            original_max_position_embeddings / (rotations * 2 * math.pi)) \
            / (2 * math.log(rope_theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    freqs = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freqs.append(f / factor * ramp + f * (1.0 - ramp))
    return tuple(freqs), float(attention_factor)


def rotary_tables(seq, width, inv_freq, attention_factor=1.0, offset=0):
    """The float32 (cos, sin) tables, (seq, width), of a rotary
    embedding written x * cos + swap(x) * sin over the first `width`
    dimensions of a head, swap exchanging dimension i with i +
    len(inv_freq): cos 1 and sin 0 on the dimensions that do not
    rotate, the sign of `rotate_half` and `attention_factor` folded in.
    Position t is `offset + t`."""
    half = len(inv_freq)
    still = width - 2 * half
    freq = np.concatenate([inv_freq, inv_freq, np.zeros(still)])
    scale = np.concatenate([np.full(2 * half, attention_factor),
                            np.ones(still)])
    sign = np.concatenate([-np.ones(half), np.ones(half), np.zeros(still)])
    pos = offset + jnp.arange(seq, dtype=jnp.float32)
    angles = pos[:, None] * jnp.asarray(freq, jnp.float32)[None, :]
    cos = jnp.cos(angles) * jnp.asarray(scale, jnp.float32)
    sin = jnp.sin(angles) * jnp.asarray(sign * attention_factor, jnp.float32)
    return cos, sin


def _k_rotary_embedding(data, *, inv_freq, attention_factor=1.0, offset=0):
    """Rotary position embedding of (batch, heads, seq, head_dim) with
    HF's `rotate_half` pairing over the first 2 * len(inv_freq)
    dimensions of a head (dimension i pairs with i + len(inv_freq));
    the rest of the head passes unchanged.  Position t of the sequence
    axis is `offset + t`; angles, cos and sin in float32.

    Computed as x * cos + swap(x) * sin over the WHOLE head
    (`rotary_tables`); swap exchanges the two halves of the rotated
    part by a reshape and a reverse.  (The textbook concatenate of two
    64-wide float32 pieces aborts the TPU compiler's fusion emitter:
    `IsFusibleUnalignedDUS`, PERF.md PR 29.)"""
    half, d = len(inv_freq), data.shape[-1]
    if d % (2 * half):
        raise ValueError(f"rotary_embedding: head size {d} is not a "
                         f"multiple of the {2 * half} rotated dimensions")
    cos, sin = rotary_tables(data.shape[-2], d, inv_freq, attention_factor,
                             offset)
    x = data.astype(jnp.float32)
    blocks = x.reshape(x.shape[:-1] + (d // (2 * half), 2, half))
    swapped = jnp.flip(blocks, axis=-2).reshape(x.shape)
    return (x * cos + swapped * sin).astype(data.dtype)


register("rotary_embedding", _k_rotary_embedding, arg_names=("data",))


def _k_swiglu(data):
    """silu(gate) * up of a (..., 2 * width) input that packs [gate |
    up] along its last axis; the product in float32."""
    gate, up = jnp.split(data.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(gate) * up).astype(data.dtype)


register("swiglu", _k_swiglu, arg_names=("data",))


def _k_squared_relu(data):
    """relu(x)^2, in float32."""
    x = jnp.maximum(data.astype(jnp.float32), 0.0)
    return (x * x).astype(data.dtype)


register("squared_relu", _k_squared_relu, arg_names=("data",))


# ---------------------------------------------------------------------------
# a mixer's heads around its kernel: `split_heads` before, `merge_heads`
# after (the kernels: ops/pallas/head_layout.py)

# every layout op traced: "<op> b<batch> s<seq> h<heads> d<head size>
# <what it does> <dtype>" -> traces, and those of them whose shapes the
# kernels take
_layout_traced = collections.Counter()
_layout_kernel_traced = collections.Counter()


def _layout_path(op, does, batch, seq, heads, size, column, dtype, half=0):
    """Count a trace of a layout op; True where its shapes are the
    kernels' (MXTPU_DISABLE_PALLAS=1 forces the XLA form)."""
    from .pallas import head_layout

    key = (f"{op} b{batch} s{seq} h{heads} d{size} {does} "
           f"{jnp.dtype(dtype).name}")
    _layout_traced[key] += 1
    taken = not getenv("DISABLE_PALLAS", False, bool) and head_layout.admits(
        seq, heads, size, column, dtype, half)
    _layout_kernel_traced[key] += taken
    return taken


def _gain_row(gamma, size, zero_centered=False):
    """A norm's gain as the kernels take it, (1, size) float32; ones
    where there is none."""
    if gamma is None:
        return jnp.ones((1, size), jnp.float32)
    gain = gamma.astype(jnp.float32).reshape(1, size)
    return 1.0 + gain if zero_centered else gain


def _k_split_heads(data, gamma=None, *, heads, head_dim, column=0, norm=None,
                   eps=1e-6, zero_centered=False, scale=1.0, inv_freq=None,
                   attention_factor=1.0, groups=1):
    """The heads a mixer's kernel takes, (batch, heads, seq, head_dim),
    from a projection's output (batch, seq, width) that packs them from
    `column` on (head j at `column + j * head_dim`): each head normed
    over its dimensions (`norm="rms"`: `rms_norm` with gain `gamma`, 1
    + gamma under `zero_centered`; `norm="l2"`: `l2_norm` times
    `scale`; None: as it is), then the rotary embedding of `inv_freq`
    and `attention_factor` (None: no rotation).

    Where the computation is lowered for the TPU and the shapes allow
    it (heads of whole lanes, `column` a multiple of the heads' width,
    a sequence of whole tiles) one Pallas pass reads the packed array
    where it lies and writes the result, with a derivative of its own
    (ops/pallas/head_layout.py); everywhere else this composition of
    reshape, `rms_norm` / `l2_norm`, transpose and
    `rotary_embedding`, which is the kernels' oracle.  `groups` changes
    no value: it says the consumer works on the heads in that many
    groups, one after another (`gated_delta_rule`'s `head_groups`), and
    the kernel then writes the heads group-major in memory, so that the
    consumer's cut into groups is no transposed copy."""
    from .pallas import head_layout

    b, seq, _ = data.shape
    half = len(inv_freq) if inv_freq else 0
    does = (norm or "plain") + (f"+rotary{2 * half}" if half else "")

    def xla(data, gamma):
        x = data[:, :, column:column + heads * head_dim] \
            .reshape(b, seq, heads, head_dim)
        if norm == "rms":
            x = _k_rms_norm(x, gamma, eps=eps, zero_centered=zero_centered)
        x = x.transpose(0, 2, 1, 3)
        if norm == "l2":
            from .linear_attention import _k_l2_norm

            x = _k_l2_norm(x, eps=eps)
            if scale != 1.0:
                x = x * scale
        if half:
            x = _k_rotary_embedding(x, inv_freq=inv_freq,
                                    attention_factor=attention_factor)
        return x

    if not _layout_path("split_heads", does, b, seq, heads, head_dim, column,
                        data.dtype, half):
        return xla(data, gamma)
    if heads % groups:
        raise ValueError(f"split_heads: {heads} heads in {groups} groups")
    spec = head_layout.Split(column, heads, head_dim, norm, eps, scale, half,
                             groups)

    def kernels(data, gamma):
        from ..parallel.mesh import per_batch_shard

        def one_shard(gain, data):
            tables = rotary_tables(
                seq, head_layout.rotated_lanes(head_dim, half), inv_freq,
                attention_factor) if half else ()
            by_group = head_layout.split(spec, gain, data, tables)
            return jnp.moveaxis(by_group, 0, 1).reshape(
                -1, heads, seq, head_dim)

        return per_batch_shard(
            one_shard, _gain_row(gamma, head_dim, zero_centered), data)

    return lax.platform_dependent(data, gamma, tpu=kernels, default=xla)


register("split_heads", _k_split_heads, arg_names=("data", "gamma"))


def _k_merge_heads(data, gate, gamma=None, *, gate_kind="per_head", column=0,
                   eps=1e-6, groups=1):
    """A mixer's kernel output (batch, heads, seq, head_dim) gated and
    laid out for the output projection, (batch, seq, heads *
    head_dim).  `gate_kind="per_head"`: data * sigmoid(gate), gate
    (batch, seq, heads); `"elementwise"`: the same with a gate an
    element, packed in gate (batch, seq, width) from `column` on;
    `"rms_silu"`: `gated_rms_norm` over the head with gain `gamma`, its
    SiLU gate packed the same way.  Sigmoid, SiLU and the norm in
    float32.

    One Pallas pass where the computation is lowered for the TPU and
    the shapes allow it, as `split_heads`; everywhere else this
    composition of `sigmoid` or `gated_rms_norm`, transpose and
    reshape, which is the kernels' oracle.  `groups`, as there: the
    producer wrote the heads in that many groups, and the kernel reads
    them group-major."""
    from .pallas import head_layout

    b, heads, seq, size = data.shape
    if gate_kind not in ("per_head", "elementwise", "rms_silu"):
        raise ValueError(f"merge_heads: gate_kind {gate_kind!r}")
    per_head = gate_kind == "per_head"

    def xla(data, gate, gamma):
        if not per_head:
            gate = gate[:, :, column:column + heads * size]
        gate = gate.reshape(b, seq, heads, -1)
        out = data.transpose(0, 2, 1, 3)
        if gate_kind == "rms_silu":
            out = _k_gated_rms_norm(out, gate, gamma, eps=eps)
        else:
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)) \
                .astype(data.dtype)
        return out.reshape(b, seq, heads * size)

    if not _layout_path("merge_heads", gate_kind, b, seq, heads, size,
                        0 if per_head else column, data.dtype):
        return xla(data, gate, gamma)
    if heads % groups:
        raise ValueError(f"merge_heads: {heads} heads in {groups} groups")
    spec = head_layout.Merge(gate_kind, column, eps)

    def kernels(data, gate, gamma):
        from ..parallel.mesh import per_batch_shard

        def one_shard(gain, data, gate):
            by_group = jnp.moveaxis(data.reshape(
                -1, groups, heads // groups, seq, size), 1, 0)
            return head_layout.merge(spec, gain, by_group, gate)

        return per_batch_shard(one_shard, _gain_row(gamma, size), data, gate)

    return lax.platform_dependent(data, gate, gamma, tpu=kernels,
                                  default=xla)


register("merge_heads", _k_merge_heads, arg_names=("data", "gate", "gamma"))


def head_layout_stats():
    """The `headLayout` profiler section: the `split_heads` and
    `merge_heads` traced since the last reset (a trace a layer's pass,
    as `linearAttention` counts), by op, shape and what the op does:
    `traces`, those whose shapes the kernels take (`kernel_traces`) and
    those that took the XLA form whatever the platform (`xla_traces`);
    `layers` counts the distinct keys."""
    return {"layers": len(_layout_traced), "traces": dict(_layout_traced),
            "kernel_traces": {key: _layout_kernel_traced[key]
                              for key in _layout_traced},
            "xla_traces": {key: n - _layout_kernel_traced[key]
                           for key, n in _layout_traced.items()}}


def reset_head_layout_stats():
    _layout_traced.clear()
    _layout_kernel_traced.clear()


def _layout_table(stats):
    out = ["Head Layout (split_heads / merge_heads traced):"]
    for key in sorted(stats["traces"]):
        out.append(f"  {key}: x{stats['traces'][key]} "
                   f"({stats['kernel_traces'][key]} of shapes the kernels "
                   f"take)")
    return out


profiler.register_section("headLayout", head_layout_stats,
                          reset_head_layout_stats, _layout_table)

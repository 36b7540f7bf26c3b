"""Normalization ops: batch_norm, layer_norm, rms_norm, lrn.

Reference: /root/reference/paddle/fluid/operators/batch_norm_op.cc (NCHW,
inputs X/Scale/Bias/Mean/Variance, outputs Y/MeanOut/VarianceOut/SavedMean/
SavedVariance, running stats out = momentum*running + (1-momentum)*batch),
layer_norm_op.cc (begin_norm_axis flattening, outputs Y/Mean/Variance),
lrn_op.cc (cross-channel local response normalization, MidOut auxiliary).

The reference dispatches cuDNN batch-norm kernels; here each op is a few
jnp reductions that XLA fuses into neighbouring convs. batch_norm's grad uses
the standard closed form over SavedMean/SavedVariance (batch_norm_op.cc
BatchNormGradKernel); layer_norm/lrn grads come from jax.vjp of the forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op, OpSpec, infer_output, same_shape
from .common import G, data_of


# ---------------------------------------------------------------------------
# batch_norm
# ---------------------------------------------------------------------------

def _bn_infer(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        return
    layout = op.attrs.get("data_layout", "NCHW")
    c = x.shape[-1] if layout == "NHWC" else x.shape[1]
    infer_output(op, block, "Y", x.shape, dtype=x.dtype)
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if op.output(slot):
            infer_output(op, block, slot, (c,), dtype=x.dtype)


def _bn_grad_maker(op):
    return [OpSpec("batch_norm_grad",
                   {"X": op.input("X"), "Scale": op.input("Scale"),
                    "SavedMean": op.output("SavedMean"),
                    "SavedVariance": op.output("SavedVariance"),
                    "Y@GRAD": G(op.output("Y"))},
                   {"X@GRAD": G(op.input("X")),
                    "Scale@GRAD": G(op.input("Scale")),
                    "Bias@GRAD": G(op.input("Bias"))},
                   dict(op.attrs))]


def _bn_channel_axis(x, layout):
    if layout == "NHWC":
        return x.ndim - 1
    if layout in (None, "NCHW", "AnyLayout"):
        # 2-D [N, C] inputs (batch_norm after fc) also take axis 1
        return 1
    raise ValueError(f"batch_norm: unsupported data_layout {layout!r}")


def _bn_axes(x, layout):
    c = _bn_channel_axis(x, layout)
    return tuple(i for i in range(x.ndim) if i != c)


def _bn_bshape(x, layout):
    c = _bn_channel_axis(x, layout)
    return tuple(x.shape[c] if i == c else 1 for i in range(x.ndim))


def bn_forward_math(x, scale, bias, running_mean, running_var, eps,
                    momentum, layout, is_test):
    """The batch_norm op's forward math, shared with the fused
    conv2d+bn op's jnp twin (ops/fused_ops.py) so the fused program and
    the unfused chain are BITWISE identical under kernel_tier=jnp.
    Returns (y, new_mean, new_var, saved_mean, saved_var)."""
    from ..core.flags import get_flag

    axes = _bn_axes(x, layout)
    bshape = _bn_bshape(x, layout)

    # stability island: statistics accumulate in float32 straight out of the
    # (possibly bf16) activations — single pass via E[x²]-E[x]², reductions
    # carry an fp32 accumulator (dtype=) so no upcast copy of x is ever
    # materialized; the normalize is one fused elementwise kernel emitting
    # the activation dtype.
    out_dtype = x.dtype

    stat_dtype = jnp.bfloat16 if get_flag("bn_bf16_stats") else jnp.float32
    if is_test:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    else:
        mean = jnp.mean(x, axis=axes, dtype=stat_dtype).astype(jnp.float32)
        if x.dtype == jnp.bfloat16 or stat_dtype == jnp.bfloat16:
            # AMP fast path: single-pass E[x²]-E[x]² with fp32 accumulators
            # (the flax recipe). Two separate jnp reductions beat a variadic
            # lax.reduce here: XLA's specialized column-reduce emitter only
            # kicks in for plain monoid reduces (a variadic (Σx, Σx²) reduce
            # measured 2185 vs 2463 img/s on the flagship bench).
            # Cancellation only bites when |mean|/std exceeds ~3e3, beyond
            # bf16 training regimes.
            mean_sq = jnp.mean(jnp.square(x), axis=axes,
                               dtype=stat_dtype).astype(jnp.float32)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
        else:
            # fp32 path keeps the numerically robust centered two-pass form
            var = jnp.var(x, axis=axes)
        new_mean = momentum * running_mean + (1.0 - momentum) * mean
        new_var = momentum * running_var + (1.0 - momentum) * var

    inv_std = jax.lax.rsqrt(var + eps)
    y = (x.astype(jnp.float32) * (scale * inv_std).reshape(bshape)
         + (bias - mean * scale * inv_std).reshape(bshape)).astype(out_dtype)
    return y, new_mean, new_var, mean, var


@register_op("batch_norm", infer_shape=_bn_infer, grad=_bn_grad_maker)
def batch_norm(ctx):
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale"))
    bias = data_of(ctx.input("Bias"))
    running_mean = data_of(ctx.input("Mean"))
    running_var = data_of(ctx.input("Variance"))
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    layout = ctx.attr("data_layout", "NCHW")

    from ..core.flags import get_flag
    if get_flag("bn_fusion_barrier") or get_flag("bn_fusion_barrier_fwd"):
        # sever the producer conv from the stat reduces (see flags.py)
        x = jax.lax.optimization_barrier(x)

    y, new_mean, new_var, mean, var = bn_forward_math(
        x, scale, bias, running_mean, running_var, eps, momentum, layout,
        bool(ctx.attr("is_test", False)))
    ctx.set_output("Y", y)
    ctx.set_output("MeanOut", new_mean)
    ctx.set_output("VarianceOut", new_var)
    ctx.set_output("SavedMean", mean)
    ctx.set_output("SavedVariance", var)


def bn_backward_math(x, scale, mean, var, dy, eps, layout, is_test):
    """The batch_norm_grad closed form over the saved statistics, shared
    with the fused conv2d+bn grad's jnp twin. Returns (dx, dscale, dbias);
    dx comes back in the activation dtype."""
    axes = _bn_axes(x, layout)
    bshape = _bn_bshape(x, layout)
    m = x.size // x.shape[_bn_channel_axis(x, layout)]

    # float32 stability island mirroring the forward; dX returns in the
    # activation dtype so the bf16 backward chain stays bf16
    out_dtype = x.dtype
    x = x.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    inv_std = jax.lax.rsqrt(var + eps).reshape(bshape)
    xhat = (x - mean.reshape(bshape)) * inv_std
    dbias = jnp.sum(dy, axis=axes)
    dscale = jnp.sum(dy * xhat, axis=axes)
    if is_test:
        dx = dy * scale.reshape(bshape) * inv_std
    else:
        dx = (scale.reshape(bshape) * inv_std / m) * (
            m * dy - dbias.reshape(bshape) - xhat * dscale.reshape(bshape))
    return dx.astype(out_dtype), dscale, dbias


@register_op("batch_norm_grad")
def batch_norm_grad(ctx):
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale"))
    mean = data_of(ctx.input("SavedMean"))
    var = data_of(ctx.input("SavedVariance"))
    dy = data_of(ctx.input("Y@GRAD"))
    eps = ctx.attr("epsilon", 1e-5)
    layout = ctx.attr("data_layout", "NCHW")
    from ..core.flags import get_flag
    if get_flag("bn_fusion_barrier") or get_flag("bn_fusion_barrier_bwd"):
        x, dy = jax.lax.optimization_barrier((x, dy))
    dx, dscale, dbias = bn_backward_math(
        x, scale, mean, var, dy, eps, layout,
        bool(ctx.attr("is_test", False)))
    ctx.set_output("X@GRAD", dx)
    ctx.set_output("Scale@GRAD", dscale)
    ctx.set_output("Bias@GRAD", dbias)


# ---------------------------------------------------------------------------
# layer_norm
# ---------------------------------------------------------------------------

def _ln_compute(x, scale, bias, begin_norm_axis, eps):
    shape = x.shape
    lead = 1
    for s in shape[:begin_norm_axis]:
        lead *= s
    flat = x.reshape(lead, -1)
    mean = jnp.mean(flat, axis=1, keepdims=True)
    var = jnp.var(flat, axis=1, keepdims=True)
    y = (flat - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.reshape(1, -1)
    if bias is not None:
        y = y + bias.reshape(1, -1)
    return y.reshape(shape), mean.reshape(lead), var.reshape(lead)


def _ln_infer(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        return
    bna = op.attrs.get("begin_norm_axis", 1)
    lead = 1
    for s in x.shape[:bna]:
        lead *= s
    infer_output(op, block, "Y", x.shape, dtype=x.dtype)
    for slot in ("Mean", "Variance"):
        if op.output(slot):
            infer_output(op, block, slot, (lead,), dtype=x.dtype)


def _ln_grad_maker(op):
    inputs = {"X": op.input("X"), "Y@GRAD": G(op.output("Y"))}
    outputs = {"X@GRAD": G(op.input("X"))}
    if op.input("Scale"):
        inputs["Scale"] = op.input("Scale")
        outputs["Scale@GRAD"] = G(op.input("Scale"))
    if op.input("Bias"):
        inputs["Bias"] = op.input("Bias")
        outputs["Bias@GRAD"] = G(op.input("Bias"))
    return [OpSpec("layer_norm_grad", inputs, outputs, dict(op.attrs))]


@register_op("layer_norm", infer_shape=_ln_infer, grad=_ln_grad_maker)
def layer_norm(ctx):
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale")) if ctx.has_input("Scale") else None
    bias = data_of(ctx.input("Bias")) if ctx.has_input("Bias") else None
    y, mean, var = _ln_compute(x, scale, bias,
                               ctx.attr("begin_norm_axis", 1),
                               ctx.attr("epsilon", 1e-5))
    ctx.set_output("Y", y)
    ctx.set_output("Mean", mean)
    ctx.set_output("Variance", var)


@register_op("layer_norm_grad")
def layer_norm_grad(ctx):
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale")) if ctx.has_input("Scale") else None
    bias = data_of(ctx.input("Bias")) if ctx.has_input("Bias") else None
    dy = data_of(ctx.input("Y@GRAD"))
    bna = ctx.attr("begin_norm_axis", 1)
    eps = ctx.attr("epsilon", 1e-5)

    args = [x] + ([scale] if scale is not None else []) \
        + ([bias] if bias is not None else [])

    def f(*a):
        s = a[1] if scale is not None else None
        b = a[-1] if bias is not None else None
        return _ln_compute(a[0], s, b, bna, eps)[0]

    _, vjp = jax.vjp(f, *args)
    grads = vjp(dy)
    ctx.set_output("X@GRAD", grads[0])
    if scale is not None:
        ctx.set_output("Scale@GRAD", grads[1])
    if bias is not None:
        ctx.set_output("Bias@GRAD", grads[-1])


# ---------------------------------------------------------------------------
# rms_norm — y = scale * x * rsqrt(mean(x^2, last axis) + eps)
# ---------------------------------------------------------------------------

def _rms_grad_maker(op):
    return [OpSpec("rms_norm_grad",
                   {"X": op.input("X"), "Scale": op.input("Scale"),
                    "Y@GRAD": G(op.output("Y"))},
                   {"X@GRAD": G(op.input("X")),
                    "Scale@GRAD": G(op.input("Scale"))},
                   dict(op.attrs))]


@register_op("rms_norm", infer_shape=same_shape("X", "Y"),
             grad=_rms_grad_maker)
def rms_norm(ctx):
    """Root-mean-square norm over the last axis. The statistic and the
    product are float32 whatever the activation type (a stability island
    under AMP); the result takes the input's type."""
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale")).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      + ctx.attr("epsilon", 1e-6))
    ctx.set_output("Y", (xf * r * scale).astype(x.dtype))


@register_op("rms_norm_grad")
def rms_norm_grad(ctx):
    """With r = rsqrt(mean(x^2) + eps) and g = dy * scale:
    dx = r * (g - x * r^2 * mean(g * x)), dscale = sum(dy * x * r)."""
    x = data_of(ctx.input("X"))
    scale = data_of(ctx.input("Scale"))
    xf = x.astype(jnp.float32)
    dy = data_of(ctx.input("Y@GRAD")).astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                      + ctx.attr("epsilon", 1e-6))
    g = dy * scale.astype(jnp.float32)
    dx = r * (g - xf * (r * r) * jnp.mean(g * xf, axis=-1, keepdims=True))
    dscale = jnp.sum((dy * xf * r).reshape(-1, x.shape[-1]), axis=0)
    ctx.set_output("X@GRAD", dx.astype(x.dtype))
    ctx.set_output("Scale@GRAD", dscale.astype(scale.dtype))


# ---------------------------------------------------------------------------
# lrn (cross-channel local response normalization)
# ---------------------------------------------------------------------------

def _lrn_compute(x, n, k, alpha, beta):
    # mid = k + alpha * sum_{c window n} x^2  (lrn_op.cc MidOut)
    sq = x * x
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, n - 1 - half), (0, 0), (0, 0)))
    windows = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * windows
    return x * mid ** (-beta), mid


def _lrn_grad_maker(op):
    return [OpSpec("lrn_grad",
                   {"X": op.input("X"), "Out@GRAD": G(op.output("Out"))},
                   {"X@GRAD": G(op.input("X"))}, dict(op.attrs))]


@register_op("lrn", infer_shape=same_shape("X", "Out"), grad=_lrn_grad_maker)
def lrn(ctx):
    x = data_of(ctx.input("X"))
    out, mid = _lrn_compute(x, int(ctx.attr("n", 5)), ctx.attr("k", 2.0),
                            ctx.attr("alpha", 1e-4), ctx.attr("beta", 0.75))
    ctx.set_output("Out", out)
    ctx.set_output("MidOut", mid)


@register_op("lrn_grad")
def lrn_grad(ctx):
    x = data_of(ctx.input("X"))
    dy = data_of(ctx.input("Out@GRAD"))
    n, k = int(ctx.attr("n", 5)), ctx.attr("k", 2.0)
    alpha, beta = ctx.attr("alpha", 1e-4), ctx.attr("beta", 0.75)
    _, vjp = jax.vjp(lambda a: _lrn_compute(a, n, k, alpha, beta)[0], x)
    ctx.set_output("X@GRAD", vjp(dy)[0])

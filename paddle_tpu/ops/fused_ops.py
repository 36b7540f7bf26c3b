"""fused_conv2d_bn: the conv+batch_norm(+act) chain as ONE op.

No reference analog — the reference executes conv2d, batch_norm and the
activation as three kernels (cuDNN + BatchNormKernel + relu). Here the
``fluid.fuse_conv_bn`` transpiler pass (fluid/fusion.py) rewrites eligible
conv2d→batch_norm(→relu) chains into this op at build time, and its
lowering picks the execution tier per dispatch:

* **pallas** (kernel_tier resolves to Pallas and the shape is eligible) —
  the fused Pallas kernels (ops/pallas/conv_bn.py): the conv block stays
  VMEM-resident through the statistics, normalize and activation instead
  of three HBM round trips; training backward likewise fuses the relu
  mask, BN grad and both conv gradients into one kernel.
* **jnp twin** (everything else, incl. per-shape fallback with a
  ``fallback_counts`` bump) — literally `_conv2d_compute` +
  `bn_forward_math` + the relu expression, i.e. the SAME jaxprs the
  unfused op chain traces, so ``kernel_tier=jnp`` reproduces the unfused
  program bitwise.

The op carries batch_norm's full output contract (MeanOut/VarianceOut
write back in place, SavedMean/SavedVariance feed the grad) so a fused
program checkpoints and resumes exactly like an unfused one.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.amp import cast_compute
from ..core.registry import register_op, OpSpec, infer_output
from .common import G, data_of
from .conv_ops import _conv_attrs, _conv_df, _conv2d_infer, _conv2d_compute
from .norm_ops import bn_forward_math, bn_backward_math
from .pallas import use_pallas, kernel_span


def _fused_supported(x, w, strides, paddings, dilations, groups, df,
                     backward=False):
    from .pallas import conv_bn as cbk
    return cbk.supported(tuple(x.shape), tuple(w.shape), strides, paddings,
                         dilations, groups, df, x.dtype, backward=backward)


def _fused_conv_bn_infer(op, block):
    _conv2d_infer(op, block)
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    if x is None or w is None or w.shape is None:
        return
    c = int(w.shape[0])
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if op.output(slot):
            infer_output(op, block, slot, (c,), dtype=x.dtype)


def _fused_conv_bn_grad_maker(op):
    return [OpSpec(
        "fused_conv2d_bn_grad",
        {"Input": op.input("Input"), "Filter": op.input("Filter"),
         "Scale": op.input("Scale"), "Bias": op.input("Bias"),
         "SavedMean": op.output("SavedMean"),
         "SavedVariance": op.output("SavedVariance"),
         "Output": op.output("Output"),
         "Output@GRAD": G(op.output("Output"))},
        {"Input@GRAD": G(op.input("Input")),
         "Filter@GRAD": G(op.input("Filter")),
         "Scale@GRAD": G(op.input("Scale")),
         "Bias@GRAD": G(op.input("Bias"))},
        dict(op.attrs))]


@register_op("fused_conv2d_bn", infer_shape=_fused_conv_bn_infer,
             grad=_fused_conv_bn_grad_maker)
def fused_conv2d_bn(ctx):
    x = data_of(ctx.input("Input"))
    w = data_of(ctx.input("Filter"))
    scale = data_of(ctx.input("Scale"))
    bias = data_of(ctx.input("Bias"))
    rm = data_of(ctx.input("Mean"))
    rv = data_of(ctx.input("Variance"))
    strides, paddings, dilations, groups = _conv_attrs(ctx, ctx.attr)
    df = _conv_df(ctx.attr)
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    act = ctx.attr("act", "") or ""
    is_test = bool(ctx.attr("is_test", False))
    x, w = cast_compute(x, w)

    # NOTE conv_space_to_depth and the fused kernels are disjoint by
    # construction: s2d needs k>1 at stride 2, the fused path takes
    # stride 2 only at k=1 — s2d-eligible convs always land on the jnp
    # twin, whose _conv2d_compute applies the rewrite itself
    if use_pallas("conv_bn", _fused_supported(x, w, strides, paddings,
                                              dilations, groups, df)):
        from .pallas import conv_bn as cbk
        if is_test:
            inv = jax.lax.rsqrt(rv.astype(jnp.float32) + eps)
            a = scale.astype(jnp.float32) * inv
            b = bias.astype(jnp.float32) - rm.astype(jnp.float32) * a
            with kernel_span("pallas", "conv_bn"):
                y = cbk.conv_affine_pallas(x, w, a, b, strides, paddings,
                                           act)
            new_mean, new_var, sm, sv = rm, rv, rm, rv
        else:
            with kernel_span("pallas", "conv_bn"):
                y, sm, sv = cbk.conv_bn_train_pallas(
                    x, w, scale, bias, eps, strides, paddings, act)
            new_mean = momentum * rm + (1.0 - momentum) * sm
            new_var = momentum * rv + (1.0 - momentum) * sv
    else:
        with kernel_span("jnp", "conv_bn"):
            z = _conv2d_compute(x, w, strides, paddings, dilations, groups,
                                df)
            y, new_mean, new_var, sm, sv = bn_forward_math(
                z, scale, bias, rm, rv, eps, momentum, df, is_test)
            if act == "relu":
                y = jnp.maximum(y, 0)
    ctx.set_output("Output", y)
    ctx.set_output("MeanOut", new_mean)
    ctx.set_output("VarianceOut", new_var)
    ctx.set_output("SavedMean", sm)
    ctx.set_output("SavedVariance", sv)


@register_op("fused_conv2d_bn_grad")
def fused_conv2d_bn_grad(ctx):
    x = data_of(ctx.input("Input"))
    w = data_of(ctx.input("Filter"))
    scale = data_of(ctx.input("Scale"))
    bias = data_of(ctx.input("Bias"))
    sm = data_of(ctx.input("SavedMean"))
    sv = data_of(ctx.input("SavedVariance"))
    y = data_of(ctx.input("Output"))
    dy = data_of(ctx.input("Output@GRAD"))
    strides, paddings, dilations, groups = _conv_attrs(ctx, ctx.attr)
    df = _conv_df(ctx.attr)
    eps = ctx.attr("epsilon", 1e-5)
    act = ctx.attr("act", "") or ""
    is_test = bool(ctx.attr("is_test", False))
    x, w = cast_compute(x, w)

    sup = (not is_test
           and _fused_supported(x, w, strides, paddings, dilations, groups,
                                df, backward=True))
    if use_pallas("conv_bn", sup):
        from .pallas import conv_bn as cbk
        with kernel_span("pallas", "conv_bn"):
            dx, dw, dscale, dbias = cbk.conv_bn_bwd_pallas(
                x, w, dy.astype(x.dtype), scale, bias, sm, sv, eps, strides,
                paddings, act)
        ctx.set_output("Input@GRAD", dx)
        ctx.set_output("Filter@GRAD", dw)
        ctx.set_output("Scale@GRAD", dscale)
        ctx.set_output("Bias@GRAD", dbias)
        return
    with kernel_span("jnp", "conv_bn"):
        # the unfused chain's exact backward: relu_grad (d·(out>0)) →
        # batch_norm_grad closed form → conv vjp (conv2d_grad's path)
        dy2 = dy * (y > 0) if act == "relu" else dy
        z = _conv2d_compute(x, w, strides, paddings, dilations, groups, df)
        dz, dscale, dbias = bn_backward_math(z, scale, sm, sv, dy2, eps, df,
                                             is_test)
        out, vjp = jax.vjp(
            lambda a, b: _conv2d_compute(a, b, strides, paddings, dilations,
                                         groups, df), x, w)
        dx, dw = vjp(dz.astype(out.dtype))
    ctx.set_output("Input@GRAD", cast_compute(dx))
    ctx.set_output("Filter@GRAD", dw)
    ctx.set_output("Scale@GRAD", dscale)
    ctx.set_output("Bias@GRAD", dbias)

"""Loss & classification ops.

Reference: softmax_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, sigmoid_cross_entropy_with_logits_op.cc,
smooth_l1_loss_op.cc, squared_l2_distance_op.cc, hinge_loss_op.cc,
log_loss_op.cc, huber_loss_op.cc (/root/reference/paddle/fluid/operators/).

cross_entropy semantics follow the reference: labels are either int64 class
ids of shape [N, 1] (hard) or a float distribution [N, D] (soft_label attr).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.amp import upcast_f32
from ..core.registry import register_op, same_shape, OpSpec
from ..obs.metrics import REGISTRY as _METRICS
from .common import G, data_of, like


@register_op("softmax", infer_shape=same_shape("X", "Out"), grad=lambda op: [OpSpec(
    "softmax_grad", {"Out": op.output("Out"), "Out@GRAD": G(op.output("Out"))},
    {"X@GRAD": G(op.input("X"))})])
def softmax(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", like(x, jax.nn.softmax(data_of(x), axis=-1)))


@register_op("softmax_grad")
def softmax_grad(ctx):
    out = data_of(ctx.input("Out"))
    d = data_of(ctx.input("Out@GRAD"))
    dx = out * (d - jnp.sum(d * out, axis=-1, keepdims=True))
    ctx.set_output("X@GRAD", like(ctx.input("Out@GRAD"), dx))


def _take_label(x, label):
    """Pick per-row probability at int label; works for any leading rank
    (dense [N, V] and padded-LoD [b, L, V] layouts alike)."""
    lab = label.reshape(x.shape[:-1]).astype(jnp.int32)
    return jnp.take_along_axis(x, lab[..., None], axis=-1)


@register_op("cross_entropy", grad=lambda op: [OpSpec(
    "cross_entropy_grad",
    {"X": op.input("X"), "Label": op.input("Label"),
     "Y@GRAD": G(op.output("Y"))},
    {"X@GRAD": G(op.input("X"))}, dict(op.attrs))])
def cross_entropy(ctx):
    xv = ctx.input("X")
    x = upcast_f32(data_of(xv))
    label = data_of(ctx.input("Label"))
    eps = 1e-8
    if ctx.attr("soft_label", False):
        y = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        y = -jnp.log(jnp.maximum(_take_label(x, label), eps))
    ctx.set_output("Y", like(xv, y))


@register_op("cross_entropy_grad")
def cross_entropy_grad(ctx):
    xv = ctx.input("X")
    x = data_of(xv)
    label = data_of(ctx.input("Label"))
    d = data_of(ctx.input("Y@GRAD"))
    eps = 1e-8
    if ctx.attr("soft_label", False):
        dx = -d * label / jnp.maximum(x, eps)
    else:
        onehot = jax.nn.one_hot(label.reshape(x.shape[:-1]).astype(jnp.int32),
                                x.shape[-1], dtype=x.dtype)
        dx = -d * onehot / jnp.maximum(x, eps)
    ctx.set_output("X@GRAD", like(xv, dx))


@register_op("softmax_with_cross_entropy", grad=lambda op: [OpSpec(
    "softmax_with_cross_entropy_grad",
    {"Softmax": op.output("Softmax"), "Label": op.input("Label"),
     "Loss@GRAD": G(op.output("Loss"))},
    {"Logits@GRAD": G(op.input("Logits"))}, dict(op.attrs))])
def softmax_with_cross_entropy(ctx):
    """Fused, numerically-stable version (reference
    softmax_with_cross_entropy_op.cc) — on TPU the fusion happens in XLA, but
    we keep the stable log-sum-exp formulation."""
    # float32 stability island: bf16 logits (AMP) are upcast before the LSE
    logits = upcast_f32(data_of(ctx.input("Logits")))
    label = data_of(ctx.input("Label"))
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    log_probs = logits - lse
    if ctx.attr("soft_label", False):
        loss = -jnp.sum(label * log_probs, axis=-1, keepdims=True)
    else:
        loss = -_take_label(log_probs, label)
    ctx.set_output("Softmax", jnp.exp(log_probs))
    ctx.set_output("Loss", loss)


@register_op("softmax_with_cross_entropy_grad")
def softmax_with_cross_entropy_grad(ctx):
    sm = data_of(ctx.input("Softmax"))
    label = data_of(ctx.input("Label"))
    d = data_of(ctx.input("Loss@GRAD"))
    if ctx.attr("soft_label", False):
        dlogits = d * (sm - label)
    else:
        onehot = jax.nn.one_hot(label.reshape(-1).astype(jnp.int32),
                                sm.shape[-1], dtype=sm.dtype)
        dlogits = d * (sm - onehot)
    ctx.set_output("Logits@GRAD", dlogits)


@register_op("sigmoid_cross_entropy_with_logits",
             infer_shape=same_shape("X", "Out"),
             grad=lambda op: [OpSpec(
                 "sigmoid_cross_entropy_with_logits_grad",
                 {"X": op.input("X"), "Label": op.input("Label"),
                  "Out@GRAD": G(op.output("Out"))},
                 {"X@GRAD": G(op.input("X"))})])
def sigmoid_cross_entropy_with_logits(ctx):
    x = data_of(ctx.input("X"))
    label = data_of(ctx.input("Label"))
    out = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set_output("Out", out)


@register_op("sigmoid_cross_entropy_with_logits_grad")
def sigmoid_cross_entropy_with_logits_grad(ctx):
    x = data_of(ctx.input("X"))
    label = data_of(ctx.input("Label"))
    d = data_of(ctx.input("Out@GRAD"))
    ctx.set_output("X@GRAD", d * (jax.nn.sigmoid(x) - label))


@register_op("squared_l2_distance", grad=lambda op: [OpSpec(
    "squared_l2_distance_grad",
    {"sub_result": op.output("sub_result"), "Out@GRAD": G(op.output("Out"))},
    {"X@GRAD": G(op.input("X")), "Y@GRAD": G(op.input("Y"))})])
def squared_l2_distance(ctx):
    x = data_of(ctx.input("X"))
    y = data_of(ctx.input("Y"))
    sub = x - y
    ctx.set_output("sub_result", sub)
    ctx.set_output("Out", jnp.sum(jnp.square(sub), axis=-1, keepdims=True))


@register_op("squared_l2_distance_grad")
def squared_l2_distance_grad(ctx):
    sub = data_of(ctx.input("sub_result"))
    d = data_of(ctx.input("Out@GRAD"))
    g = 2.0 * d * sub
    ctx.set_output("X@GRAD", g)
    # Y may be a broadcast single row (reference squared_l2_distance_op.h)
    dy = -g
    ynames = ctx.op.output("Y@GRAD")
    if ynames and ctx.block.has_var(ynames[0]):
        yshape = ctx.block.var(ynames[0]).shape
        if yshape and yshape[0] == 1 and g.shape[0] != 1:
            dy = -jnp.sum(g, axis=0, keepdims=True)
    ctx.set_output("Y@GRAD", dy)


@register_op("smooth_l1_loss", grad=lambda op: [OpSpec(
    "smooth_l1_loss_grad",
    {"Diff": op.output("Diff"), "Out@GRAD": G(op.output("Out")),
     **({"InsideWeight": op.input("InsideWeight")}
        if op.input("InsideWeight") else {}),
     **({"OutsideWeight": op.input("OutsideWeight")}
        if op.input("OutsideWeight") else {})},
    {"X@GRAD": G(op.input("X"))}, dict(op.attrs))])
def smooth_l1_loss(ctx):
    """smooth_l1_loss_op.h: InsideWeight gates the diff, OutsideWeight
    scales the per-element loss before the row sum (the SSD positive
    mask)."""
    x = data_of(ctx.input("X"))
    y = data_of(ctx.input("Y"))
    sigma2 = ctx.attr("sigma", 1.0) ** 2
    diff = x - y
    if ctx.has_input("InsideWeight"):
        diff = diff * data_of(ctx.input("InsideWeight"))
    ad = jnp.abs(diff)
    val = jnp.where(ad < 1.0 / sigma2, 0.5 * sigma2 * diff * diff,
                    ad - 0.5 / sigma2)
    if ctx.has_input("OutsideWeight"):
        val = val * data_of(ctx.input("OutsideWeight"))
    ctx.set_output("Diff", diff)
    ctx.set_output("Out", jnp.sum(val, axis=tuple(range(1, x.ndim)),
                                  keepdims=False).reshape(-1, 1))


@register_op("smooth_l1_loss_grad")
def smooth_l1_loss_grad(ctx):
    diff = data_of(ctx.input("Diff"))
    d = data_of(ctx.input("Out@GRAD")).reshape((-1,) + (1,) * (diff.ndim - 1))
    sigma2 = ctx.attr("sigma", 1.0) ** 2
    g = jnp.where(jnp.abs(diff) < 1.0 / sigma2, sigma2 * diff, jnp.sign(diff))
    if ctx.has_input("OutsideWeight"):
        g = g * data_of(ctx.input("OutsideWeight"))
    if ctx.has_input("InsideWeight"):
        g = g * data_of(ctx.input("InsideWeight"))
    ctx.set_output("X@GRAD", d * g)


@register_op("log_loss", infer_shape=same_shape("Predicted", "Loss"),
             grad=lambda op: [OpSpec(
                 "log_loss_grad",
                 {"Predicted": op.input("Predicted"), "Labels": op.input("Labels"),
                  "Loss@GRAD": G(op.output("Loss"))},
                 {"Predicted@GRAD": G(op.input("Predicted"))}, dict(op.attrs))])
def log_loss(ctx):
    p = data_of(ctx.input("Predicted"))
    y = data_of(ctx.input("Labels"))
    eps = ctx.attr("epsilon", 1e-4)
    ctx.set_output("Loss", -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps))


@register_op("log_loss_grad")
def log_loss_grad(ctx):
    p = data_of(ctx.input("Predicted"))
    y = data_of(ctx.input("Labels"))
    d = data_of(ctx.input("Loss@GRAD"))
    eps = ctx.attr("epsilon", 1e-4)
    ctx.set_output("Predicted@GRAD", d * (-y / (p + eps) + (1 - y) / (1 - p + eps)))


@register_op("hinge_loss", infer_shape=same_shape("Logits", "Loss"),
             grad=lambda op: [OpSpec(
                 "hinge_loss_grad",
                 {"Logits": op.input("Logits"), "Labels": op.input("Labels"),
                  "Loss@GRAD": G(op.output("Loss"))},
                 {"Logits@GRAD": G(op.input("Logits"))})])
def hinge_loss(ctx):
    x = data_of(ctx.input("Logits"))
    y = data_of(ctx.input("Labels"))
    ctx.set_output("Loss", jnp.maximum(1.0 - (2.0 * y - 1.0) * x, 0.0))


@register_op("hinge_loss_grad")
def hinge_loss_grad(ctx):
    x = data_of(ctx.input("Logits"))
    y = data_of(ctx.input("Labels"))
    d = data_of(ctx.input("Loss@GRAD"))
    alt = 2.0 * y - 1.0
    ctx.set_output("Logits@GRAD", d * jnp.where(1.0 - alt * x > 0, -alt, 0.0))


@register_op("huber_loss", grad=lambda op: [OpSpec(
    "huber_loss_grad",
    {"Residual": op.output("Residual"), "Out@GRAD": G(op.output("Out"))},
    {"X@GRAD": G(op.input("X")), "Y@GRAD": G(op.input("Y"))}, dict(op.attrs))])
def huber_loss(ctx):
    x = data_of(ctx.input("X"))
    y = data_of(ctx.input("Y"))
    delta = ctx.attr("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    out = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    ctx.set_output("Residual", r)
    ctx.set_output("Out", out)


@register_op("huber_loss_grad")
def huber_loss_grad(ctx):
    r = data_of(ctx.input("Residual"))
    d = data_of(ctx.input("Out@GRAD"))
    delta = ctx.attr("delta", 1.0)
    g = jnp.where(jnp.abs(r) <= delta, r, delta * jnp.sign(r))
    ctx.set_output("X@GRAD", -d * g)
    ctx.set_output("Y@GRAD", d * g)


# ---------------------------------------------------------------------------
# hsigmoid (legacy gserver HierarchicalSigmoidLayer; math/MatrixBitCode.cpp
# SimpleCode: c = label + num_classes, node(b) = (c >> (b+1)) - 1,
# bit(b) = (c >> b) & 1, cost = sum_b softplus(z_b) - bit_b * z_b)
# ---------------------------------------------------------------------------

def _hsigmoid_compute(x, w, bias, label, num_classes):
    c = label.reshape(-1).astype(jnp.int32) + num_classes
    max_len = int(num_classes - 1).bit_length()
    cost = jnp.zeros((x.shape[0],), jnp.float32)
    xf = x.astype(jnp.float32)
    for b in range(max_len):
        parent = (c >> (b + 1))
        valid = (parent >= 1).astype(jnp.float32)
        idx = jnp.maximum(parent - 1, 0)
        bit = ((c >> b) & 1).astype(jnp.float32)
        z = jnp.sum(xf * w[idx].astype(jnp.float32), axis=-1)
        if bias is not None:
            z = z + bias.reshape(-1)[idx].astype(jnp.float32)
        cost = cost + valid * (jax.nn.softplus(z) - bit * z)
    return cost.reshape(-1, 1)


@register_op("hsigmoid", grad=lambda op: [OpSpec(
    "hsigmoid_grad",
    {"X": op.input("X"), "W": op.input("W"), "Label": op.input("Label"),
     **({"Bias": op.input("Bias")} if op.input("Bias") else {}),
     "Out@GRAD": G(op.output("Out"))},
    {"X@GRAD": G(op.input("X")), "W@GRAD": G(op.input("W")),
     **({"Bias@GRAD": G(op.input("Bias"))} if op.input("Bias") else {})},
    dict(op.attrs))])
def hsigmoid(ctx):
    """Hierarchical sigmoid cost over the complete-binary-tree SimpleCode
    (reference HierarchicalSigmoidLayer.cpp:127 sumByBitCode +
    MatrixBitCode.cpp)."""
    x = data_of(ctx.input("X"))
    w = data_of(ctx.input("W"))
    label = data_of(ctx.input("Label"))
    bias = data_of(ctx.input("Bias")) if ctx.has_input("Bias") else None
    ctx.set_output("Out", _hsigmoid_compute(
        x, w, bias, label, int(ctx.attr("num_classes"))))


@register_op("hsigmoid_grad")
def hsigmoid_grad(ctx):
    x = data_of(ctx.input("X"))
    w = data_of(ctx.input("W"))
    label = data_of(ctx.input("Label"))
    has_bias = ctx.has_input("Bias")
    bias = data_of(ctx.input("Bias")) if has_bias else None
    d = data_of(ctx.input("Out@GRAD"))
    n = int(ctx.attr("num_classes"))
    args = (x, w) + ((bias,) if has_bias else ())

    def f(*a):
        xx, ww = a[0], a[1]
        bb = a[2] if has_bias else None
        return _hsigmoid_compute(xx, ww, bb, label, n)

    out, vjp = jax.vjp(f, *args)
    grads = vjp(d.astype(out.dtype).reshape(out.shape))
    ctx.set_output("X@GRAD", grads[0])
    ctx.set_output("W@GRAD", grads[1])
    if has_bias:
        ctx.set_output("Bias@GRAD", grads[2])


# ---------------------------------------------------------------------------
# mtp_loss — the main loss plus the multi-token-prediction module's, weighted
# ---------------------------------------------------------------------------


_M_MTP = _METRICS.gauge(
    "paddle_tpu_mtp",
    "the multi-token-prediction objective as last traced: kind=depth the "
    "modules (predicted tokens beyond the next), kind=positions the "
    "positions its loss averages over, kind=loss_weight its weight in the "
    "total",
    labels=("kind",))


def _mtp_loss_grad_maker(op):
    return [OpSpec("mtp_loss_grad",
                   {"MtpLoss": op.input("MtpLoss"),
                    "Out@GRAD": G(op.output("Out"))},
                   {"Loss@GRAD": G(op.input("Loss")),
                    "MtpLoss@GRAD": G(op.input("MtpLoss"))},
                   dict(op.attrs))]


def _mtp_loss_infer(op, block):
    loss = block.var(op.input("Loss")[0])
    for name in op.output("Out"):
        out = block.var(name)
        out.shape, out.dtype = loss.shape, out.dtype or "float32"


@register_op("mtp_loss", infer_shape=_mtp_loss_infer,
             grad=_mtp_loss_grad_maker)
def mtp_loss(ctx):
    """``Out = Loss + loss_weight * mean(MtpLoss)``: the main objective (a
    scalar, of any shape of one element) and the MTP module's cross-entropy
    at every position, float32. Attrs
    ``loss_weight`` (lambda) and ``depth``, which with the positions set
    the gauge ``paddle_tpu_mtp`` at trace time."""
    loss = upcast_f32(data_of(ctx.input("Loss")))
    mtp = upcast_f32(data_of(ctx.input("MtpLoss")))
    weight = float(ctx.attr("loss_weight"))
    _M_MTP.labels(kind="depth").set(int(ctx.attr("depth", 1)))
    _M_MTP.labels(kind="positions").set(int(mtp.size))
    _M_MTP.labels(kind="loss_weight").set(weight)
    ctx.set_output("Out", loss + weight * jnp.mean(mtp))


@register_op("mtp_loss_grad")
def mtp_loss_grad(ctx):
    """``Loss`` takes the gradient as it comes, every position of
    ``MtpLoss`` ``loss_weight / positions`` of it."""
    mtp = data_of(ctx.input("MtpLoss"))
    d = upcast_f32(data_of(ctx.input("Out@GRAD")))
    weight = float(ctx.attr("loss_weight"))
    ctx.set_output("Loss@GRAD", d)
    ctx.set_output("MtpLoss@GRAD", jnp.broadcast_to(
        jnp.sum(d) * (weight / mtp.size), mtp.shape).astype(mtp.dtype))

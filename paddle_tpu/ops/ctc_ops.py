"""CTC ops: warpctc (CTC loss), ctc_align, edit_distance.

Reference: /root/reference/paddle/fluid/operators/warpctc_op.{h,cc} (dynloads
the warp-ctc CUDA library, ragged logits + ragged labels → per-sequence loss;
operators/math/sequence_padding.h converts ragged↔padded for it),
ctc_align_op.h (merge repeated tokens then drop blanks), edit_distance_op.h
(Levenshtein between hypothesis and reference sequences).

TPU-native: the warp-ctc library is replaced by a log-space forward algorithm
(alpha recurrence over the 2U+1 blank-interleaved label sequence) expressed as
ONE masked lax.scan over time for the whole padded batch — XLA fuses it; the
gradient falls out of jax.vjp over the same scan, replacing warp-ctc's
hand-written backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.lod import LoDArray
from ..core.registry import register_op, OpSpec
from .common import G, data_of

_NEG = -1e30


def _ctc_loss(logits, x_lens, labels, y_lens, blank):
    """logits [b, T, C] unnormalized; labels [b, U] int; returns [b, 1].
    Dispatches to the Pallas whole-recurrence kernel under the kernel
    tier (backward always runs the scan path via custom_vjp). T==1
    sequences have no recurrence to fuse and route to the scan path
    (counted fallback)."""
    from .pallas import use_pallas, kernel_span
    if use_pallas("ctc", logits.shape[1] > 1):
        with kernel_span("pallas", "ctc"):
            return _ctc_loss_pallas(logits, x_lens, labels, y_lens, blank)
    return _ctc_loss_scan(logits, x_lens, labels, y_lens, blank)


def _ctc_loss_scan(logits, x_lens, labels, y_lens, blank):
    b, T, C = logits.shape
    U = labels.shape[1]
    S = 2 * U + 1
    logp = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.swapaxes(logp, 0, 1)                       # [T, b, C]

    # blank-interleaved extended labels z: [b, S]
    z = jnp.full((b, S), blank, dtype=jnp.int32)
    z = z.at[:, 1::2].set(labels.astype(jnp.int32))
    s_valid = jnp.arange(S)[None, :] < (2 * y_lens[:, None] + 1)

    # can we skip from s-2 (different label and not blank)?
    z_prev2 = jnp.pad(z, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (jnp.arange(S)[None, :] % 2 == 1) & (z != z_prev2)

    def emit(t_logp, zz):
        return jnp.take_along_axis(t_logp, zz, axis=1)    # [b, S]

    alpha0 = jnp.full((b, S), _NEG)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = emit(logp[0], z)[:, 1]
    alpha0 = alpha0.at[:, 1].set(jnp.where(y_lens > 0, first_lab, _NEG))
    alpha0 = jnp.where(s_valid, alpha0, _NEG)

    def final_of(alpha, ylen):
        last = 2 * ylen            # index of final blank
        a_last = jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0]
        a_lab = jnp.take_along_axis(alpha,
                                    jnp.maximum(last - 1, 0)[:, None],
                                    axis=1)[:, 0]
        a_lab = jnp.where(ylen > 0, a_lab, _NEG)
        return jnp.logaddexp(a_last, a_lab)

    init = dict(alpha=alpha0,
                final=jnp.where(x_lens == 1, final_of(alpha0, y_lens), _NEG))

    def step(c, inp):
        t, lp = inp
        a = c["alpha"]
        a1 = jnp.pad(a, ((0, 0), (1, 0)), constant_values=_NEG)[:, :S]
        a2 = jnp.pad(a, ((0, 0), (2, 0)), constant_values=_NEG)[:, :S]
        a2 = jnp.where(can_skip, a2, _NEG)
        merged = jnp.logaddexp(jnp.logaddexp(a, a1), a2)
        nxt = merged + emit(lp, z)
        nxt = jnp.where(s_valid, nxt, _NEG)
        alive = (t < x_lens)[:, None]
        alpha = jnp.where(alive, nxt, a)
        final = jnp.where(t == x_lens - 1, final_of(alpha, y_lens),
                          c["final"])
        return dict(alpha=alpha, final=final), None

    if T > 1:
        c, _ = jax.lax.scan(step, init, (jnp.arange(1, T), logp[1:]))
    else:
        c = init
    return (-c["final"])[:, None]


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ctc_loss_pallas(logits, x_lens, labels, y_lens, blank):
    """Pallas whole-recurrence CTC forward (alpha VMEM-resident across T,
    the warp-ctc shared-memory pattern, ops/pallas/ctc.ctc_alpha_pallas);
    the emit gather, masks and t=0 init are precomputed here where XLA owns
    them. Backward = jax.vjp of the scan path (custom_vjp)."""
    from .pallas.ctc import ctc_alpha_pallas

    b, T, C = logits.shape
    U = labels.shape[1]
    S = 2 * U + 1
    logp = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.swapaxes(logp, 0, 1)                       # [T, b, C]

    z = jnp.full((b, S), blank, dtype=jnp.int32)
    z = z.at[:, 1::2].set(labels.astype(jnp.int32))
    s_valid = jnp.arange(S)[None, :] < (2 * y_lens[:, None] + 1)
    z_prev2 = jnp.pad(z, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (jnp.arange(S)[None, :] % 2 == 1) & (z != z_prev2)

    alpha0 = jnp.full((b, S), _NEG)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    first_lab = jnp.take_along_axis(logp[0], z, axis=1)[:, 1]
    alpha0 = alpha0.at[:, 1].set(jnp.where(y_lens > 0, first_lab, _NEG))
    alpha0 = jnp.where(s_valid, alpha0, _NEG)

    last = 2 * y_lens
    a_last = jnp.take_along_axis(alpha0, last[:, None], axis=1)[:, 0]
    a_lab = jnp.take_along_axis(alpha0, jnp.maximum(last - 1, 0)[:, None],
                                axis=1)[:, 0]
    a_lab = jnp.where(y_lens > 0, a_lab, _NEG)
    final0 = jnp.where(x_lens == 1, jnp.logaddexp(a_last, a_lab), _NEG)

    sp = max(8, -(-S // 8) * 8)              # pad S to a sublane multiple
    pad = sp - S
    e = jnp.swapaxes(jnp.take_along_axis(
        logp, jnp.broadcast_to(z[None], (T, b, S)), axis=2), 0, 1)
    e = jnp.pad(e, ((0, 0), (0, 0), (0, pad)), constant_values=_NEG)
    a0 = jnp.pad(alpha0, ((0, 0), (0, pad)), constant_values=_NEG)
    cs = jnp.pad(can_skip.astype(logp.dtype), ((0, 0), (0, pad)))
    sv = jnp.pad(s_valid.astype(logp.dtype), ((0, 0), (0, pad)))
    return ctc_alpha_pallas(
        e, a0, final0[:, None].astype(logp.dtype), cs, sv,
        x_lens.astype(jnp.int32).reshape(b, 1),
        y_lens.astype(jnp.int32).reshape(b, 1))


def _ctc_pallas_fwd(logits, x_lens, labels, y_lens, blank):
    return (_ctc_loss_pallas(logits, x_lens, labels, y_lens, blank),
            (logits, x_lens, labels, y_lens))


def _ctc_pallas_bwd(blank, res, ct):
    logits, x_lens, labels, y_lens = res
    _, vjp = jax.vjp(
        lambda lg: _ctc_loss_scan(lg, x_lens, labels, y_lens, blank), logits)
    return (vjp(ct)[0], None, None, None)


_ctc_loss_pallas.defvjp(_ctc_pallas_fwd, _ctc_pallas_bwd)


def _warpctc_grad_maker(op):
    return [OpSpec(
        "warpctc_grad",
        {"Logits": op.input("Logits"), "Label": op.input("Label"),
         "Loss@GRAD": G(op.output("Loss"))},
        {"Logits@GRAD": G(op.input("Logits"))}, dict(op.attrs))]


def _ctc_inputs(ctx):
    lv = ctx.input("Logits")
    if not isinstance(lv, LoDArray):
        raise TypeError("warpctc expects LoD logits")
    lab = ctx.input("Label")
    if not isinstance(lab, LoDArray):
        raise TypeError("warpctc expects a LoD label")
    labels = lab.data
    if labels.ndim == 3:
        labels = labels[..., 0]
    return lv, labels.astype(jnp.int32), lab.lens


@register_op("warpctc", grad=_warpctc_grad_maker)
def warpctc(ctx):
    lv, labels, y_lens = _ctc_inputs(ctx)
    blank = int(ctx.attr("blank", 0))
    # norm_by_times does NOT scale the forward Loss — the reference scales
    # only the logits gradient in the backward kernel (warpctc_op.h:217-223,
    # ScaleLoDTensorFunctor) and returns the unscaled loss.
    loss = _ctc_loss(lv.data, lv.lens, labels, y_lens, blank)
    ctx.set_output("Loss", loss)


@register_op("warpctc_grad")
def warpctc_grad(ctx):
    lv, labels, y_lens = _ctc_inputs(ctx)
    blank = int(ctx.attr("blank", 0))
    d = data_of(ctx.input("Loss@GRAD"))

    def f(lg):
        return _ctc_loss(lg, lv.lens, labels, y_lens, blank)

    _, vjp = jax.vjp(f, lv.data)
    dlogits = vjp(d)[0]
    if ctx.attr("norm_by_times", False):
        # 1/T scaling applied to the logits gradient only (warpctc_op.h:217)
        dlogits = dlogits / jnp.maximum(
            lv.lens[:, None, None], 1).astype(dlogits.dtype)
    ctx.set_output("Logits@GRAD", LoDArray(dlogits, lv.lens))


@register_op("ctc_align")
def ctc_align(ctx):
    """Merge repeated tokens, drop blanks, compact (ctc_align_op.h)."""
    x = ctx.input("Input")
    if not isinstance(x, LoDArray):
        raise TypeError("ctc_align expects LoD input")
    blank = int(ctx.attr("blank", 0))
    merge = bool(ctx.attr("merge_repeated", True))
    d = x.data
    flat = d if d.ndim == 2 else d[..., 0]
    valid = jnp.arange(flat.shape[1])[None, :] < x.lens[:, None]
    keep = valid & (flat != blank)
    if merge:
        prev = jnp.pad(flat, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
        keep = keep & (flat != prev)
    order = jnp.argsort(~keep, axis=1, stable=True)
    comp = jnp.take_along_axis(flat, order, axis=1)
    lens = keep.sum(axis=1).astype(jnp.int32)
    comp = comp * (jnp.arange(comp.shape[1])[None, :]
                   < lens[:, None]).astype(comp.dtype)
    ctx.set_output("Output", LoDArray(comp if d.ndim == 2 else comp[..., None],
                                      lens))


@register_op("edit_distance")
def edit_distance(ctx):
    """Levenshtein distance per (hypothesis, reference) sequence pair
    (edit_distance_op.h). normalized attr divides by reference length."""
    hyp = ctx.input("Hyps")
    ref = ctx.input("Refs")
    if not isinstance(hyp, LoDArray) or not isinstance(ref, LoDArray):
        raise TypeError("edit_distance expects LoD inputs")
    h = hyp.data if hyp.data.ndim == 2 else hyp.data[..., 0]
    r = ref.data if ref.data.ndim == 2 else ref.data[..., 0]
    hl, rl = hyp.lens, ref.lens
    b, H = h.shape
    R = r.shape[1]

    # DP over hypothesis tokens; row j = distance of hyp prefix vs ref
    # prefix of length j
    row0 = jnp.broadcast_to(jnp.arange(R + 1, dtype=jnp.float32)[None, :],
                            (b, R + 1))

    def step(row, i):
        tok = h[:, i]                                   # [b]
        sub_or_match = row[:, :-1] + (r != tok[:, None]).astype(jnp.float32)
        deletion = row[:, 1:] + 1.0
        new_tail = jnp.minimum(sub_or_match, deletion)
        first = row[:, 0] + 1.0

        def inner(carry, j):
            left = carry
            val = jnp.minimum(new_tail[:, j], left + 1.0)
            return val, val

        _, cols = jax.lax.scan(inner, first, jnp.arange(R))
        new_row = jnp.concatenate([first[:, None],
                                   jnp.swapaxes(cols, 0, 1)], axis=1)
        # rows beyond this hypothesis's length keep the previous row
        alive = (i < hl)[:, None]
        return jnp.where(alive, new_row, row), None

    final_row, _ = jax.lax.scan(step, row0, jnp.arange(H))
    dist = jnp.take_along_axis(final_row, rl[:, None], axis=1)[:, 0]
    if ctx.attr("normalized", False):
        dist = dist / jnp.maximum(rl, 1).astype(dist.dtype)
    ctx.set_output("Out", dist[:, None])
    ctx.set_output("SequenceNum", jnp.asarray([b], jnp.int32))

"""Optimizer update ops.

Reference: sgd_op.cc, momentum_op.cc, adam_op.h, adagrad_op.cc, rmsprop_op.cc,
adamax_op.cc, adadelta_op.cc, decayed_adagrad_op.cc, ftrl_op.cc
(/root/reference/paddle/fluid/operators/). In the reference these are ops
*inside the training program* that update parameters in place
(ParamOut == Param); the functional lowering rebinds the name, and because the
whole block is one jitted computation, XLA fuses the update into the backward
pass — no separate "optimizer step" launch ever exists on TPU.

Each op's ``*Out`` aliases follow the reference exactly so that
optimizer.py-built programs are structurally identical to the reference's.

Sparse (SelectedRows) branches: every reference optimizer kernel has a
SelectedRows path that merges duplicate gradient rows then updates ONLY the
touched rows of the parameter/accumulators ("lazy" updates —
operators/adam_op.h SparseAdamFunctor, operators/sgd_op.cu sparse branch,
operators/adagrad_op.cc). Here sgd/momentum/adagrad/adam consume a
``SparseRows`` gradient the same way via core.sparse.apply_rowwise (gather
touched rows → per-row update → scatter back); the remaining optimizers
densify the gradient first (correct, just not lazy).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op
from ..core.sparse import SparseRows, merge_rows, apply_rowwise, is_sparse
from .common import data_of


def _lr(ctx):
    return data_of(ctx.input("LearningRate")).reshape(())


def _param_grad(ctx):
    """Param + Grad with the gradient cast up to the parameter dtype: under
    AMP the backward produces bf16 grads while master weights and optimizer
    state stay float32 (the mixed-precision contract). A SparseRows grad
    reaching an optimizer without a sparse branch is densified here."""
    p = data_of(ctx.input("Param"))
    g = ctx.input("Grad")
    if is_sparse(g):
        g = g.to_dense()
    g = data_of(g).astype(p.dtype)
    return p, g


def _sparse_grad(ctx, p):
    """The Grad input as a merged SparseRows in the param dtype, or None."""
    g = ctx.input("Grad")
    if not is_sparse(g):
        return None
    return merge_rows(g.astype(p.dtype))


# ---- dense update expressions, shared verbatim by the per-param ops and
# the fused megakernel's jnp twin (so kernel_tier=jnp keeps the fused
# program bitwise-identical to the per-param one) ----

def _sgd_dense(p, g, lr):
    return p - lr * g


def _momentum_dense(p, g, v, lr, mu, nesterov):
    v_new = mu * v + g
    if nesterov:
        return p - (g + mu * v_new) * lr, v_new
    return p - lr * v_new, v_new


def _adam_dense(p, g, m1, m2, lr_eff, b1, b2, eps):
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * g * g
    return p - lr_eff * m1n / (jnp.sqrt(m2n) + eps), m1n, m2n


def _sgd_apply(p_v, g_v, lr):
    """One param's SGD step: dense expression, or the sparse branch
    (sgd_op.cu): scatter-subtract the touched rows. The sparse branch
    dispatches to the fused embedding-lookup+sgd Pallas kernel under the
    tier — gather + rowwise update in ONE kernel, O(touched rows) HBM
    traffic (rows pre-merged like every reference sparse optimizer
    kernel; the jnp scatter needs no merge — the update is linear, so
    duplicate rows accumulate correctly)."""
    p = data_of(p_v)
    if is_sparse(g_v):
        from .pallas import kernel_span, use_pallas
        if use_pallas("embedding_sgd",
                      p.ndim == 2 and g_v.values.ndim == 2):
            from .pallas.embedding import embedding_sgd_pallas
            m = merge_rows(g_v.astype(p.dtype))
            with kernel_span("pallas", "embedding_sgd"):
                return embedding_sgd_pallas(p, m.rows, m.values, lr)
        vals = g_v.values.astype(p.dtype)
        return p.at[g_v.rows].add(-lr * vals, mode="drop")
    return _sgd_dense(p, data_of(g_v).astype(p.dtype), lr)


@register_op("sgd", in_place=True)
def sgd(ctx):
    ctx.set_output("ParamOut",
                   _sgd_apply(ctx.input("Param"), ctx.input("Grad"),
                              _lr(ctx)))


@register_op("momentum", in_place=True)
def momentum(ctx):
    p = data_of(ctx.input("Param"))
    v = data_of(ctx.input("Velocity"))
    mu = ctx.attr("mu")
    lr = _lr(ctx)
    nesterov = ctx.attr("use_nesterov", False)
    sg = _sparse_grad(ctx, p)
    if sg is not None:
        def upd(g, p_r, v_r):
            v_new = mu * v_r + g
            if nesterov:
                return p_r - (g + mu * v_new) * lr, v_new
            return p_r - lr * v_new, v_new
        p_new, v_new = apply_rowwise(sg, [p, v], upd)
        ctx.set_output("ParamOut", p_new)
        ctx.set_output("VelocityOut", v_new)
        return
    p, g = _param_grad(ctx)
    p_new, v_new = _momentum_dense(p, g, v, lr, mu, nesterov)
    ctx.set_output("ParamOut", p_new)
    ctx.set_output("VelocityOut", v_new)


@register_op("adam", in_place=True)
def adam(ctx):
    p = data_of(ctx.input("Param"))
    m1 = data_of(ctx.input("Moment1"))
    m2 = data_of(ctx.input("Moment2"))
    b1p = data_of(ctx.input("Beta1Pow")).reshape(())
    b2p = data_of(ctx.input("Beta2Pow")).reshape(())
    b1, b2 = ctx.attr("beta1", 0.9), ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    lr = _lr(ctx) * jnp.sqrt(1 - b2p) / (1 - b1p)
    sg = _sparse_grad(ctx, p)
    if sg is not None:
        # adam_op.h SparseAdamFunctor: lazy per-row moment/param update
        def upd(g, p_r, m1_r, m2_r):
            m1n = b1 * m1_r + (1 - b1) * g
            m2n = b2 * m2_r + (1 - b2) * g * g
            return p_r - lr * m1n / (jnp.sqrt(m2n) + eps), m1n, m2n
        p_new, m1_new, m2_new = apply_rowwise(sg, [p, m1, m2], upd)
        ctx.set_output("ParamOut", p_new)
        ctx.set_output("Moment1Out", m1_new)
        ctx.set_output("Moment2Out", m2_new)
        return
    p, g = _param_grad(ctx)
    p_new, m1n, m2n = _adam_dense(p, g, m1, m2, lr, b1, b2, eps)
    ctx.set_output("ParamOut", p_new)
    ctx.set_output("Moment1Out", m1n)
    ctx.set_output("Moment2Out", m2n)


@register_op("adagrad", in_place=True)
def adagrad(ctx):
    p = data_of(ctx.input("Param"))
    m = data_of(ctx.input("Moment"))
    eps = ctx.attr("epsilon", 1e-6)
    lr = _lr(ctx)
    sg = _sparse_grad(ctx, p)
    if sg is not None:
        def upd(g, p_r, m_r):
            m_new = m_r + g * g
            return p_r - lr * g / (jnp.sqrt(m_new) + eps), m_new
        p_new, m_new = apply_rowwise(sg, [p, m], upd)
        ctx.set_output("ParamOut", p_new)
        ctx.set_output("MomentOut", m_new)
        return
    p, g = _param_grad(ctx)
    m_new = m + g * g
    ctx.set_output("ParamOut", p - lr * g / (jnp.sqrt(m_new) + eps))
    ctx.set_output("MomentOut", m_new)


@register_op("decayed_adagrad", in_place=True)
def decayed_adagrad(ctx):
    p, g = _param_grad(ctx)
    m = data_of(ctx.input("Moment"))
    decay = ctx.attr("decay", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    m_new = decay * m + (1 - decay) * g * g
    ctx.set_output("ParamOut", p - _lr(ctx) * g / (jnp.sqrt(m_new) + eps))
    ctx.set_output("MomentOut", m_new)


@register_op("adadelta", in_place=True)
def adadelta(ctx):
    p, g = _param_grad(ctx)
    avg_sq_grad = data_of(ctx.input("AvgSquaredGrad"))
    avg_sq_upd = data_of(ctx.input("AvgSquaredUpdate"))
    rho = ctx.attr("rho", 0.95)
    eps = ctx.attr("epsilon", 1e-6)
    asg = rho * avg_sq_grad + (1 - rho) * g * g
    upd = -jnp.sqrt((avg_sq_upd + eps) / (asg + eps)) * g
    asu = rho * avg_sq_upd + (1 - rho) * upd * upd
    ctx.set_output("ParamOut", p + upd)
    ctx.set_output("AvgSquaredGradOut", asg)
    ctx.set_output("AvgSquaredUpdateOut", asu)


@register_op("rmsprop", in_place=True)
def rmsprop(ctx):
    p, g = _param_grad(ctx)
    ms = data_of(ctx.input("MeanSquare"))
    mom = data_of(ctx.input("Moment"))
    rho = ctx.attr("decay", 0.9)
    eps = ctx.attr("epsilon", 1e-10)
    momentum_c = ctx.attr("momentum", 0.0)
    ms_new = rho * ms + (1 - rho) * g * g
    mom_new = momentum_c * mom + _lr(ctx) * g / jnp.sqrt(ms_new + eps)
    ctx.set_output("ParamOut", p - mom_new)
    ctx.set_output("MeanSquareOut", ms_new)
    ctx.set_output("MomentOut", mom_new)


@register_op("adamax", in_place=True)
def adamax(ctx):
    p, g = _param_grad(ctx)
    m = data_of(ctx.input("Moment"))
    inf_norm = data_of(ctx.input("InfNorm"))
    b1p = data_of(ctx.input("Beta1Pow")).reshape(())
    b1, b2 = ctx.attr("beta1", 0.9), ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf_norm, jnp.abs(g) + eps)
    lr = _lr(ctx) / (1 - b1p)
    ctx.set_output("ParamOut", p - lr * m_new / inf_new)
    ctx.set_output("MomentOut", m_new)
    ctx.set_output("InfNormOut", inf_new)


@register_op("ftrl", in_place=True)
def ftrl(ctx):
    p, g = _param_grad(ctx)
    sq = data_of(ctx.input("SquaredAccumulator"))
    lin = data_of(ctx.input("LinearAccumulator"))
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    lr_power = ctx.attr("lr_power", -0.5)
    lr = _lr(ctx)
    new_sq = sq + g * g
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq, -lr_power)) / lr
    new_lin = lin + g - sigma * p
    x = jnp.clip(new_lin, -l1, l1) - new_lin
    if lr_power == -0.5:
        y = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        y = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    ctx.set_output("ParamOut", x / y)
    ctx.set_output("SquaredAccumOut", new_sq)
    ctx.set_output("LinearAccumOut", new_lin)


@register_op("proximal_gd", in_place=True)
def proximal_gd(ctx):
    p, g = _param_grad(ctx)
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    lr = _lr(ctx)
    prox = p - lr * g
    ctx.set_output("ParamOut",
                   jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                   / (1.0 + lr * l2))


@register_op("proximal_adagrad", in_place=True)
def proximal_adagrad(ctx):
    p, g = _param_grad(ctx)
    m = data_of(ctx.input("Moment"))
    l1 = ctx.attr("l1", 0.0)
    l2 = ctx.attr("l2", 0.0)
    m_new = m + g * g
    lr = _lr(ctx) / jnp.sqrt(m_new)
    prox = p - lr * g
    ctx.set_output("ParamOut",
                   jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                   / (1.0 + lr * l2))
    ctx.set_output("MomentOut", m_new)


# ---------------------------------------------------------------------------
# fused dense-optimizer megakernel ops (no reference analog)
# ---------------------------------------------------------------------------
#
# The per-param ops above trace one small update per parameter — XLA emits
# one fused kernel per param, so a ResNet-50 step dispatches ~160 tiny
# launches for the momentum tail alone (bench.py profile). These variadic
# ops take ALL dense params in one op; under a Pallas tier the update runs
# as ONE arena megakernel (ops/pallas/optimizer.py: params/grads/state
# concatenated into flat f32 arenas, one launch walks the tiles), and the
# jnp twin applies the per-param dense expressions above in a python loop
# — bitwise the per-param program. A SparseRows grad (or a non-f32 param)
# keeps its param on the per-param path inside the same op. Emitted by
# fluid.optimizer.{SGD,Momentum,Adam}(fused=True).

def _fused_apply(ctx, state_slots, out_slots, dense_fn, sparse_fn,
                 arena_fn):
    """Shared driver for the fused ops: split the param list into
    arena-fusable entries (dense f32 grads) and per-param entries
    (SparseRows / non-f32), run the per-param branch with ``sparse_fn``/
    ``dense_fn``, and the fusable set through ONE arena megakernel
    (``arena_fn``) under a Pallas tier — or the same ``dense_fn``
    expressions per param under jnp (bitwise the per-param program).

    dense_fn(p, g, *states) and sparse_fn(p_var, g_sparse, *states) both
    return a (p_new, *state_news) tuple; arena_fn(*arenas) returns the
    updated arenas in the same order.
    """
    from .pallas import kernel_span, use_pallas

    slots = ("Params", "Grads") + tuple(state_slots)
    entries = list(zip(*[ctx.inputs(s) for s in slots]))
    k = 1 + len(state_slots)
    outs = [[None] * len(entries) for _ in range(k)]
    fusable = []
    for i, e in enumerate(entries):
        p = data_of(e[0])
        if (not is_sparse(e[1])) and p.dtype == jnp.float32:
            fusable.append(i)
            continue
        if is_sparse(e[1]):
            res = sparse_fn(e[0], e[1], *[data_of(v) for v in e[2:]])
        else:
            res = dense_fn(p, data_of(e[1]).astype(p.dtype),
                           *[data_of(v) for v in e[2:]])
        for j, v in enumerate(res):
            outs[j][i] = v
    # the dispatch runs even with no fusable params so an all-sparse op
    # under a Pallas tier is a counted fallback, not a silent miss
    if use_pallas("optimizer", bool(fusable)):
        from .pallas import optimizer as opk
        ps = [data_of(entries[i][0]) for i in fusable]
        gs = [data_of(entries[i][1]).astype(jnp.float32) for i in fusable]
        states = [[data_of(entries[i][2 + j]) for i in fusable]
                  for j in range(len(state_slots))]
        shapes = [p.shape for p in ps]
        with kernel_span("pallas", "optimizer"):
            arenas = [opk.flatten_arena(xs)[0]
                      for xs in (ps, gs, *states)]
            results = arena_fn(*arenas)
            split = [opk.split_arena(r, shapes) for r in results]
        for j in range(k):
            for i, v in zip(fusable, split[j]):
                outs[j][i] = v
    else:
        if fusable:
            # the jnp twin: the per-param dense expressions verbatim
            # (bitwise the per-param program)
            with kernel_span("jnp", "optimizer"):
                for i in fusable:
                    p = data_of(entries[i][0])
                    res = dense_fn(
                        p, data_of(entries[i][1]).astype(p.dtype),
                        *[data_of(v) for v in entries[i][2:]])
                    for j, v in enumerate(res):
                        outs[j][i] = v
    for slot, vals in zip(out_slots, outs):
        ctx.set_outputs(slot, vals)


@register_op("fused_sgd", in_place=True)
def fused_sgd(ctx):
    lr = _lr(ctx)

    def arena(pa, ga):
        from .pallas import optimizer as opk
        return (opk.sgd_arena_pallas(pa, ga, lr),)

    _fused_apply(ctx, (), ("ParamsOut",),
                 dense_fn=lambda p, g: (_sgd_dense(p, g, lr),),
                 sparse_fn=lambda p_v, g_v: (_sgd_apply(p_v, g_v, lr),),
                 arena_fn=arena)


@register_op("fused_momentum", in_place=True)
def fused_momentum(ctx):
    lr = _lr(ctx)
    mu = ctx.attr("mu")
    nesterov = bool(ctx.attr("use_nesterov", False))

    def dense(p, g, v):
        return _momentum_dense(p, g, v, lr, mu, nesterov)

    def sparse(p_v, g_v, v):
        p = data_of(p_v)
        sg = merge_rows(g_v.astype(p.dtype))

        def upd(g, p_r, v_r):
            v_new = mu * v_r + g
            if nesterov:
                return p_r - (g + mu * v_new) * lr, v_new
            return p_r - lr * v_new, v_new
        return tuple(apply_rowwise(sg, [p, v], upd))

    def arena(pa, ga, va):
        from .pallas import optimizer as opk
        return opk.momentum_arena_pallas(pa, ga, va, lr, mu, nesterov)

    _fused_apply(ctx, ("Velocities",), ("ParamsOut", "VelocitiesOut"),
                 dense, sparse, arena)


@register_op("fused_adam", in_place=True)
def fused_adam(ctx):
    b1, b2 = ctx.attr("beta1", 0.9), ctx.attr("beta2", 0.999)
    eps = ctx.attr("epsilon", 1e-8)
    b1p = data_of(ctx.input("Beta1Pow")).reshape(())
    b2p = data_of(ctx.input("Beta2Pow")).reshape(())
    # ONE shared beta-power pair (every param shares the step count), so
    # lr_eff is one scalar for the whole arena
    lr_eff = _lr(ctx) * jnp.sqrt(1 - b2p) / (1 - b1p)

    def dense(p, g, m1, m2):
        return _adam_dense(p, g, m1, m2, lr_eff, b1, b2, eps)

    def sparse(p_v, g_v, m1, m2):
        p = data_of(p_v)
        sg = merge_rows(g_v.astype(p.dtype))

        def upd(g, p_r, m1_r, m2_r):
            m1n = b1 * m1_r + (1 - b1) * g
            m2n = b2 * m2_r + (1 - b2) * g * g
            return (p_r - lr_eff * m1n / (jnp.sqrt(m2n) + eps), m1n, m2n)
        return tuple(apply_rowwise(sg, [p, m1, m2], upd))

    def arena(pa, ga, m1a, m2a):
        from .pallas import optimizer as opk
        return opk.adam_arena_pallas(pa, ga, m1a, m2a, lr_eff, b1, b2, eps)

    _fused_apply(ctx, ("Moment1s", "Moment2s"),
                 ("ParamsOut", "Moment1sOut", "Moment2sOut"),
                 dense, sparse, arena)

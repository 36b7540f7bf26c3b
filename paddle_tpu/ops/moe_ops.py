"""routed_experts: a mixture-of-experts layer that is told which experts it
holds.

The router keeps its published width: logits ``x @ RouterW`` over all
``num_experts`` in float32 at ``highest`` precision, scored by
``scoring_func`` — a softmax over all of them (the default) or a sigmoid of
each — the ``top_k`` largest, renormalised to sum 1 where
``norm_topk_prob``, times ``routed_scaling_factor``. With the input
``SelectBias`` ([num_experts], float32) the top k are those of ``score +
bias``: the bias selects and does not weigh (the weights are the scores'
own). It is no gradient's business: ``expert_bias_update`` moves it by the
step's loads. The expert weights are ``[held, ...]``: the experts
``expert_offset .. expert_offset + held - 1``. The op computes, for every
token, the part of ``y = sum_k w_k WDown[e_k](silu(WGate[e_k] x) *
WUp[e_k] x)`` that the held experts give; nothing stands in for the absent
ones. With the attr ``expert_form`` ``relu2`` an expert is ``WDown[e]
relu(WUp[e] x)^2`` with NO gate: the op then has no ``WGate`` input and no
``Gate`` output, and a layer is six grouped products forward and backward
where the gated form has nine. With ``held == num_experts`` it is the whole
layer; across chips it is what runs between the two exchanges of an
expert-parallel step.

No token routed to a held expert is dropped. The ``tokens * top_k``
assignments are sorted by expert (absent ones last) into a row buffer of
static size in which every held expert's group starts on a tile of ``TILE``
rows, and each weight meets the buffer in ONE grouped product: the
``grouped_matmul`` Pallas family (ops/pallas/grouped_matmul.py: a tile of
rows meets one expert's resident weights), or ``jax.lax.ragged_dot`` over
the same aligned groups (XLA:TPU's own grouped kernel; the twin on the CPU).
The work follows the rows held, not rows x experts. The rows go back to
their tokens in ``combine``, forward and backward: the ``moe_combine``
Pallas family (ops/pallas/moe_combine.py), which leans on the sort being
STABLE (tokens ascend inside a group, so a block of tokens owns one range of
rows in each) and reads no row outside those ranges, or a scatter-add over
the whole buffer, which a TPU runs one update after another. The buffer holds
``row_buffer_factor`` times the expectation ``tokens * top_k * held /
num_experts`` (and a tile of padding per expert); a step whose held rows
pass that makes the whole output NaN: loud, never a silent drop.

The grad op recomputes no product: the forward keeps the two
pre-activations of the row buffer (``Gate``, ``Up``) and the routing
(``RowAssign``, ``RowWeight``, ``ExpertLoad``, ``TopIdx``, ``Probs``) as
outputs. ``ExpertLoad`` ([held] int32, rows per held expert) and the other
integer outputs take no gradient. ``AuxLoss`` is the load-balancing term of
the source's family, ``num_experts * sum_e f_e P_e`` over all router
outputs (f_e the share of assignments, no gradient; P_e the mean
probability; under sigmoid scores the mean of ``s_e / sum(s)``).

With the attr ``router_task_gradient`` off (default on: the layer's whole
gradient) the weights of the top k are constants in the backward pass and
the router learns from ``AuxLoss`` alone. That is for a layer that holds a
SHARE of the experts, whose part of that gradient is the held experts'
alone: only they can reward a token, and training on it drives every token
onto them (PERF.md, PR 28); the exchange that would bring the other
experts' part is not built.

Precision under AMP: the expert products take bfloat16 operands and
accumulate in float32; ``Gate``/``Up`` are kept in bfloat16; the router, the
weights of the top k and the combine are float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.amp import cast_compute
from ..core.registry import OpSpec, register_op, same_shape
from ..obs.metrics import REGISTRY as _METRICS
from .common import G, data_of
from .pallas import kernel_span, use_pallas

_M_ROW_BUFFER = _METRICS.gauge(
    "paddle_tpu_moe_row_buffer",
    "routed_experts' row buffer as last traced: kind=rows is the "
    "expectation tokens*top_k*held/num_experts, kind=capacity the static "
    "buffer a step's held rows must fit, kind=products the grouped "
    "products over it forward and backward (9: gated experts, 6: relu2)",
    labels=("kind",))
_M_WEIGHTS = _METRICS.counter(
    "paddle_tpu_grouped_matmul_weights",
    "expert weights routed_experts hands to the grouped_matmul kernels "
    "(counted at trace time, once a weight, from the forward), by "
    "orientation: stored as the parameter is, or swapped (its transpose, "
    "where that is how XLA:TPU stores it: ops/pallas/grouped_matmul.swapped)",
    labels=("orientation",))

ROW_ALIGN = 512
TILE = 256              # every expert's group starts on a tile of rows


def row_buffer(tokens, top_k, held, num_experts, factor):
    """(expected rows, rows that may be held, buffer rows): the capacity is
    ``factor`` times the expectation, rounded up to ``ROW_ALIGN`` rows,
    never more than every assignment; the buffer adds a tile per held
    expert, for the padding that starts each group on a tile."""
    expected = tokens * top_k * held / num_experts
    cap = int(math.ceil(factor * expected / ROW_ALIGN) * ROW_ALIGN)
    cap = min(cap, tokens * top_k)
    return expected, cap, (-(-cap // TILE) + held) * TILE


EXPERT_FORMS = ("gated_silu", "relu2")


def _relu2(ctx):
    """Whether the experts are ``WDown relu(WUp x)^2`` without a gate; the
    form goes on the row buffer's gauge as the products it costs."""
    form = ctx.attr("expert_form", "gated_silu")
    if form not in EXPERT_FORMS:
        raise ValueError(f"routed_experts: unknown expert_form {form!r}")
    _M_ROW_BUFFER.labels(kind="products").set(6 if form == "relu2" else 9)
    return form == "relu2"


def _attrs(ctx):
    scoring = ctx.attr("scoring_func", "softmax")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"routed_experts: unknown scoring_func {scoring!r}")
    return dict(num_experts=int(ctx.attr("num_experts")),
                top_k=int(ctx.attr("top_k")),
                norm_topk_prob=bool(ctx.attr("norm_topk_prob", True)),
                expert_offset=int(ctx.attr("expert_offset", 0)),
                factor=float(ctx.attr("row_buffer_factor", 2.0)),
                scoring=scoring,
                scale=float(ctx.attr("routed_scaling_factor", 1.0)))


SIGMOID_EPS = 1e-20     # in the renormalisation of sigmoid scores' top k


def _count(ids, n):
    """How often each of 0..n-1 occurs in ``ids``, int32: a compare and a
    sum (a scatter-add of ones runs one update after another on a TPU)."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(n, dtype=ids.dtype),
                   axis=0, dtype=jnp.int32)


def _chosen(top_i, num_experts):
    """[n, k, num_experts] bool: slot k of token n chose expert e."""
    return top_i[:, :, None] == jnp.arange(num_experts, dtype=top_i.dtype)


def _scores_of(scores, top_i):
    """``scores`` [n, e] at ``top_i`` [n, k], as a compare and a sum (a
    gather takes its elements one after another on a TPU)."""
    return jnp.sum(jnp.where(_chosen(top_i, scores.shape[-1]),
                             scores[:, None, :], 0.0), axis=-1)


def route(x, router_w, held, num_experts, top_k, norm_topk_prob,
          expert_offset, factor, scoring="softmax", scale=1.0, bias=None):
    """The routing of ``x`` [n, h] (float32): scores (``probs``: softmax
    probabilities or sigmoids), the top k (of ``scores + bias`` where a
    selection bias is given) and their weights, and the row buffer: for
    each row the assignment (token * top_k + slot) it holds, or -1, and
    its weight; rows per held expert; whether they passed the capacity.
    The buffer is sorted by expert (a stable sort: tokens ascend inside a
    group) and each group starts on a tile of ``TILE`` rows."""
    n = x.shape[0]
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_p, top_i = jax.lax.top_k(probs, top_k)
    else:
        _, top_i = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        top_p = _scores_of(probs, top_i)
    top_w = top_p / _top_total(top_p, scoring) if norm_topk_prob else top_p
    if scale != 1.0:
        top_w = top_w * scale
    local = top_i.reshape(-1) - expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held)
    load = _count(key, held)
    expected, cap, buffer = row_buffer(n, top_k, held, num_experts, factor)
    _M_ROW_BUFFER.labels(kind="rows").set(expected)
    _M_ROW_BUFFER.labels(kind="capacity").set(cap)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    lay = layout(load, buffer)
    expert = jnp.repeat(lay["tile_expert"], TILE)            # of each row
    rank = jnp.arange(buffer) - lay["starts"][expert]
    valid = (rank < load[expert]) & (jnp.arange(buffer) < lay["rows"])
    sorted_at = (jnp.cumsum(load) - load)[expert] + rank
    assign = jnp.where(valid, order[jnp.clip(sorted_at, 0, n * top_k - 1)],
                       -1)
    weight = jnp.where(valid, top_w.reshape(-1)[jnp.maximum(assign, 0)],
                       0.0)
    return dict(probs=probs, top_i=top_i.astype(jnp.int32), load=load,
                assign=assign, weight=weight, layout=lay,
                overflow=jnp.sum(load) > cap)


def _top_total(top_p, scoring):
    total = jnp.sum(top_p, -1, keepdims=True)
    return total + SIGMOID_EPS if scoring == "sigmoid" else total


def _balance(probs, scoring):
    """P [n, e] of the balance term: the scores as a distribution over the
    experts."""
    if scoring == "sigmoid":
        return probs / jnp.sum(probs, -1, keepdims=True)
    return probs


def layout(load, buffer):
    """Where the groups lie in a buffer of ``buffer`` rows: each group's
    aligned size and start, the expert of each tile (tiles past the last
    group say the last expert), the tiles and rows in use."""
    tiles = -(-load // TILE)
    ends = jnp.cumsum(tiles)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(buffer // TILE), side="right"),
        load.shape[0] - 1).astype(jnp.int32)
    return dict(sizes=tiles * TILE, starts=(ends - tiles) * TILE,
                tile_expert=tile_expert, tiles=ends[-1:].astype(jnp.int32),
                rows=ends[-1] * TILE)


class _Products:
    """The three grouped products of one call over one buffer layout, by
    the route: the ``grouped_matmul`` kernels, or ``jax.lax.ragged_dot``
    over the same aligned groups (XLA:TPU's own grouped kernel there; the
    twin on the CPU). On the kernels each weight goes over in the
    orientation the chip stores it in (``grouped_matmul.swapped``: a
    bitcast, where the other orientation costs a copy of the weight and of
    its Adam moments each way); ragged_dot takes it as it is."""

    _CONTRACT_ROWS = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def __init__(self, load, lay, rows, w):
        from .pallas import grouped_matmul as gm

        self.load, self.lay, self.gm = load, lay, gm
        self.route = "pallas" if use_pallas(
            "grouped_matmul", gm.supported(rows, w)) else "jnp"

    def span(self):
        return kernel_span(self.route, "grouped_matmul")

    def count(self, ws):
        """Each weight's orientation on the kernels, on the counter: once a
        trace, from the forward."""
        if self.route == "pallas":
            for w in ws:
                _M_WEIGHTS.labels(orientation="swapped" if self.gm.swapped(
                    *w.shape[1:]) else "stored").inc()

    def _kernel(self, plain, swapped, rows, w, dtype=jnp.float32):
        """``plain`` over ``w``, or ``swapped`` over its transpose."""
        if self.gm.swapped(*w.shape[1:]):
            plain, w = swapped, jnp.swapaxes(w, 1, 2)
        return plain(rows, w, self.lay["tile_expert"], self.lay["tiles"],
                     dtype)

    def rows_by(self, rows, w, dtype=jnp.float32):
        """[R, a] x [held, a, b] -> [R, b]."""
        with self.span():
            if self.route == "pallas":
                return self._kernel(self.gm.gmm, self.gm.gmm_t, rows, w,
                                    dtype)
            return jax.lax.ragged_dot(
                rows, w, self.lay["sizes"],
                preferred_element_type=jnp.float32).astype(dtype)

    def rows_by_transposed(self, rows, w):
        """[R, b] x [held, a, b] -> [R, a]: a product's input gradient.
        Off the kernels the weights are transposed first: XLA:TPU lowers the
        plain form to its grouped kernel, but a ragged_dot_general that
        contracts the weights' last axis to a dense product over every
        (row, expert) pair, held times the work (AOT cost analysis)."""
        with self.span():
            if self.route == "pallas":
                return self._kernel(self.gm.gmm_t, self.gm.gmm, rows, w)
            return jax.lax.ragged_dot(
                rows, jnp.swapaxes(w, 1, 2), self.lay["sizes"],
                preferred_element_type=jnp.float32)

    def weights_grad(self, rows, grads):
        """[R, a] x [R, b] -> [held, a, b], each group's rows contracted
        apart; an expert without a row gets zeros."""
        with self.span():
            if self.route == "pallas":
                held = self.load.shape[0]
                tiles = self.lay["tile_expert"], self.lay["tiles"]
                if self.gm.swapped(rows.shape[1], grads.shape[1]):
                    out = jnp.swapaxes(
                        self.gm.tgmm(grads, rows, held, *tiles), 1, 2)
                else:
                    out = self.gm.tgmm(rows, grads, held, *tiles)
                return jnp.where((self.load > 0)[:, None, None], out, 0.0)
            return jax.lax.ragged_dot_general(
                rows, grads, self.lay["sizes"], self._CONTRACT_ROWS,
                preferred_element_type=jnp.float32)


def combine_jnp(rows, weight, token, n):
    """``combine``'s twin: a scatter-add over the whole buffer, rows of
    padding masked out."""
    y = jnp.where((token >= 0)[:, None], rows, 0.0) * weight[:, None]
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[
        jnp.maximum(token, 0)].add(y)


def combine(rows, weight, assign, top_k, lay, n):
    """Rows -> tokens: for each of ``n`` tokens the float32 sum of the
    buffer's rows that hold one of its assignments (``assign`` [R]: token *
    top_k + slot, or -1 on a row of padding, which is left out), each times
    its ``weight``. The ``moe_combine`` Pallas family
    (ops/pallas/moe_combine.py: a gather and a sum over blocks of tokens
    that reads only the rows in use), or a scatter-add over the whole
    buffer (the twin on the CPU; one update after another on a TPU)."""
    from .pallas import moe_combine as mc

    token = jnp.where(assign >= 0, assign // top_k, -1)
    route = "pallas" if use_pallas(
        "moe_combine",
        mc.supported(rows, n, lay["starts"].shape[0])) else "jnp"
    with kernel_span(route, "moe_combine"):
        if route == "pallas":
            return mc.combine(rows, weight, token, lay["starts"],
                              lay["tile_expert"], n)
        return combine_jnp(rows, weight, token, n)


def _infer(op, block):
    x = block.var(op.input("X")[0])
    if x.shape is None:
        return
    for name in op.output("Out"):
        v = block.var(name)
        v.shape, v.dtype = x.shape, v.dtype or x.dtype


def _grad_maker(op):
    weights = [s for s in ("X", "RouterW", "WGate", "WUp", "WDown")
               if op.input(s)]                  # no WGate under relu2
    inputs = {s: op.input(s) for s in weights}
    for s in ("Gate", "Up", "RowAssign", "RowWeight", "ExpertLoad", "TopIdx",
              "Probs"):
        if op.output(s):
            inputs[s] = op.output(s)
    inputs["Out@GRAD"] = G(op.output("Out"))
    inputs["AuxLoss@GRAD"] = G(op.output("AuxLoss"))
    return [OpSpec("routed_experts_grad", inputs,
                   {s + "@GRAD": G(op.input(s)) for s in weights},
                   dict(op.attrs))]


def _weights(ctx, relu2):
    """The experts' weights in the order of the products: [WGate,] WUp,
    WDown."""
    slots = ("WUp", "WDown") if relu2 else ("WGate", "WUp", "WDown")
    return [data_of(ctx.input(s)) for s in slots]


@register_op("routed_experts", infer_shape=_infer, grad=_grad_maker)
def routed_experts(ctx):
    relu2 = _relu2(ctx)
    xv = data_of(ctx.input("X"))
    ws = _weights(ctx, relu2)
    a = _attrs(ctx)
    x = xv.reshape(-1, xv.shape[-1])
    bias = data_of(ctx.input("SelectBias")) \
        if ctx.has_input("SelectBias") else None
    r = route(x, data_of(ctx.input("RouterW")), ws[0].shape[0], bias=bias,
              **a)
    xc, *ws = cast_compute(x, *ws)
    keep = (r["assign"] >= 0)[:, None]
    token = jnp.maximum(r["assign"], 0) // a["top_k"]
    rows = xc[token]
    dot = _Products(r["load"], r["layout"], rows, ws[0])
    dot.count(ws)
    # a row of padding holds some token's data and whatever the product
    # makes of it: kept out of everything that sums over rows
    *pre, up = (jnp.where(keep, dot.rows_by(rows, w, xc.dtype), 0)
                for w in ws[:-1])
    if relu2:
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32)))
    else:
        act = jax.nn.silu(pre[0].astype(jnp.float32)) * up.astype(jnp.float32)
    out = combine(dot.rows_by(act.astype(xc.dtype), ws[-1]), r["weight"],
                  r["assign"], a["top_k"], r["layout"], x.shape[0])
    out = jnp.where(r["overflow"], jnp.nan, out)

    counts = _count(r["top_i"], a["num_experts"]).astype(jnp.float32)
    aux = a["num_experts"] * jnp.sum(
        counts / x.shape[0]
        * jnp.mean(_balance(r["probs"], a["scoring"]), axis=0))

    ctx.set_output("Out", out.reshape(xv.shape).astype(xv.dtype))
    ctx.set_output("AuxLoss", aux.reshape(1))
    ctx.set_output("ExpertLoad", r["load"])
    if pre:
        ctx.set_output("Gate", pre[0])
    ctx.set_output("Up", up)
    ctx.set_output("RowAssign", r["assign"])
    ctx.set_output("RowWeight", r["weight"])
    ctx.set_output("TopIdx", r["top_i"])
    ctx.set_output("Probs", r["probs"])


@register_op("routed_experts_grad")
def routed_experts_grad(ctx):
    """By hand, from the forward's kept rows: the three products' (two
    under ``relu2``) input and weight gradients as grouped products over
    the same groups, then the router's through the scaled, renormalised top
    k, the balance term and the score function (softmax or sigmoid). The
    selection bias takes no gradient: it moved the selection, which has
    none."""
    relu2 = _relu2(ctx)
    xv = data_of(ctx.input("X"))
    router_w = data_of(ctx.input("RouterW"))
    ws = _weights(ctx, relu2)
    a = _attrs(ctx)
    k, n_exp = a["top_k"], a["num_experts"]
    x = xv.reshape(-1, xv.shape[-1])
    n = x.shape[0]
    up = data_of(ctx.input("Up"))
    assign = data_of(ctx.input("RowAssign"))
    weight = data_of(ctx.input("RowWeight"))
    load = data_of(ctx.input("ExpertLoad"))
    top_i = data_of(ctx.input("TopIdx"))
    probs = data_of(ctx.input("Probs"))
    dout = data_of(ctx.input("Out@GRAD")).reshape(x.shape)
    daux = data_of(ctx.input("AuxLoss@GRAD")).reshape(()).astype(jnp.float32)

    xc, *wc, dc = cast_compute(x, *ws, dout)
    keep = (assign >= 0)[:, None]
    token = jnp.maximum(assign, 0) // k
    rows, drows = xc[token], jnp.where(keep, dc[token], 0)
    lay = layout(load, rows.shape[0])
    dot = _Products(load, lay, rows, wc[0])
    # (the gate's convert stays ahead of Up's: the gated form traces what
    # it traced before the forms parted)
    g32 = None if relu2 \
        else data_of(ctx.input("Gate")).astype(jnp.float32)
    u32 = up.astype(jnp.float32)
    if relu2:
        relu = jax.nn.relu(u32)
        act = relu * relu
    else:
        sig = jax.nn.sigmoid(g32)
        silu = g32 * sig
        act = silu * u32

    dact = jnp.where(keep, dot.rows_by_transposed(drows, wc[-1]), 0.0)
    dweight = jnp.sum(dact * act, axis=-1)               # of the unweighted
    dact = dact * weight[:, None]
    d_wd = dot.weights_grad((act * weight[:, None]).astype(xc.dtype), drows)
    if relu2:
        dup = (dact * 2.0 * relu).astype(xc.dtype)
        d_pre = [dot.weights_grad(rows, dup)]
        drows_in = dot.rows_by_transposed(dup, wc[0])
    else:
        dgate = (dact * u32 * sig
                 * (1.0 + g32 * (1.0 - sig))).astype(xc.dtype)
        dup = (dact * silu).astype(xc.dtype)
        d_pre = [dot.weights_grad(rows, dgate), dot.weights_grad(rows, dup)]
        drows_in = dot.rows_by_transposed(dgate, wc[0]) \
            + dot.rows_by_transposed(dup, wc[1])
    dx = combine(drows_in, jnp.ones_like(weight), assign, k, lay, n)

    # the router: rows' weights back to their (token, slot), through the
    # renormalisation, the selection, the balance term and the softmax
    if ctx.attr("router_task_gradient", True):
        # every row's index is its own: a row of padding points past the
        # end and is dropped
        at = jnp.where(keep[:, 0], assign,
                       n * k + jnp.arange(assign.shape[0]))
        dtop_w = jnp.zeros((n * k,), jnp.float32).at[at].add(
            dweight, mode="drop", unique_indices=True).reshape(n, k)
    else:
        dtop_w = jnp.zeros((n, k), jnp.float32)
    sigmoid = a["scoring"] == "sigmoid"
    if a["scale"] != 1.0:
        dtop_w = dtop_w * a["scale"]
    top_p = _scores_of(probs, top_i) if sigmoid \
        else jnp.take_along_axis(probs, top_i, axis=-1)
    if a["norm_topk_prob"]:
        total = _top_total(top_p, a["scoring"])
        dtop_p = (dtop_w - jnp.sum(dtop_w * top_p / total, -1,
                                   keepdims=True)) / total
    else:
        dtop_p = dtop_w
    counts = _count(top_i, n_exp).astype(jnp.float32)
    dprobs = jnp.sum(jnp.where(_chosen(top_i, n_exp), dtop_p[:, :, None],
                               0.0), axis=1)
    dbalance = daux * n_exp * counts[None, :] / (n * n)
    if sigmoid:
        # P = s / sum(s); then each score's own slope s (1 - s)
        total = jnp.sum(probs, -1, keepdims=True)
        dprobs = dprobs + (dbalance - jnp.sum(dbalance * probs / total, -1,
                                              keepdims=True)) / total
        dlogits = dprobs * probs * (1.0 - probs)
    else:
        dprobs = dprobs + dbalance
        dlogits = probs * (dprobs - jnp.sum(dprobs * probs, -1,
                                            keepdims=True))
    hi = jax.lax.Precision.HIGHEST
    x32, rw32 = x.astype(jnp.float32), router_w.astype(jnp.float32)
    dx = dx + jnp.dot(dlogits, rw32.T, precision=hi)

    ctx.set_output("X@GRAD", dx.reshape(xv.shape).astype(xv.dtype))
    ctx.set_output("RouterW@GRAD",
                   jnp.dot(x32.T, dlogits, precision=hi)
                   .astype(router_w.dtype))
    for slot, w, dw in zip(("WUp",) if relu2 else ("WGate", "WUp"), ws,
                           d_pre):
        ctx.set_output(slot + "@GRAD", dw.astype(w.dtype))
    ctx.set_output("WDown@GRAD", d_wd.astype(ws[-1].dtype))


@register_op("expert_bias_update", infer_shape=same_shape("Bias", "BiasOut"))
def expert_bias_update(ctx):
    """The selection bias's own rule, no gradient step (the source family's
    ``noaux_tc``): after a step's routing, ``bias_e += rate * sign(mean(c) -
    c_e)`` with ``c_e`` the step's assignments to expert e over ALL router
    outputs (``TopIdx`` of the layer's ``routed_experts``): an expert that
    got more than its share is chosen less readily at the next step. It
    writes ``BiasOut`` under ``Bias``'s own name, as batch-norm's moving
    statistics are written."""
    bias = data_of(ctx.input("Bias"))
    counts = _count(data_of(ctx.input("TopIdx")),
                    bias.shape[0]).astype(jnp.float32)
    step = float(ctx.attr("rate")) * jnp.sign(jnp.mean(counts) - counts)
    ctx.set_output("BiasOut", bias + step.astype(bias.dtype))

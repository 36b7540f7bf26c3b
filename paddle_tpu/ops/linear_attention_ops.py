"""Linear attention by the gated delta rule (Kimi Delta Attention, KDA) and
the small ops a KDA layer puts around it.

``gated_delta_rule`` keeps, per head, a state ``S`` [key, value] in float32
and reads it with the query:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``alpha_t = exp(g_t)`` a decay per key CHANNEL (``g`` is the log-decay,
<= 0) and ``beta_t`` a step size per head: the state decays first, and the
delta correction reads the decayed state. The op computes it in CHUNKS of
``chunk_size`` tokens (no op of the program walks token by token; the
recurrence lives in the plain reference). With ``G`` the cumulative
log-decay inside a chunk, ``S_0`` the state at its start and ``u_i`` the
pseudo-values of the delta rule,

    (I + Diag(beta) A) U = Diag(beta) (V - (K * e^G) S_0),
        A_ij = sum_c k_ic k_jc e^(G_ic - G_jc)   for j < i
    O   = (Q * e^G) S_0 + B U,
        B_ij = sum_c q_ic k_jc e^(G_ic - G_jc)   for j <= i
    S_C = Diag(e^(G_C)) S_0 + (K * e^(G_C - G))^T U

(the WY / UT form of the delta rule): ``U = W_v - W_k S_0`` with ``[W_v |
W_k]`` one unit-lower-triangular solve a chunk, everything that does not
read ``S`` computed for all chunks at once as batched products, and a scan
over the chunks that carries ``S`` alone (two products a step). The pairwise
decay ``e^(G_i - G_j)`` is never formed as ``e^(G_i) * e^(-G_j)``, which
overflows float32 at a decay of 1.6 nats a token over 64 tokens: a pair
either takes the difference first, channel by channel, or factors through a
row ``r`` between them, where both ``G_i - r`` and ``r - G_j`` are <= 0
(the jnp twin: differences inside a sub-block of ``SUB`` tokens, the later
sub-block's first row across them; the kernels: a row between the pair at
every scale, by halves). No exponent is ever positive, so the op is exact
at any decay (a channel wiped at every token included).

Products take the operands' compute type (bfloat16 under AMP) and
accumulate in float32, but the two pairwise-decay products, which are
float32 at every pass; the cumulative sums, the exponentials, the
triangular solve and the state are float32. The forward keeps the chunks'
starting states (``States`` [b, chunks, heads, key, value] float32: 134 MB
a layer at 4096 tokens of 32 heads of 128) and nothing else.

Two paths compute this, chosen at ONE site (``_route``) by the kernel
tier's rule, ``use_pallas("delta_rule", supported(shapes))``, the same
answer for the op and its grad op:

* the Pallas family ``delta_rule`` (ops/pallas/delta_rule.py; heads of
  whole 128-lane widths, float32 ``G``): a forward and a backward kernel
  that keep a chunk's terms in VMEM and carry the state (or its gradient)
  in scratch from chunk to chunk. The backward rebuilds a chunk's terms
  once from the inputs and the kept state and applies HAND-DERIVED
  gradients of the read, the state update, the solve (``dR = T^-T dW``,
  ``dT = -dR W^T`` on the strict triangle), the pairwise decays and the L2
  norms; ``jax.vjp`` of the twin is its test oracle.
* the jnp twin below (the CPU, ``kernel_tier=jnp``, any other shape, which
  under a Pallas tier bumps ``paddle_tpu_pallas_fallbacks{kernel=
  delta_rule}``): the chunk terms of all chunks as batched products and a
  scan that carries the state; its grad rebuilds what does not read the
  state, walks the chunks backwards with ``jax.vjp`` of one chunk's step at
  its kept state, and differentiates the rest with ``jax.vjp`` of the same
  chunked forward: no hand-derived formula there.

Beside it: ``causal_conv1d`` (a causal depthwise convolution, one filter a
channel, a bias where the op has one, then SiLU), ``kda_decay_gate`` (``g =
-exp(A_log) * softplus(x + dt_bias)``, float32) and ``gated_rms_norm``
(RMSNorm per head times a sigmoid gate; ``gate_first``: the Mamba form);
their grad ops are ``jax.vjp`` of their forwards, but ``causal_conv1d``'s
where the Pallas family ``causal_conv1d`` takes the op (``_conv_route``: a
forward and a backward kernel, each one pass over HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.registry import OpSpec, register_op, same_shape
from .common import G, data_of
from .pallas import kernel_span, use_pallas

L2_EPS = 1e-6           # in the L2 norm of queries and keys
SUB = 16                # rows of a sub-block: pairs inside one take
                        # G_i - G_j channel by channel
GROUP = 8               # heads whose chunk terms are built at a time


# ---------------------------------------------------------------------------
# gated_delta_rule
# ---------------------------------------------------------------------------

def _dot(a, b, spec, ct):
    """An einsum on the MXU: operands in the compute type ``ct``, float32
    accumulation; at float32 every pass (the CPU tests' exactness)."""
    return jnp.einsum(spec, a.astype(ct), b.astype(ct),
                      preferred_element_type=jnp.float32,
                      precision=(jax.lax.Precision.HIGHEST
                                 if ct == jnp.float32 else None))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _pair_decays(q, k, cum):
    """``A_ij = sum_c k_ic k_jc e^(cum_ic - cum_jc)`` for j < i and ``B_ij``
    the same with ``q_ic`` for j <= i, else 0, of every chunk: ``q``, ``k``,
    ``cum`` [..., C, d] float32 -> two [..., C, C] float32. Pairs inside a
    sub-block of ``SUB`` rows take the difference first, channel by channel
    (exact at any decay); a sub-block's rows meet the columns BEFORE it in
    one product through its first row ``r``, rows carrying ``e^(G_i -
    G_r)`` and columns ``e^(G_r - G_j)``, both <= 1. The products are
    float32 at every pass whatever the compute type: they are a few GFLOP
    a layer, and ``A`` feeds a triangular solve."""
    lead, (C, d) = k.shape[:-2], k.shape[-2:]
    sub = SUB if C % SUB == 0 else C
    ns = C // sub
    blocks = lead + (ns, sub, d)
    kb, gb = k.reshape(blocks), cum.reshape(blocks)
    i = jnp.arange(sub)
    decay = jnp.exp(jnp.minimum(gb[..., :, None, :] - gb[..., None, :, :],
                                0.0))                   # [.., sub, sub, d]
    first = gb[..., :1, :]
    grow = jnp.exp(gb - first)
    before = jnp.arange(C)[None, :] < (jnp.arange(ns) * sub)[:, None]
    cols = jnp.where(before[..., None], k[..., None, :, :] * jnp.exp(
        jnp.minimum(first - cum[..., None, :, :], 0.0)), 0.0)
    own = jnp.eye(ns, dtype=bool)[:, None, :, None]
    out = []
    for x, seen in ((k, i[None, :] < i[:, None]), (q, i[None, :] <= i[:, None])):
        xb = x.reshape(blocks)
        inner = jnp.sum(jnp.where(
            seen[..., None], xb[..., :, None, :] * kb[..., None, :, :] * decay,
            0.0), -1)                                   # [.., ns, sub, sub]
        if ns > 1:
            outer = _dot(xb * grow, cols, "...sid,...sjd->...sij",
                         jnp.float32).reshape(lead + (ns, sub, ns, sub))
            inner = jnp.where(own, inner[..., :, :, None, :], outer)
        out.append(inner.reshape(lead + (C, C)))
    return out


def _chunk_terms(q, k, v, g, beta, scale, ct):
    """Everything of the chunked form that does not read the state, for all
    chunks at once. q, k, g [N, b, H, C, dk], v [N, b, H, C, dv], beta
    [N, b, H, C] -> dict of float32 arrays."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    q = _l2(q) * scale
    k = _l2(k)
    cum = jnp.cumsum(g, axis=-2)
    last = cum[..., -1:, :]
    a, b = _pair_decays(q, k, cum)
    C = q.shape[-2]
    system = jnp.eye(C, dtype=jnp.float32) + beta[..., None] * a
    rhs = beta[..., None] * jnp.concatenate([v, k * jnp.exp(cum)], -1)
    w = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True)
    dv = v.shape[-1]
    return dict(w_v=w[..., :dv], w_k=w[..., dv:], b=b,
                q_in=q * jnp.exp(cum),              # reads S_0
                k_out=k * jnp.exp(last - cum),      # writes S_C
                keep=jnp.exp(last[..., 0, :]))      # [N, b, H, dk]


def _step(state, w_v, w_k, k_out, keep, ct):
    """One chunk: the state at its end from the state at its start."""
    u = w_v - _dot(w_k, state, "bhcd,bhde->bhce", ct)
    return keep[..., None] * state + _dot(k_out, u, "bhcd,bhce->bhde", ct)


def _read(terms, states, ct):
    """The outputs of all chunks from their starting states."""
    u = terms["w_v"] - _dot(terms["w_k"], states, "nbhcd,nbhde->nbhce", ct)
    return (_dot(terms["q_in"], states, "nbhcd,nbhde->nbhce", ct)
            + _dot(terms["b"], u, "nbhij,nbhje->nbhie", ct))


def _to_chunks(x, heads, chunk):
    """[b, T, heads * d] -> [N, b, heads, chunk, d] (T a multiple of the
    chunk), in x's own type: a bfloat16 array is moved as one."""
    b, t, e = x.shape
    x = x.reshape(b, t // chunk, chunk, heads, e // heads)
    return jnp.transpose(x, (1, 0, 3, 2, 4))


def _from_chunks(x):
    n, b, h, c, d = x.shape
    return jnp.transpose(x, (1, 0, 3, 2, 4)).reshape(b, n * c, h * d)


def _padded(chunk, *arrays):
    """The arrays with zeros after the last token up to a whole chunk: a
    token of zeros (k = v = 0, beta = 0, g = 0) leaves state and outputs as
    they are."""
    t = arrays[0].shape[1]
    pad = -t % chunk
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def _terms_of(q, k, v, g, beta, heads, chunk, scale, ct):
    """``_chunk_terms`` of whole [b, T, heads * d] arrays, ``GROUP`` heads at
    a time (heads do not meet): a group's pairwise decays inside sub-blocks
    are a [chunks, sub-blocks, SUB, SUB, d] tensor, 1 GB for 32 heads of 128
    at 4096 tokens where it is not fused away, and a backward pass keeps
    several. Each group is checkpointed: the backward pass keeps the inputs
    alone and rebuilds a group's terms when it reaches it."""
    q, k, v, g, beta = _padded(chunk, q, k, v, g, beta)
    beta = jnp.transpose(
        beta.reshape(beta.shape[0], -1, chunk, heads), (1, 0, 3, 2))
    args = [_to_chunks(x, heads, chunk) for x in (q, k, v, g)] + [beta]
    group = GROUP if heads % GROUP == 0 else heads
    if group == heads:
        return _chunk_terms(*args, scale, ct)

    def split(x):           # [N, b, H, ...] -> [H / group, N, b, group, ...]
        x = x.reshape(x.shape[:2] + (heads // group, group) + x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    def join(x):
        x = jnp.moveaxis(x, 0, 2)
        return x.reshape(x.shape[:2] + (heads,) + x.shape[4:])

    terms = jax.lax.map(
        jax.checkpoint(lambda a: _chunk_terms(*a, scale, ct)),
        tuple(split(x) for x in args))
    return {name: join(x) for name, x in terms.items()}


def _route(q, v, g, heads, chunk):
    """(the kernel module, "pallas" | "jnp"): ONE question for the forward
    and the backward, so they never disagree. The module is imported here,
    at the first dispatch, and not with the ops package."""
    from .pallas import delta_rule as dr
    return dr, "pallas" if use_pallas(
        "delta_rule", dr.supported(q, v, g, heads, chunk)) else "jnp"


def chunked_delta_rule(q, k, v, g, beta, heads, chunk, scale):
    """(out [b, T, heads * dv] in v's type, states [b, N, heads, dk, dv]
    float32: each chunk's starting state): the ``delta_rule`` Pallas
    family (ops/pallas/delta_rule.py) where the tier and the shapes allow
    it, the jnp twin below anywhere else."""
    dr, route = _route(q, v, g, heads, chunk)
    with kernel_span(route, "delta_rule"):
        if route == "jnp":
            return chunked_delta_rule_jnp(q, k, v, g, beta, heads, chunk,
                                          scale)
        out, states = dr.delta_rule_fwd(*_padded(chunk, q, k, v, g, beta),
                                        heads, chunk, scale)
        return out[:, :q.shape[1]], states


def chunked_delta_rule_bwd(q, k, v, g, beta, states, dout, heads, chunk,
                           scale):
    """Gradients of ``chunked_delta_rule``'s ``out`` to (q, k, v, g, beta)
    from the kept ``states``, by the route the forward took."""
    dr, route = _route(q, v, g, heads, chunk)
    with kernel_span(route, "delta_rule"):
        if route == "jnp":
            return chunked_delta_rule_bwd_jnp(q, k, v, g, beta, states, dout,
                                              heads, chunk, scale)
        grads = dr.delta_rule_bwd(*_padded(chunk, q, k, v, g, beta), states,
                                  *_padded(chunk, dout), heads, chunk, scale)
        return tuple(dx[:, :q.shape[1]] for dx in grads)


def chunked_delta_rule_jnp(q, k, v, g, beta, heads, chunk, scale):
    """The twin of ``delta_rule_fwd``: the chunk terms of all chunks as
    batched products, then a scan that carries the state alone."""
    ct = v.dtype
    t = q.shape[1]
    terms = _terms_of(q, k, v, g, beta, heads, chunk, scale, ct)
    n, b, h, _, dk = terms["w_k"].shape

    def body(state, x):
        return _step(state, *x, ct), state
    _, states = jax.lax.scan(
        body, jnp.zeros((b, h, dk, terms["w_v"].shape[-1]), jnp.float32),
        (terms["w_v"], terms["w_k"], terms["k_out"], terms["keep"]))
    out = _from_chunks(_read(terms, states, ct))[:, :t]
    return out.astype(v.dtype), jnp.swapaxes(states, 0, 1)


def chunked_delta_rule_bwd_jnp(q, k, v, g, beta, states, dout, heads, chunk,
                               scale):
    """The twin of ``delta_rule_bwd``: the terms rebuilt, the chunks walked
    backwards with ``jax.vjp`` of one chunk's step at its kept state, the
    rest by ``jax.vjp`` of the same chunked forward."""
    ct = v.dtype
    # the terms are rebuilt HERE: without the barrier the compiler finds the
    # forward op's own and keeps them alive from there to here instead
    q, k, v, g, beta, dout = jax.lax.optimization_barrier(
        (q, k, v, g, beta, dout))
    states = jnp.swapaxes(states, 0, 1)
    terms, back = jax.vjp(
        lambda *a: _terms_of(*a, heads, chunk, scale, ct), q, k, v, g, beta)
    (dout,) = _padded(chunk, dout)
    _, read_back = jax.vjp(lambda tm, s: _read(tm, s, ct), terms, states)
    d_terms, d_states = read_back(
        _to_chunks(dout, heads, chunk).astype(jnp.float32))

    def body(d_next, x):
        state, d_read, w_v, w_k, k_out, keep = x
        _, step_back = jax.vjp(
            lambda *a: _step(*a, ct), state, w_v, w_k, k_out, keep)
        d_state, *d_x = step_back(d_next)
        return d_state + d_read, d_x
    _, (d_wv, d_wk, d_ko, d_keep) = jax.lax.scan(
        body, jnp.zeros_like(states[0]),
        (states, d_states, terms["w_v"], terms["w_k"], terms["k_out"],
         terms["keep"]), reverse=True)
    d_terms = dict(d_terms, w_v=d_terms["w_v"] + d_wv,
                   w_k=d_terms["w_k"] + d_wk, k_out=d_ko, keep=d_keep)
    return back(d_terms)


_DELTA_SLOTS = ("Q", "K", "V", "G", "Beta")


def _delta_attrs(ctx, q):
    heads = int(ctx.attr("num_heads"))
    if q.shape[-1] % heads:
        raise ValueError(f"gated_delta_rule: {heads} heads do not fit Q "
                         f"{q.shape}")
    return (heads, int(ctx.attr("chunk_size", 64)),
            (q.shape[-1] // heads) ** -0.5)


def _delta_grad_maker(op):
    inputs = {s: op.input(s) for s in _DELTA_SLOTS}
    inputs["States"] = op.output("States")
    inputs["Out@GRAD"] = G(op.output("Out"))
    return [OpSpec("gated_delta_rule_grad", inputs,
                   {s + "@GRAD": G(op.input(s)) for s in _DELTA_SLOTS},
                   dict(op.attrs))]


def _delta_infer(op, block):
    v = block.var(op.input("V")[0])
    for name in op.output("Out"):
        out = block.var(name)
        out.shape = v.shape
        out.dtype = out.dtype or v.dtype


@register_op("gated_delta_rule", infer_shape=_delta_infer,
             grad=_delta_grad_maker)
def gated_delta_rule(ctx):
    """The gated delta rule over ``Q``, ``K`` [b, T, heads * dk] (each
    head L2-normalised here, the queries times dk^-0.5), ``V`` [b, T, heads * dv], the log-decay ``G`` [b, T, heads *
    dk] (<= 0, float32) and the step size ``Beta`` [b, T, heads], from a
    zero state, in chunks of ``chunk_size`` tokens (a last partial chunk is
    padded with tokens that change nothing). ``Out`` [b, T, heads * dv] in
    V's type; ``States`` the chunks' starting states, for the grad op."""
    q, k, v, g, beta = (data_of(ctx.input(s)) for s in _DELTA_SLOTS)
    out, states = chunked_delta_rule(q, k, v, g, beta, *_delta_attrs(ctx, q))
    ctx.set_output("Out", out)
    ctx.set_output("States", states)


@register_op("gated_delta_rule_grad")
def gated_delta_rule_grad(ctx):
    args = [data_of(ctx.input(s)) for s in _DELTA_SLOTS]
    grads = chunked_delta_rule_bwd(
        *args, data_of(ctx.input("States")),
        data_of(ctx.input("Out@GRAD")), *_delta_attrs(ctx, args[0]))
    for slot, x, dx in zip(_DELTA_SLOTS, args, grads):
        ctx.set_output(slot + "@GRAD", dx.astype(x.dtype))


# ---------------------------------------------------------------------------
# the ops around it: forward functions, grad ops by jax.vjp
# ---------------------------------------------------------------------------

def _vjp_grads(fn, ctx, args, dout):
    """Gradients of ``fn(ctx, *args)`` to ``args`` by ``jax.vjp``."""
    # the barrier keeps the compiler from finding the forward op's own
    # float32 intermediates and holding them from there to here, where
    # rebuilding them from the (bfloat16) inputs costs one fused pass
    args, dout = jax.lax.optimization_barrier((args, dout))
    out, back = jax.vjp(lambda *a: fn(ctx, *a), *args)
    return back(dout.astype(out.dtype))


def _register_with_vjp(op_type, slots, out_slot, fn, doc, out_dtype=None,
                       grad_fn=None):
    """Register ``op_type`` (inputs ``slots`` -> ``out_slot``, of the first
    input's shape and, but for ``out_dtype``, type) and its grad op,
    ``jax.vjp`` of the same ``fn(ctx, *inputs)`` unless the op brings its
    own ``grad_fn(ctx, inputs, dout)``. A slot the op was built without is
    left out of the call, and of the grad op."""
    grad_fn = grad_fn or functools.partial(_vjp_grads, fn)

    def maker(op):
        held = [s for s in slots if op.input(s)]
        inputs = {s: op.input(s) for s in held}
        inputs[out_slot + "@GRAD"] = G(op.output(out_slot))
        return [OpSpec(op_type + "_grad", inputs,
                       {s + "@GRAD": G(op.input(s)) for s in held},
                       dict(op.attrs))]

    def forward(ctx):
        ctx.set_output(out_slot, fn(ctx, *(
            data_of(ctx.input(s)) for s in slots if ctx.op.input(s))))

    def backward(ctx):
        held = [s for s in slots if ctx.op.input(s)]
        grads = grad_fn(ctx, [data_of(ctx.input(s)) for s in held],
                        data_of(ctx.input(out_slot + "@GRAD")))
        for slot, dx in zip(held, grads):
            ctx.set_output(slot + "@GRAD", dx)

    def infer(op, block):
        same_shape(slots[0], out_slot)(op, block)
        if out_dtype:
            for name in op.output(out_slot):
                block.var(name).dtype = out_dtype

    forward.__doc__ = doc
    forward.__name__, backward.__name__ = op_type, op_type + "_grad"
    register_op(op_type, infer_shape=infer, grad=maker)(forward)
    register_op(op_type + "_grad")(backward)


def _causal_conv1d(ctx, x, w, bias=None):
    taps = w.shape[0]
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    t = x.shape[1]
    back = jnp.pad(xf, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(back[:, j:j + t] * wf[j] for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def _conv_route(x, w):
    """(the kernel module, "pallas" | "jnp"): ONE question for the op and
    its grad op, so they never disagree. The module is imported here, at
    the first dispatch, and not with the ops package."""
    from .pallas import causal_conv1d as cc
    return cc, "pallas" if use_pallas(
        "causal_conv1d", cc.supported(x, w)) else "jnp"


def _conv_forward(ctx, x, w, bias=None):
    cc, route = _conv_route(x, w)
    with kernel_span(route, "causal_conv1d"):
        if route == "jnp":
            return _causal_conv1d(ctx, x, w, bias)
        return cc.causal_conv1d_fwd(x, w, bias)


def _conv_backward(ctx, args, dout):
    cc, route = _conv_route(*args[:2])
    with kernel_span(route, "causal_conv1d"):
        if route == "jnp":
            return _vjp_grads(_causal_conv1d, ctx, args, dout)
        x, w, *bias = args
        grads = cc.causal_conv1d_bwd(x, w, bias[0] if bias else None, dout)
        return [g.astype(a.dtype) for g, a in zip(grads, args)]


_register_with_vjp(
    "causal_conv1d", ("X", "Filter", "Bias"), "Out", _conv_forward,
    """A causal depthwise convolution over time: ``X`` [b, T, channels],
    ``Filter`` [taps, channels] (one filter a channel; the LAST tap meets
    the current token, the first the token ``taps - 1`` back; before the
    first token lie zeros), plus ``Bias`` [channels] where the op has one,
    then SiLU. Float32 inside, X's type out. By the kernel tier's rule,
    ``use_pallas("causal_conv1d", supported(x, w))``, the same answer for
    the op and its grad op: the Pallas family ``causal_conv1d``
    (ops/pallas/causal_conv1d.py: one kernel each way that reads its
    inputs from HBM once and writes its outputs once, hand-derived
    gradients) on a TPU for channels in whole 128-lane widths, tokens in
    whole sublane tiles, at most 8 taps and a whole time axis that fits a
    block in VMEM; ``_causal_conv1d`` above and ``jax.vjp`` of it on the
    CPU, under ``kernel_tier=jnp`` and for any other shape (which under a
    Pallas tier bumps ``paddle_tpu_pallas_fallbacks{kernel=
    causal_conv1d}``).""", grad_fn=_conv_backward)


def _kda_decay_gate(ctx, x, a_log, dt_bias):
    heads = a_log.shape[0]
    b, t, e = x.shape
    step = jax.nn.softplus(x.astype(jnp.float32)
                           + dt_bias.astype(jnp.float32))
    rate = jnp.exp(a_log.astype(jnp.float32))
    return (-rate[:, None] * step.reshape(b, t, heads, e // heads)) \
        .reshape(b, t, e)


_register_with_vjp(
    "kda_decay_gate", ("X", "ALog", "DtBias"), "Out", _kda_decay_gate,
    """The log-decay of the gated delta rule, one per key channel: ``Out =
    -exp(ALog[head]) * softplus(X + DtBias)`` for ``X`` [b, T, heads * dk],
    ``ALog`` [heads], ``DtBias`` [heads * dk]; float32 whatever X's
    type.""", out_dtype="float32")


def _gate_first_group_norm(ctx, x, gate, scale):
    """The Mamba form: ``x * silu(gate)``, RMSNorm over each group of
    ``group_size`` channels, times a scale per CHANNEL."""
    size = int(ctx.attr("group_size"))
    xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    g = xf.reshape(x.shape[:-1] + (-1, size))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + ctx.attr("epsilon", 1e-6))
    return (g.reshape(x.shape) * scale.astype(jnp.float32)).astype(x.dtype)


def _gated_rms_norm(ctx, x, gate, scale):
    if ctx.attr("gate_first", False):
        return _gate_first_group_norm(ctx, x, gate, scale)
    d = scale.shape[0]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, d))
    r = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                      + ctx.attr("epsilon", 1e-6))
    y = (xf * r * scale.astype(jnp.float32)).reshape(x.shape)
    return (y * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)


_register_with_vjp(
    "gated_rms_norm", ("X", "Gate", "Scale"), "Out", _gated_rms_norm,
    """RMSNorm over each head of ``X`` [b, T, heads * d] with ONE learned
    ``Scale`` [d] for all heads, times ``sigmoid(Gate)`` (Gate of X's
    shape). With the attr ``gate_first`` (the Mamba form) the gate is
    ``silu(Gate)`` and multiplies BEFORE the norm, the norm is over groups
    of ``group_size`` channels and ``Scale`` is one a channel [heads * d].
    Float32 inside, X's type out.""")


# ---------------------------------------------------------------------------
# gated_short_conv — the gated short-convolution mixer's core (LFM2)
# ---------------------------------------------------------------------------

def _gated_short_conv(ctx, x, w):
    """The plain formula: ``C * conv(B * x)``, float32 inside."""
    taps, h = w.shape
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    u = xf[..., :h] * xf[..., 2 * h:]
    t = x.shape[1]
    back = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(back[:, j:j + t] * wf[j] for j in range(taps))
    return (xf[..., h:2 * h] * z).astype(x.dtype)


def _gated_route(x, w):
    """(the kernel module, "pallas" | "jnp"): the ``causal_conv1d``
    family's question for its gated form, one answer for the op and its
    grad op."""
    from .pallas import causal_conv1d as cc
    return cc, "pallas" if use_pallas(
        "causal_conv1d", cc.gated_supported(x, w)) else "jnp"


def _gated_forward(ctx, x, w):
    cc, route = _gated_route(x, w)
    with kernel_span(route, "causal_conv1d"):
        if route == "jnp":
            return _gated_short_conv(ctx, x, w)
        return cc.gated_short_conv_fwd(x, w)


def _gated_backward(ctx, args, dout):
    cc, route = _gated_route(*args)
    with kernel_span(route, "causal_conv1d"):
        if route == "jnp":
            return _vjp_grads(_gated_short_conv, ctx, args, dout)
        return cc.gated_short_conv_bwd(*args, dout)


def _gated_infer(op, block):
    x = block.var(op.input("X")[0])
    for name in op.output("Out"):
        out = block.var(name)
        out.dtype = out.dtype or x.dtype
        if x.shape is not None and x.shape[-1] is not None:
            out.shape = tuple(x.shape[:-1]) + (int(x.shape[-1]) // 3,)


def _gated_grad_maker(op):
    return [OpSpec("gated_short_conv_grad",
                   {"X": op.input("X"), "Filter": op.input("Filter"),
                    "Out@GRAD": G(op.output("Out"))},
                   {"X@GRAD": G(op.input("X")),
                    "Filter@GRAD": G(op.input("Filter"))}, dict(op.attrs))]


@register_op("gated_short_conv", infer_shape=_gated_infer,
             grad=_gated_grad_maker)
def gated_short_conv(ctx):
    """The gated short convolution of LFM2's conv mixer: ``X`` [b, T, 3 *
    channels] is ``[B | C | x]`` (the in-projection's output in that
    order), ``Filter`` [taps, channels] (one filter a channel, the LAST tap
    on the current token, zeros before the first), and ``Out = C * conv(B
    * x)`` [b, T, channels]: no bias, no activation. Float32 inside, X's
    type out. By the kernel tier's rule, ``use_pallas("causal_conv1d",
    gated_supported(x, w))``, one answer for the op and its grad op: the
    gated form of the Pallas family ``causal_conv1d``
    (ops/pallas/causal_conv1d.py: one kernel each way that reads X once,
    keeps ``B * x``, the convolution and ``dz`` in VMEM and writes
    ``X@GRAD`` in X's own layout) on a TPU; ``_gated_short_conv`` and
    ``jax.vjp`` of it on the CPU, under ``kernel_tier=jnp`` and for any
    other shape."""
    ctx.set_output("Out", _gated_forward(ctx, data_of(ctx.input("X")),
                                         data_of(ctx.input("Filter"))))


@register_op("gated_short_conv_grad")
def gated_short_conv_grad(ctx):
    """``X@GRAD`` in X's type and layout, ``Filter@GRAD`` float32."""
    x, w = data_of(ctx.input("X")), data_of(ctx.input("Filter"))
    dx, dw = _gated_backward(ctx, (x, w), data_of(ctx.input("Out@GRAD")))
    ctx.set_output("X@GRAD", dx.astype(x.dtype))
    ctx.set_output("Filter@GRAD", dw.astype(jnp.float32))

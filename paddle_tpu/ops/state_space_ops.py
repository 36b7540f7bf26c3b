"""The state-space core of a Mamba-2 layer (SSD, "state-space duality") as a
Fluid op, ``ssd_scan``.

Per head (``num_heads`` H heads of P channels; ``n_groups`` G groups share a
``B`` and a ``C`` of N state coordinates, head h reading group ``h // (H /
G)``) the layer keeps a state ``S`` [P, N] in float32 and reads it with
``C``:

    dt_t = softplus(Dt_t + DtBias)          a step per head and token
    a_t  = exp(dt_t * A),  A = -exp(ALog)   ONE scalar decay a head and token
    S_t  = a_t S_{t-1} + dt_t x_t B_t^T     from a zero state
    y_t  = S_t C_t + D x_t

The op computes it in CHUNKS of ``chunk_size`` tokens (no op of the program
walks token by token; the recurrence lives in the plain reference). With
``cum`` the cumulative log-decay ``dt * A`` inside a chunk and ``S_0`` the
state at its start,

    Y   = (C B^T * L) (dt * X) + (C S_0^T) * e^cum + D X,
        L_ij = e^(cum_i - cum_j) for j <= i, else 0
    S_C = e^(cum_C) S_0 + (B * e^(cum_C - cum))^T (dt * X)

so a chunk is four products (``C B^T`` once a GROUP, the other three once a
head) and the states of all chunks follow from the chunks' own
contributions in one small product over the chunk axis (no loop). Every
exponent is a difference taken first and never positive, so the op is exact
at any decay.

Products take the operands' compute type (bfloat16 under AMP) and
accumulate in float32; ``dt``, the cumulative sums, the exponentials and
the state are float32. The forward keeps the chunks' starting states
(``States`` [b, chunks, heads, P, N] float32: 67 MB a layer at 4096 tokens
of 64 heads of 64 x 128) and nothing else. The grad op reads them: it
rebuilds the terms that do not read the state, differentiates them and the
read with ``jax.vjp`` of the forward's own functions, and takes the
gradient of the pass over the chunk states BY HAND from the kept states
(the total gradient of each chunk's end state is the same small product
transposed; a chunk's decay gets ``e^(cum_C) <dS_C, S_0>``); it never runs
the pass again.

Two paths compute this, chosen at ONE site (``_route``) by the kernel tier's
rule, ``use_pallas("ssd_scan", supported(shapes))``, the same answer for the
op and its grad op:

* the Pallas family ``ssd_scan`` (ops/pallas/ssd_scan.py; a group's ``R *
  P`` channels and ``N`` in whole 128-lane widths, chunks of at most 256
  tokens): a forward and a backward kernel that keep a chunk's terms in VMEM
  and carry the group's states (or their gradient) in scratch from chunk to
  chunk, with hand-derived gradients; ``_prepare`` (the softplus, ``dt *
  A``, the cumulative sum: 1 MB) and its ``jax.vjp`` stay here. On a TPU v5
  lite at 4096 tokens, 64 heads of 64 in 8 groups of state 128, chunks of
  128, bfloat16 (``tools/kernel_probe.py --only ssd_scan``, PR 39): forward
  0.454 ms of device time against 1.720 for the program below, backward
  0.973 against 3.293.
* the plain chunked program below (the CPU, ``kernel_tier=jnp``, any other
  shape, which under a Pallas tier bumps ``paddle_tpu_pallas_fallbacks{
  kernel=ssd_scan}``): the kernels' twin and test oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import OpSpec, register_op
from ..obs.metrics import REGISTRY as _METRICS
from .common import G, data_of
from .linear_attention_ops import _dot, _padded
from .pallas import kernel_span, use_pallas

_M_SSD = _METRICS.gauge(
    "paddle_tpu_ssd_scan",
    "ssd_scan as last traced: kind=chunk the tokens of a chunk, kind=chunks "
    "the chunks of a sequence (the last one padded), kind=heads, kind=state "
    "the float32 elements of one head's state (head_dim * state_size)",
    labels=("kind",))

_HI = jax.lax.Precision.HIGHEST


def _route(x, b, heads, groups, chunk):
    """(the kernel module, "pallas" | "jnp"): ONE question for the op and
    its grad op, so they never disagree. The module is imported here, at the
    first dispatch, and not with the ops package."""
    from .pallas import ssd_scan as ss
    return ss, "pallas" if use_pallas(
        "ssd_scan", ss.supported(x, b, heads, groups, chunk)) else "jnp"


def _prepare(dt_raw, dt_bias, a_log, chunk):
    """(dt, cum) [b, chunks, chunk, H] float32: the step after its softplus
    and the cumulative log-decay inside each chunk. Tokens of padding after
    the last (up to a whole chunk) take a step of zero: no decay, no
    input."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    log_decay = -jnp.exp(a_log.astype(jnp.float32)) * dt
    dt, log_decay = (_chunked(v, chunk) for v in (dt, log_decay))
    return dt, jnp.cumsum(log_decay, axis=2)


def _chunked(x, chunk, split=()):
    """[b, T, e] -> [b, chunks, chunk, *split] with zeros after the last
    token up to a whole chunk (``split`` factors e)."""
    b, t, e = x.shape
    pad = -t % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x.reshape((b, (t + pad) // chunk, chunk) + tuple(split or (e,)))


def _per_group(v, groups):
    """[b, n, C, H] -> [b, n, C, G, H / G]."""
    return v.reshape(v.shape[:3] + (groups, -1))


def _local(x, b, c, d, dt, cum, ct):
    """What a chunk gives without reading the state. x [b, n, C, G, R, P]
    (R heads a group), b, c [b, n, C, G, N], d [G, R], dt, cum [b, n, C, G,
    R] -> (y [b, n, C, G, R, P] float32: the pairs inside the chunk and the
    skip; s [b, n, G, R, P, N] float32: the chunk's own contribution to the
    state at its end)."""
    xf = x.astype(jnp.float32)
    n_tok = x.shape[2]
    cb = _dot(c, b, "bnigs,bnjgs->bngij", ct)
    cum_h = jnp.moveaxis(cum, 2, -1)                   # [b, n, G, R, C]
    seen = jnp.arange(n_tok)[:, None] >= jnp.arange(n_tok)[None, :]
    decay = jnp.where(seen, jnp.exp(jnp.minimum(
        cum_h[..., :, None] - cum_h[..., None, :], 0.0)), 0.0)
    xdt = xf * dt[..., None]
    y = _dot(cb[:, :, :, None] * decay, xdt, "bngrij,bnjgrp->bnigrp", ct)
    y = y + d.astype(jnp.float32)[..., None] * xf
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    s = _dot(b, xdt * to_end[..., None], "bnjgs,bnjgrp->bngrps", ct)
    return y, s


def _pass_weights(last):
    """``last`` [b, n, G, R]: each chunk's whole log-decay -> W [b, z, c, G,
    R]: what is left at the START of chunk z of a state written at the END
    of chunk c (c < z; 0 elsewhere)."""
    through = jnp.cumsum(last, axis=1)
    seg = (through - last)[:, :, None] - through[:, None, :]
    n = last.shape[1]
    earlier = (jnp.arange(n)[None, :] < jnp.arange(n)[:, None])
    return jnp.where(earlier[None, :, :, None, None],
                     jnp.exp(jnp.minimum(seg, 0.0)), 0.0)


def _read(c, cum, states, ct):
    """What the state at a chunk's start gives its tokens."""
    y = _dot(c, states, "bnigs,bngrps->bnigrp", ct)
    return y * jnp.exp(cum)[..., None]


def _shapes(x, b, heads, groups):
    p, n = x.shape[-1] // heads, b.shape[-1] // groups
    if x.shape[-1] % heads or b.shape[-1] % groups or heads % groups:
        raise ValueError(f"ssd_scan: {heads} heads in {groups} groups do "
                         f"not fit X {x.shape} and B {b.shape}")
    return heads // groups, p, n


def _terms(x, b, c, d, dt, cum, heads, groups, chunk, ct):
    """``_local`` of whole [b, T, .] arrays."""
    r, p, n = _shapes(x, b, heads, groups)
    return _local(_chunked(x, chunk, (groups, r, p)),
                  _chunked(b, chunk, (groups, n)),
                  _chunked(c, chunk, (groups, n)), d.reshape(groups, r),
                  _per_group(dt, groups), _per_group(cum, groups), ct)


def _read_of(c, cum, states, groups, chunk, ct):
    n = c.shape[-1] // groups
    return _read(_chunked(c, chunk, (groups, n)), _per_group(cum, groups),
                 states, ct)


def ssd_chunked(x, dt_raw, b, c, a_log, dt_bias, d, heads, groups, chunk):
    """(out [b, T, heads * P] in x's type, states [b, chunks, heads, P, N]
    float32: each chunk's starting state): the ``ssd_scan`` Pallas family
    (ops/pallas/ssd_scan.py) where the tier and the shapes allow it, the jnp
    twin below anywhere else."""
    ss, route = _route(x, b, heads, groups, chunk)
    with kernel_span(route, "ssd_scan"):
        if route == "jnp":
            return ssd_chunked_jnp(x, dt_raw, b, c, a_log, dt_bias, d, heads,
                                   groups, chunk)
        dt, cum = _prepare(dt_raw, dt_bias, a_log, chunk)
        out, states = ss.ssd_scan_fwd(*_padded(chunk, x, b, c), d, dt, cum,
                                      heads, groups)
        return out[:, :x.shape[1]], states


def ssd_chunked_bwd(x, dt_raw, b, c, a_log, dt_bias, d, states, dout, heads,
                    groups, chunk):
    """Gradients of ``ssd_chunked``'s ``out`` to (x, dt_raw, b, c, a_log,
    dt_bias, d) from the kept ``states``, by the route the forward took."""
    ss, route = _route(x, b, heads, groups, chunk)
    with kernel_span(route, "ssd_scan"):
        if route == "jnp":
            return ssd_chunked_bwd_jnp(x, dt_raw, b, c, a_log, dt_bias, d,
                                       states, dout, heads, groups, chunk)
        (dt, cum), prepare_back = jax.vjp(
            lambda *a: _prepare(*a, chunk), dt_raw, dt_bias, a_log)
        dx, db, dc, dd, ddt, dcum = ss.ssd_scan_bwd(
            *_padded(chunk, x, b, c), d, dt, cum, states,
            *_padded(chunk, dout), heads, groups)
        ddt_raw, ddt_bias, da_log = prepare_back((ddt, dcum))
        t = x.shape[1]
        return dx[:, :t], ddt_raw, db[:, :t], dc[:, :t], da_log, ddt_bias, dd


def ssd_chunked_jnp(x, dt_raw, b, c, a_log, dt_bias, d, heads, groups,
                    chunk):
    """The twin of ``ssd_scan_fwd``: the plain chunked program."""
    ct = x.dtype
    t = x.shape[1]
    dt, cum = _prepare(dt_raw, dt_bias, a_log, chunk)
    y, s = _terms(x, b, c, d, dt, cum, heads, groups, chunk, ct)
    last = _per_group(cum, groups)[:, :, -1]
    states = jnp.einsum("bzcgr,bcgrps->bzgrps", _pass_weights(last), s,
                        precision=_HI)
    y = y + _read_of(c, cum, states, groups, chunk, ct)
    out = y.reshape(y.shape[0], -1, x.shape[-1])[:, :t]
    return out.astype(x.dtype), states.reshape(
        states.shape[:2] + (heads,) + states.shape[4:])


def ssd_chunked_bwd_jnp(x, dt_raw, b, c, a_log, dt_bias, d, states, dout,
                        heads, groups, chunk):
    """The twin of ``ssd_scan_bwd`` (with ``_prepare``'s own backward)."""
    ct = x.dtype
    # the terms are rebuilt HERE: without the barrier the compiler finds the
    # forward op's own and keeps them alive from there to here instead
    x, dt_raw, b, c, dout = jax.lax.optimization_barrier(
        (x, dt_raw, b, c, dout))
    states = states.reshape(          # [b, n, H, P, N] -> [b, n, G, R, P, N]
        states.shape[:2] + (groups, -1) + states.shape[3:])
    (dt, cum), prepare_back = jax.vjp(
        lambda *a: _prepare(*a, chunk), dt_raw, dt_bias, a_log)
    _, terms_back = jax.vjp(
        lambda *a: _terms(*a, heads, groups, chunk, ct),
        x, b, c, d, dt, cum)
    _, read_back = jax.vjp(
        lambda *a: _read_of(*a, groups, chunk, ct), c, cum, states)
    r, p, _ = _shapes(x, b, heads, groups)
    dy = _chunked(dout.astype(jnp.float32), chunk, (groups, r, p))
    dc_read, dcum_read, dstates = read_back(dy)
    # the pass over the chunk states, by hand: the total gradient of the
    # state at the END of chunk c gathers every later chunk's read
    last = _per_group(cum, groups)[:, :, -1]
    d_end = jnp.einsum("bzcgr,bzgrps->bcgrps", _pass_weights(last),
                       dstates, precision=_HI)
    dlast = jnp.exp(last) * jnp.sum(d_end * states, axis=(-1, -2))
    dx, db, dc, dd, ddt, dcum = terms_back((dy, d_end))
    dcum = dcum + dcum_read
    dcum = dcum.at[:, :, -1].add(dlast.reshape(dcum[:, :, -1].shape))
    ddt_raw, ddt_bias, da_log = prepare_back((ddt, dcum))
    return dx, ddt_raw, db, dc + dc_read, da_log, ddt_bias, dd


_SSD_SLOTS = ("X", "Dt", "B", "C", "ALog", "DtBias", "D")


def _ssd_attrs(ctx):
    return (int(ctx.attr("num_heads")), int(ctx.attr("n_groups", 1)),
            int(ctx.attr("chunk_size", 128)))


def _ssd_grad_maker(op):
    inputs = {s: op.input(s) for s in _SSD_SLOTS}
    inputs["States"] = op.output("States")
    inputs["Out@GRAD"] = G(op.output("Out"))
    return [OpSpec("ssd_scan_grad", inputs,
                   {s + "@GRAD": G(op.input(s)) for s in _SSD_SLOTS},
                   dict(op.attrs))]


def _ssd_infer(op, block):
    x = block.var(op.input("X")[0])
    for name in op.output("Out"):
        out = block.var(name)
        out.shape = x.shape
        out.dtype = out.dtype or x.dtype


@register_op("ssd_scan", infer_shape=_ssd_infer, grad=_ssd_grad_maker)
def ssd_scan(ctx):
    """The Mamba-2 state-space core over ``X`` [b, T, heads * P], the raw
    step ``Dt`` [b, T, heads], ``B`` and ``C`` [b, T, n_groups * N], ``ALog``,
    ``DtBias`` and the skip ``D`` [heads], from a zero state, in chunks of
    ``chunk_size`` tokens (a last partial chunk is padded with tokens that
    change nothing). ``Out`` [b, T, heads * P] in X's type; ``States`` the
    chunks' starting states, for the grad op."""
    args = [data_of(ctx.input(s)) for s in _SSD_SLOTS]
    heads, groups, chunk = _ssd_attrs(ctx)
    x, b = args[0], args[2]
    _, p, n = _shapes(x, b, heads, groups)
    for kind, value in (("chunk", chunk), ("chunks", -(-x.shape[1] // chunk)),
                        ("heads", heads), ("state", p * n)):
        _M_SSD.labels(kind=kind).set(value)
    out, states = ssd_chunked(*args, heads, groups, chunk)
    ctx.set_output("Out", out)
    ctx.set_output("States", states)


@register_op("ssd_scan_grad")
def ssd_scan_grad(ctx):
    args = [data_of(ctx.input(s)) for s in _SSD_SLOTS]
    grads = ssd_chunked_bwd(*args, data_of(ctx.input("States")),
                            data_of(ctx.input("Out@GRAD")), *_ssd_attrs(ctx))
    for slot, v, dv in zip(_SSD_SLOTS, args, grads):
        ctx.set_output(slot + "@GRAD", dv.reshape(v.shape).astype(v.dtype))

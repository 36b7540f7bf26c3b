"""The chunked gated delta rule in VMEM: the Pallas family ``delta_rule``.

Two kernels, ``delta_rule_fwd`` and ``delta_rule_bwd``, compute what
``ops/linear_attention_ops.py`` states (its docstring's equations are the
contract) for one (batch, head) a grid row, a chunk a grid step, the chunk
axis innermost and sequential. Everything a chunk needs between reading its
``q``, ``k``, ``v``, ``g``, ``beta`` and writing its output (or its
gradients) is a value in VMEM; the ``[dk, dv]`` float32 state (forward) or
its gradient (backward, walked from the last chunk to the first) is carried
in VMEM scratch. The forward writes ``Out`` and each chunk's starting state
and nothing else; the backward rebuilds a chunk's terms ONCE from the inputs
and the kept state and applies hand-derived gradients (below). Arrays keep
the model's layout ``[b, T, heads * d]``: a block spec's last index picks
the head's 128-lane columns, so nothing is transposed on the way in or out.

**Orientation.** ``A`` and ``B`` are built TRANSPOSED and side by side,
``abt[j, i] = A[i, j]`` and ``abt[j, C + i] = B[i, j]`` (a ``[C, 2C]``
array: at chunks of 64 exactly one 128-lane row of vregs), because every
later use is a product, and a product contracts whichever axis it is told
to; so is the inverse of the unit triangular system.

**The pairwise decays** ``e^(G_i - G_j)`` never take a positive exponent
(the op is exact at any decay) and never sum across lanes: pairs ``j < i``
are split BY HALVES. At level ``h`` (1, 2, 4, ... below the chunk) a pair
belongs to the level where ``i`` and ``j`` first part: same block of ``2h``
tokens, ``j`` in its lower half, ``i`` in its upper. Through the lower
half's LAST row ``r`` both ``G_i - G_r`` and ``G_r - G_j`` are <= 0, so the
level is ONE float32 product of ``k_j e^(G_r - G_j)`` with ``[k_i | q_i]
e^(G_i - G_r)`` on the MXU, kept where the level's mask holds; ``B``'s
diagonal is a row-wise dot. This is the scheme of the jnp twin's
``_pair_decays`` (differences first, products through a row between the
pair) taken down to single tokens, which turns its ``[16, 16, 128]`` sums
over channels into products; the reference rows' own derivative is zero
(a product through ``r`` does not depend on ``r``) and is not computed.

**The solve** ``(I + Diag(beta) A) W = Diag(beta) [V | K e^G]`` is a product
with the explicit inverse, which the backward needs twice more (``dR =
T^-T dW``, ``dT = -dR W^T``): the diagonal blocks of ``SUB`` tokens by the
finite Neumann product ``(I - L)(I + L^2)(I + L^4)...`` (``L`` is
nilpotent there), the blocks below them by the same product over blocks.

Precision is the twin's: the pairwise-decay products, the cumulative sums
(shifted adds), the exponentials, the inverse and the
state in float32 (products at every pass); the four products with the state and with
``U`` in the operands' compute type (bfloat16 under AMP) with float32
accumulation.

The jnp twin is ``ops/linear_attention_ops.py``'s chunked scan, which is
also what runs where the family is not on the tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu
from ..linear_attention_ops import L2_EPS

SUB = 16                # tokens of a diagonal block of the inverse
LANES = 128
VMEM_LIMIT = 64 * 1024 * 1024

NN = ((1,), (0,))       # a @ b
NT = ((1,), (1,))       # a @ b.T
TN = ((0,), (0,))       # a.T @ b


def supported(q, v, g, heads, chunk):
    """Shapes only: key and value head sizes whole 128-lane widths, a chunk
    of whole sub-blocks (at most 256 tokens: two heads' systems side by side
    stay a few tiles), ``g`` float32, and the blocks of a step inside the
    VMEM budget."""
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    return (q.shape[-1] % heads == 0 and v.shape[-1] % heads == 0
            and dk % LANES == 0 and dv % LANES == 0
            and chunk % SUB == 0 and chunk <= 2 * LANES
            and g.dtype == jnp.float32
            and _vmem_bytes(chunk, dk, dv) <= VMEM_LIMIT // 2)


def _vmem_bytes(chunk, dk, dv):
    """What a backward step holds at most, for each of its ``HEADS`` heads:
    the state and its gradient (in, scratch; the pipeline's two buffers),
    and some forty ``[chunk, dk + dv]`` float32 values."""
    return HEADS * 4 * (5 * dk * dv + 40 * chunk * (dk + dv))


def _f32dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ctdot(a, b, dims, ct):
    """A product in the compute type ``ct`` with float32 accumulation (at
    float32 every pass), as the twin's ``_dot``."""
    if ct == jnp.float32:
        return _f32dot(a.astype(ct), b.astype(ct), dims)
    return jax.lax.dot_general(a.astype(ct), b.astype(ct), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _unit(x):
    """(x / |x|, 1 / |x|) over lanes, float32."""
    x = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)
    return x * r, r


def _unit_bwd(unit, r, d_unit):
    return r * (d_unit - unit * jnp.sum(unit * d_unit, axis=1, keepdims=True))


def _to_col(row):
    """[1, n] -> [n, 1]."""
    n = row.shape[1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col):
    """[n, 1] -> [1, n]."""
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _cumsum(g, reverse=False):
    """Inclusive sums over the chunk's tokens (rows), float32, from the first
    token down or (``reverse``) from the last up: log2(C) shifted adds."""
    C = g.shape[0]
    row = _iota(g.shape, 0)
    shift = 1
    while shift < C:
        if reverse:
            g = g + jnp.where(row < C - shift,
                              pltpu.roll(g, C - shift, 0), 0.0)
        else:
            g = g + jnp.where(row >= shift, pltpu.roll(g, shift, 0), 0.0)
        shift *= 2
    return g


def _levels(cum, kn, qn):
    """The by-halves levels of the pairwise decays: for each, ``h``, the
    stacked rows ``[k_i | q_i] e^(G_i - G_r)`` [2C, d], the columns ``k_j
    e^(G_r - G_j)`` [C, d], their two decay factors and the level's mask on
    a ``[C, 2C]`` product (``j`` down, ``i`` across, twice)."""
    C = cum.shape[0]
    j = _iota((C, 2 * C), 0)
    lane = _iota((C, 2 * C), 1)
    i = jnp.where(lane >= C, lane - C, lane)
    upper = _iota(cum.shape, 0)
    stacked = jnp.concatenate([kn, qn], axis=0)
    last = cum              # cum at the last row of each token's block of h
    h = 1
    while h < C:
        grow = jnp.exp(jnp.minimum(cum - pltpu.roll(last, h, 0), 0.0))
        shrink = jnp.exp(jnp.minimum(last - cum, 0.0))
        mask = (((i & h) != 0) & ((j & h) == 0)
                & ((i >> h.bit_length()) == (j >> h.bit_length())))
        yield (h, stacked * jnp.concatenate([grow, grow], axis=0),
               kn * shrink, grow, shrink, mask)
        last = jnp.where((upper & h) != 0, last, pltpu.roll(last, C - h, 0))
        h *= 2


def _halves(h, n, upper=False):
    """(cut, spread) of level ``h`` on ``n`` rows: ``cut`` keeps the rows of
    the blocks' lower (``upper``: upper) halves, the only ``j`` (``i``) of
    the level, so that a product streams half the rows; ``spread`` puts
    them back with zeros between. Whole tiles of sublanes move, so only
    where a half is one (``h`` >= 8): None below that."""
    if h % 8 or n % (2 * h):
        return None
    first = h if upper else 0

    def cut(x):
        return jnp.concatenate([x[b + first:b + first + h]
                                for b in range(0, n, 2 * h)], axis=0)

    def spread(x):
        zeros = jnp.zeros((h,) + x.shape[1:], x.dtype)
        return jnp.concatenate(
            [part for b in range(0, n // 2, h)
             for part in ((zeros, x[b:b + h]) if upper
                          else (x[b:b + h], zeros))], axis=0)
    return cut, spread


def _masks(C):
    """On a ``[C, 2C]`` array of ``[A^T | B^T]``: B's diagonal, and what of
    it is in use at all (``j < i`` for A, ``j <= i`` for B)."""
    j = _iota((C, 2 * C), 0)
    lane = _iota((C, 2 * C), 1)
    i = jnp.where(lane >= C, lane - C, lane)
    diagonal = (lane >= C) & (j == i)
    return diagonal, (j < i) | diagonal


def _pair_decays(cum, kn, qn):
    """``[A^T | B^T]`` [C, 2C] float32."""
    C = cum.shape[0]
    abt = jnp.zeros((C, 2 * C), jnp.float32)
    for h, rows, cols, _, _, mask in _levels(cum, kn, qn):
        halves = _halves(h, C)
        if halves:
            cut, spread = halves
            p = spread(_f32dot(cut(cols), rows, NT))
        else:
            p = _f32dot(cols, rows, NT)
        abt = jnp.where(mask, p, abt)
    diagonal, _ = _masks(C)
    return jnp.where(diagonal, jnp.sum(qn * kn, axis=1, keepdims=True), abt)


def _pair_decays_bwd(cum, kn, qn, d_abt):
    """Gradients of ``_pair_decays`` to (kn, qn, cum) from ``d_abt`` (zero
    outside what is in use)."""
    C = cum.shape[0]
    d_kn, d_qn, d_cum = (jnp.zeros_like(cum) for _ in range(3))
    for h, rows, cols, grow, shrink, mask in _levels(cum, kn, qn):
        d_p = jnp.where(mask, d_abt, 0.0)
        halves = _halves(h, C)
        if halves:
            cut, spread = halves
            cut_i, spread_i = _halves(h, 2 * C, upper=True)
            d_p = cut(d_p)
            d_cols = spread(_f32dot(d_p, rows, NN))         # [C, d]
            d_rows = spread_i(_f32dot(cut_i(d_p.T), cut(cols), NN))
        else:
            d_cols = _f32dot(d_p, rows, NN)
            d_rows = _f32dot(d_p, cols, TN)                 # [2C, d]
        d_kn = d_kn + d_cols * shrink + d_rows[:C] * grow
        d_qn = d_qn + d_rows[C:] * grow
        through = d_rows * rows
        d_cum = d_cum + through[:C] + through[C:] - d_cols * cols
    diagonal, _ = _masks(C)
    d_diag = jnp.sum(jnp.where(diagonal, d_abt, 0.0), axis=1, keepdims=True)
    return d_kn + d_diag * qn, d_qn + d_diag * kn, d_cum


def _inverse(lt, C):
    """``(I + lt)^-1`` for ``lt`` [n, n] strictly upper triangular and block
    diagonal in blocks of ``C`` (a group's heads). The diagonal blocks of
    ``SUB`` first, by the finite product ``(I - L)(I + L^2)(I + L^4)...``
    on all of them at once as a STRIP ``[SUB, n]`` (block ``b`` in lanes
    ``b * SUB`` on): a strip times a block-diagonal matrix is the strip of
    the blocks' products, at ``SUB`` rows through the MXU and not ``n``,
    and the running product and the next square, stacked, share one pass.
    Then the blocks above them inside each ``C``, by the same product over
    blocks."""
    n = lt.shape[0]
    same = (_iota((n, n), 0) // SUB) == (_iota((n, n), 1) // SUB)
    eye = (_iota((n, n), 0) == _iota((n, n), 1)).astype(jnp.float32)

    def strip(blocks):          # block diagonal [n, n] -> [SUB, n]
        return sum(blocks[b:b + SUB] for b in range(0, n, SUB))

    def diagonal(s):            # and back
        return jnp.where(same, jnp.concatenate([s] * (n // SUB), axis=0), 0.0)

    inner = jnp.where(same, lt, 0.0)
    x = strip(eye - inner)
    square = _f32dot(strip(inner), inner, NN)
    covered = 4
    while covered < SUB:
        both = _f32dot(jnp.concatenate([x, square], axis=0),
                       diagonal(square), NN)
        x, square = x + both[:SUB], both[SUB:]
        covered *= 2
    x = diagonal(x + _f32dot(x, diagonal(square), NN))
    if C == SUB:
        return x
    # (I + m)^-1 = (I - m)(I + m^2)(I + m^4)... for m nilpotent at C / SUB
    m = _f32dot(x, jnp.where(same, 0.0, lt), NN)
    y, square, covered = eye - m, _f32dot(m, m, NN), 2
    while covered < C // SUB:
        y = y + _f32dot(y, square, NN)
        covered *= 2
        if covered < C // SUB:
            square = _f32dot(square, square, NN)
    return _f32dot(y, x, NN)


def _terms(q, k, v, g, row, col, scale):
    """What of a head's chunk reads neither the state nor the system's
    inverse (``row`` [1, 2C] is ``[beta | 0]`` across lanes, ``col`` [C, 1]
    beta down the rows)."""
    C = g.shape[0]
    t = {"row": row, "col": col}
    t["q_unit"], t["q_r"] = _unit(q)
    t["kn"], t["k_r"] = _unit(k)
    t["qn"] = t["q_unit"] * scale
    t["cum"] = cum = _cumsum(g)
    t["abt"] = _pair_decays(cum, t["kn"], t["qn"])
    t["e_cum"] = e_cum = jnp.exp(cum)
    t["last"] = last = cum[C - 1:C, :]
    t["e_out"] = jnp.exp(last - cum)
    t["k_in"], t["q_in"] = t["kn"] * e_cum, t["qn"] * e_cum
    t["k_out"] = t["kn"] * t["e_out"]
    t["raw"] = jnp.concatenate([v.astype(jnp.float32), t["k_in"]], axis=1)
    return t


def _solve(group):
    """The inverse of a group's systems (one head, or two side by side as
    one block-diagonal matrix: two chains of dependent products become one)
    and each head's ``w = [W_v | W_k]``."""
    C = group[0]["cum"].shape[0]
    scaled = [t["abt"] * t["row"] for t in group]   # zero right of lane C
    if len(group) == 1:
        lt = scaled[0][:, :C]
    else:
        lt = jnp.concatenate([scaled[0], pltpu.roll(scaled[1], C, 1)], axis=0)
    inv = _inverse(lt, C)
    w = _f32dot(inv, jnp.concatenate([t["col"] * t["raw"] for t in group],
                                     axis=0), TN)
    for n, t in enumerate(group):
        t["w"] = w[n * C:(n + 1) * C]
    return inv


def _groups(block, **refs):
    """The block's heads in groups of ``GROUP``: for each head its number
    and, by name, each ref's slice of it (``refs``: name=(ref, the head's
    lanes in a [1, C, heads * width] block, or None for a [1, heads, 1, ...]
    or [heads, ...] block))."""
    def of_head(ref, width, h):
        if width:
            return ref.at[0, :, h * width:(h + 1) * width]
        return ref.at[0, h, 0] if len(ref.shape) == 5 else ref.at[h]

    size = GROUP if block % GROUP == 0 else 1
    for first in range(0, block, size):
        yield [(h, {name: of_head(ref, width, h)
                    for name, (ref, width) in refs.items()})
               for h in range(first, first + size)]


def _group_terms(heads, scale):
    return [_terms(*(r[name][...] for name in ("q", "k", "v", "g", "row",
                                               "col")), scale)
            for _, r in heads]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, row_ref, col_ref, o_ref,
                states_ref, s_ref, *, scale, ct, block):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    C = g_ref.shape[1]
    dk, dv = (x.shape[2] // block for x in (q_ref, v_ref))
    for heads in _groups(block, q=(q_ref, dk), k=(k_ref, dk), v=(v_ref, dv),
                         g=(g_ref, dk), row=(row_ref, None),
                         col=(col_ref, None), o=(o_ref, dv),
                         s=(s_ref, None)):
        group = _group_terms(heads, scale)
        _solve(group)
        for (h, r), t in zip(heads, group):
            state = r["s"][...]
            states_ref[0, 0, h] = state
            u = t["w"][:, :dv] - _ctdot(t["w"][:, dv:], state, NN, ct)
            out = (_ctdot(t["q_in"], state, NN, ct)
                   + _ctdot(t["abt"], u, TN, ct)[C:])
            r["o"][...] = out.astype(o_ref.dtype)
            r["s"][...] = (_to_col(jnp.exp(t["last"])) * state
                           + _ctdot(t["k_out"], u, TN, ct))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, row_ref, col_ref, states_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref, *,
                scale, ct, block):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    C = g_ref.shape[1]
    dk, dv = (x.shape[2] // block for x in (q_ref, v_ref))
    for heads in _groups(block, q=(q_ref, dk), k=(k_ref, dk), v=(v_ref, dv),
                         g=(g_ref, dk), row=(row_ref, None),
                         col=(col_ref, None), do=(do_ref, dv),
                         ds=(ds_ref, None), dq=(dq_ref, dk), dk=(dk_ref, dk),
                         dv=(dv_ref, dv), dg=(dg_ref, dk),
                         dbeta=(dbeta_ref, None)):
        group = _group_terms(heads, scale)
        inv = _solve(group)
        for (h, r), t in zip(heads, group):
            _read_bwd(t, states_ref[0, 0, h], r["do"][...], r["ds"], ct)
        # the solve: dR = T^-T dW for the group, then dT = -dR W^T a head
        d_r = _f32dot(inv, jnp.concatenate([t["d_w"] for t in group],
                                           axis=0), NN)
        for n, ((_, r), t) in enumerate(zip(heads, group)):
            _terms_bwd(t, d_r[n * C:(n + 1) * C], r, scale)


def _read_bwd(t, state, d_out, ds, ct):
    """Backwards through a head's read and state update: ``ds`` (a ref)
    holds the gradient to the state at the chunk's end and leaves with the
    gradient to ``state``, the kept one at its start; the rest into ``t``."""
    abt, w = t["abt"], t["w"]
    dv = state.shape[1]
    t["u"] = u = w[:, :dv] - _ctdot(w[:, dv:], state, NN, ct)
    d_next = ds[...]
    below = jnp.concatenate([jnp.zeros_like(d_out), d_out], axis=0)
    d_u = _ctdot(abt, below, NN, ct) + _ctdot(t["k_out"], d_next, NN, ct)
    t["d_b"] = _ctdot(u, below, NT, ct)                 # lanes of B^T
    t["d_q_in"] = _ctdot(d_out, state, NT, ct)
    t["d_k_out"] = d_k_out = _ctdot(u, d_next, NT, ct)
    e_last = jnp.exp(t["last"])
    ds[...] = (_to_col(e_last) * d_next + _ctdot(t["q_in"], d_out, TN, ct)
               - _ctdot(w[:, dv:], d_u, TN, ct))
    t["d_last"] = (jnp.sum(d_k_out * t["k_out"], axis=0, keepdims=True)
                   + e_last * _to_row(jnp.sum(d_next * state, axis=1,
                                              keepdims=True)))
    t["d_w"] = jnp.concatenate([d_u, -_ctdot(d_u, state, NT, ct)], axis=1)


def _terms_bwd(t, d_r, r, scale):
    """From ``d_r``, the gradient to the system's right-hand side, to the
    head's gradients (the refs ``r`` by name)."""
    C, dv = r["dv"].shape
    abt, w, row = t["abt"], t["w"], t["row"]
    _, in_use = _masks(C)
    d_t = jnp.where(in_use, -_f32dot(w, jnp.concatenate(
        [d_r, jnp.zeros_like(d_r)], axis=0), NT), 0.0)      # lanes of A^T
    d_abt = jnp.where(in_use, t["d_b"], 0.0) + row * d_t
    d_raw = t["col"] * d_r
    r["dv"][...] = d_raw[:, :dv].astype(r["dv"].dtype)
    d_k_in = d_raw[:, dv:]
    # beta scales A's rows and the right-hand side's
    by_rhs = _f32dot(jnp.ones((8, d_r.shape[1]), jnp.float32),
                     jnp.concatenate([d_r * t["raw"],
                                      jnp.zeros_like(d_r)], axis=0), NT)
    r["dbeta"][...] = jnp.sum(d_t * abt, axis=0, keepdims=True) + by_rhs[:1]

    d_kn, d_qn, d_cum = _pair_decays_bwd(t["cum"], t["kn"], t["qn"], d_abt)
    d_kn = d_kn + d_k_in * t["e_cum"] + t["d_k_out"] * t["e_out"]
    d_qn = d_qn + t["d_q_in"] * t["e_cum"]
    d_cum = (d_cum + d_k_in * t["k_in"] + t["d_q_in"] * t["q_in"]
             - t["d_k_out"] * t["k_out"])
    d_cum = d_cum + jnp.where(_iota(d_cum.shape, 0) == C - 1, t["d_last"],
                              0.0)
    r["dg"][...] = _cumsum(d_cum, reverse=True)
    r["dq"][...] = _unit_bwd(t["q_unit"], t["q_r"],
                             d_qn * scale).astype(r["dq"].dtype)
    r["dk"][...] = _unit_bwd(t["kn"], t["k_r"], d_kn).astype(r["dk"].dtype)


GROUP = 2               # heads whose systems are inverted as one
HEADS = 4               # heads of a grid step, at most: two groups, whose
                        # chains of products fill each other's waits


def _block(heads):
    return next(n for n in (HEADS, GROUP, 1) if heads % n == 0)


def _specs(block, chunks, C, dk, dv, order):
    """Block specs at grid step (batch, block of heads, chunk) with the
    chunks walked in ``order`` (+1 from the first, -1 from the last)."""
    def at(n):
        return n if order > 0 else chunks - 1 - n

    def tokens(width):
        return pl.BlockSpec((1, C, block * width),
                            lambda b, h, n: (b, at(n), h))

    def per_chunk(*tail):
        return pl.BlockSpec((1, block, 1) + tail,
                            lambda b, h, n: (b, h, at(n), 0, 0))

    state = pl.BlockSpec((1, 1, block, dk, dv),
                         lambda b, h, n: (b, at(n), h, 0, 0))
    return tokens(dk), tokens(dv), per_chunk(1, 2 * C), per_chunk(C, 1), state


def _beta_blocks(beta, heads, chunk):
    """``beta`` [b, T, heads] as the two small arrays the kernels read: a
    row of lanes ``[beta | 0]`` [b, heads, chunks, 1, 2C] where it scales
    A's rows (which lie across), a column [b, heads, chunks, C, 1] where it
    scales the right-hand side's."""
    b, t, _ = beta.shape
    beta = jnp.transpose(beta.astype(jnp.float32), (0, 2, 1)) \
        .reshape(b, heads, t // chunk, chunk)
    return (jnp.pad(beta, ((0, 0),) * 3 + ((0, chunk),))[..., None, :],
            beta[..., None])


def _call(kernel, name, operands, in_specs, out_shape, out_specs, heads,
          scale):
    """One kernel over the grid (batch, blocks of heads, chunks), the state
    (or its gradient) of the block's heads in scratch."""
    q, _, v = operands[:3]
    b, t = q.shape[:2]
    chunk = in_specs[0].block_shape[1]
    block = _block(heads)
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, ct=v.dtype, block=block),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, heads // block, t // chunk),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(
                (block, q.shape[2] // heads, v.shape[2] // heads),
                jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=on_cpu(),
    )(*operands)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "scale"))
def delta_rule_fwd(q, k, v, g, beta, heads, chunk, scale):
    """``(out [b, T, heads * dv] in v's type, states [b, T / chunk, heads,
    dk, dv] float32)`` for T a multiple of ``chunk``. Jitted, so that a
    program's layers share one trace of the kernel."""
    b, t, _ = q.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    chunks = t // chunk
    keys, values, row, col, state = _specs(_block(heads), chunks, chunk, dk,
                                           dv, 1)
    return _call(
        _fwd_kernel, "delta_rule_fwd",
        (q, k, v, g) + _beta_blocks(beta, heads, chunk),
        [keys, keys, values, keys, row, col],
        (jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct((b, chunks, heads, dk, dv), jnp.float32)),
        (values, state), heads, scale)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "scale"))
def delta_rule_bwd(q, k, v, g, beta, states, d_out, heads, chunk, scale):
    """Gradients of ``delta_rule_fwd``'s ``out`` to (q, k, v, g, beta), the
    first three in their inputs' types, from the kept ``states``."""
    b, t, _ = q.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    chunks = t // chunk
    keys, values, row, col, state = _specs(_block(heads), chunks, chunk, dk,
                                           dv, -1)
    dq, dk_, dv_, dg, dbeta = _call(
        _bwd_kernel, "delta_rule_bwd",
        (q, k, v, g) + _beta_blocks(beta, heads, chunk)
        + (states, d_out.astype(v.dtype)),
        [keys, keys, values, keys, row, col, state, values],
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct(g.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, heads, chunks, 1, 2 * chunk),
                              jnp.float32)),
        (keys, keys, values, keys, row), heads, scale)
    dbeta = jnp.transpose(dbeta[..., 0, :chunk].reshape(b, heads, t),
                          (0, 2, 1))
    return dq, dk_, dv_, dg, dbeta.astype(beta.dtype)

"""The Mamba-2 state-space core with a chunk's terms in VMEM: the Pallas
family ``ssd_scan``.

Two kernels, ``ssd_scan_fwd`` and ``ssd_scan_bwd``, compute what
``ops/state_space_ops.py`` states (its docstring's equations are the
contract) for one (batch row, GROUP of heads) a grid row, a chunk a grid
step, the chunk axis innermost and sequential. A group's ``R`` heads share
``B`` and ``C``, so ``C B^T`` is one product a step. The heads' states
``[R * P, N]`` float32 (forward) or their gradient (backward, walked from
the last chunk to the first) are carried in VMEM scratch; the pairwise decay
``e^(cum_i - cum_j)``, ``dt * x``, the read's and the contribution's decays
are values of the step and never reach HBM. The forward writes ``Out`` and
each chunk's STARTING state (``States``, what the op keeps) and nothing
else; the backward rebuilds a chunk's terms from the inputs and the kept
state and applies hand-derived gradients (below).

Arrays keep the model's layout: ``X`` ``[b, T, H * P]`` is read in blocks of
``[chunk, R * P]`` (a group's heads side by side), ``B`` / ``C`` ``[b, T, G *
N]`` in blocks of ``[chunk, N]``; nothing is transposed on the way in or
out. Inside, the block is walked a UNIT at a time: 128 lanes (two heads of
64, or one head's lane tiles where ``P`` is a multiple of 128), and where a
unit holds several heads each head's decay meets the unit's WHOLE ``[chunk,
unit]`` array on the MXU and the head's own lanes of the product are kept
(the one product that contracts over lanes takes the array with the other
heads' lanes zeroed), so no value is ever sliced inside a lane tile. The
step ``dt`` and the cumulative log-decay ``cum`` (``_prepare``'s, float32,
1 MB at the cell's shape) arrive twice: token-major ``[chunk, R]`` (a
column a head, spread across the head's lanes) and, for the pairwise decay's
``cum_j``, head-major ``[R, chunk]``; a chunk's whole decay ``e^(cum_C)``,
which scales a state's rows, arrives as scalars (Mosaic broadcasts a value
one way at a time, a scalar both).

**Gradients**, with ``Yc = Y - D x`` (pairs and read), ``w = dt e^(cum_C -
cum)``, ``dS_C`` the total gradient of the state at the chunk's end (the
scratch) and ``dxdt`` the gradient of ``dt x``:

    dxdt   = M^T dY + e^(cum_C - cum) (B dS_C^T),    M = C B^T * L
    dx     = dt dxdt + D dY
    ddt_i  = sum_p dxdt_ip x_ip
    dcum_i = sum_p (dY Yc - (dt x) (M^T dY) - (B dS_C^T) w x)_ip
             + [i last] (e^(cum_C) <dS_C, S_0> + sum_jp (B dS_C^T)_jp w_j x_jp)
    dS_0   = e^(cum_C) dS_C + (dY e^cum)^T C
    dB     = (dM * L summed over the group's heads)^T C + (w x) dS_C
    dC     = (dM * L ...) B + (dY e^cum) S_0,        dM = dY (dt x)^T

The decay's own gradient needs no pass of its own: ``sum_j dL_ij L_ij`` over
a row is ``sum_p dY (M dt x)`` and over a column ``sum_p (dt x) (M^T dY)``,
both already there; they are taken with ``dY`` and ``dt x`` AS THE PRODUCTS
ROUNDED THEM, so that the two are sums over one matrix and what they share
cancels as it does in the twin (from differently rounded products the
gradients of ``A_log`` and ``dt_bias`` stood fifteen times further from the
float32 core). Every exponent is a difference taken first and never
positive.

Precision is the twin's: the four products of a chunk (``C B^T``, the pairs,
the read, the contribution) and their transposes take the compute type
(bfloat16 under AMP; float32 at every pass where ``X`` is float32) and
accumulate in float32; ``dt``, ``cum``, every exponential, the state and its
gradient are float32.

The jnp twin is ``ops/state_space_ops.py``'s chunked program, which is also
what runs where the family is not on the tier or ``supported`` says no.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu
from .causal_conv1d import LANES, _tile
# the products in the compute type and their dimension numbers are the
# delta rule's kernels' own
from .delta_rule import NN, NT, TN, _ctdot, _iota

MAX_CHUNK = 256
VMEM_LIMIT = 64 * 1024 * 1024


def vmem_bytes(chunk, width, n, dtype):
    """What a backward step holds at most: its blocks twice for the pipeline
    (``x``, ``Out@GRAD``, ``X@GRAD``; ``B``, ``C`` and their gradients; the
    kept state), the state's gradient in scratch, and some twenty float32
    values of ``[chunk, width]`` and ``[chunk, chunk]``."""
    size = jnp.dtype(dtype).itemsize
    blocks = 2 * (3 * chunk * width + 4 * chunk * n) * size \
        + 2 * width * n * 4
    return blocks + width * n * 4 + 20 * chunk * max(width, chunk) * 4


def supported(x, b, heads, groups, chunk):
    """Shapes only: float32 or bfloat16 arrays, a group's ``R * P``
    channels and the state's ``N`` in whole 128-lane widths, heads that fill
    or evenly share a lane tile, a chunk of whole sublane tiles and at most
    ``MAX_CHUNK`` tokens, and the blocks of a backward step inside the VMEM
    budget (ONE answer for the op and its grad op)."""
    if (x.ndim != 3 or x.shape[-1] % heads or b.shape[-1] % groups
            or heads % groups
            or x.dtype not in (jnp.float32, jnp.bfloat16)):
        return False
    p, n = x.shape[-1] // heads, b.shape[-1] // groups
    width = heads // groups * p
    return (width % LANES == 0 and n % LANES == 0 and p % 8 == 0
            and (p % LANES == 0 or LANES % p == 0)
            and chunk % _tile(x.dtype) == 0 and chunk <= MAX_CHUNK
            and vmem_bytes(chunk, width, n, x.dtype) <= VMEM_LIMIT // 2)


class _Unit:
    """A unit of the block: ``lanes`` of ``x`` (and rows of the state) and
    its heads (numbered inside the group), each ``p`` lanes."""

    def __init__(self, u, p):
        self.size = max(p, LANES)
        per = self.size // p
        self.p = p
        self.lanes = pl.ds(u * self.size, self.size)
        self.heads = list(range(u * per, (u + 1) * per))

    def rows(self, k, v):
        """Head ``k``'s rows of a [unit, n] array."""
        return v if len(self.heads) == 1 else v[k * self.p:(k + 1) * self.p]

    def only(self, k, v):
        """``v`` [m, unit] with the other heads' lanes zeroed."""
        if len(self.heads) == 1:
            return v
        lane = _iota(v.shape, 1)
        return jnp.where((lane >= k * self.p) & (lane < (k + 1) * self.p), v,
                         0.0)

    def _each(self, values, shape, axis):
        """The heads' ``values`` (each broadcast to ``shape``) side by side
        along ``axis``, ``p`` each."""
        out = values[-1]
        at = _iota(shape, axis)
        for k in range(len(values) - 2, -1, -1):
            out = jnp.where(at < (k + 1) * self.p, values[k], out)
        return out

    def across(self, values):
        """The heads' [m, unit] ``values``, each taken on its own lanes."""
        return self._each(values, values[0].shape, 1)

    def spread(self, cols):
        """``cols`` [chunk, R] -> [chunk, unit]: each head's column across
        its lanes."""
        return self._each([cols[:, h:h + 1] for h in self.heads],
                          (cols.shape[0], self.size), 1)

    def down(self, whole_ref):
        """The heads' scalars ``whole_ref`` [1, 1, 1, 1, R] (scalar memory),
        each down its head's rows of the state: [unit, 1], or the scalar
        itself where the unit is one head."""
        return self._each([whole_ref[0, 0, 0, 0, h] for h in self.heads],
                          (self.size, 1), 0)

    def sums(self, v):
        """``v`` [m, unit] -> each head's sum over its lanes, [m, 1]."""
        return [jnp.sum(self.only(k, v), axis=1, keepdims=True)
                for k in range(len(self.heads))]


def _units(width, p):
    return [_Unit(u, p) for u in range(width // max(p, LANES))]


def _decay(cum, cum_rows, h, seen):
    """``L`` [chunk, chunk] of head ``h``: ``e^(cum_i - cum_j)`` for j <= i,
    else 0."""
    return jnp.where(seen, jnp.exp(jnp.minimum(
        cum[:, h:h + 1] - cum_rows[h:h + 1, :], 0.0)), 0.0)


def _step_terms(b_ref, c_ref, dt_ref, cum_ref, rows_ref, ct):
    """What a step's heads share: ``B``, ``C``, ``C B^T``, the causal mask,
    ``dt`` and ``cum`` [chunk, R], ``cum`` [R, chunk] and the whole chunk's
    log-decay [1, R]."""
    b, c = b_ref[0], c_ref[0]
    chunk = b.shape[0]
    seen = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
    cum = cum_ref[0, 0, 0]
    return (b, c, _ctdot(c, b, NT, ct), seen, dt_ref[0, 0, 0], cum,
            rows_ref[0, 0, 0], cum[chunk - 1:chunk, :])


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, d_ref,
                whole_ref, o_ref, states_ref, s_ref, *, p, ct):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    b, c, cb, seen, dt, cum, cum_rows, last = _step_terms(
        b_ref, c_ref, dt_ref, cum_ref, rows_ref, ct)
    e_cum = jnp.exp(cum)
    to_end = jnp.exp(last - cum)
    for unit in _units(x_ref.shape[2], p):
        x = x_ref[0, :, unit.lanes].astype(jnp.float32)
        state = s_ref[unit.lanes, :]
        for k, h in enumerate(unit.heads):
            states_ref[0, 0, h] = unit.rows(k, state)
        xdt = x * unit.spread(dt)
        y = (d_ref[0, :, unit.lanes] * x
             + _ctdot(c, state, NT, ct) * unit.spread(e_cum))
        # each head's decay meets the unit's whole [chunk, unit] array, and
        # the head's lanes of that product are kept
        xdt_c = xdt.astype(ct)
        y = y + unit.across([
            _ctdot(cb * _decay(cum, cum_rows, h, seen), xdt_c, NN, ct)
            for h in unit.heads])
        o_ref[0, :, unit.lanes] = y.astype(o_ref.dtype)
        s_ref[unit.lanes, :] = (
            unit.down(whole_ref) * state
            + _ctdot(xdt * unit.spread(to_end), b, TN, ct))


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, rows_ref, d_ref,
                whole_ref, states_ref, do_ref, dx_ref, db_ref, dc_ref,
                ddt_ref, dcum_ref, dd_ref, ds_ref, *, p, ct):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    chunk, width = x_ref.shape[1:]
    n = b_ref.shape[2]
    r = width // p
    b, c, cb, seen, dt, cum, cum_rows, last = _step_terms(
        b_ref, c_ref, dt_ref, cum_ref, rows_ref, ct)
    e_cum = jnp.exp(cum)
    to_end = jnp.exp(last - cum)
    at_last = _iota((chunk, 1), 0) == chunk - 1
    head_lane = _iota((chunk, r), 1)
    d_cb = jnp.zeros((chunk, chunk), jnp.float32)
    d_b = jnp.zeros((chunk, n), jnp.float32)
    d_c = jnp.zeros((chunk, n), jnp.float32)
    d_dt = jnp.zeros((chunk, r), jnp.float32)
    d_cum = jnp.zeros((chunk, r), jnp.float32)
    for unit in _units(width, p):
        x = x_ref[0, :, unit.lanes].astype(jnp.float32)
        dy = do_ref[0, :, unit.lanes].astype(jnp.float32)
        state = jnp.concatenate([states_ref[0, 0, h] for h in unit.heads],
                                axis=0)
        d_end = ds_ref[unit.lanes, :]
        dt_u, e_cum_u, to_end_u = (unit.spread(v)
                                   for v in (dt, e_cum, to_end))
        xdt = x * dt_u
        xw = xdt * to_end_u
        dye = dy * e_cum_u
        d_c = d_c + _ctdot(dye, state, NN, ct)
        d_b = d_b + _ctdot(xw, d_end, NN, ct)
        d_xw = _ctdot(b, d_end, NT, ct)
        ds_ref[unit.lanes, :] = (unit.down(whole_ref) * d_end
                                 + _ctdot(dye, c, TN, ct))
        xdt_c, dy_c = xdt.astype(ct), dy.astype(ct)
        pairs, d_pairs = [], []
        for k, h in enumerate(unit.heads):
            decay = _decay(cum, cum_rows, h, seen)
            m = (cb * decay).astype(ct)
            pairs.append(_ctdot(m, xdt_c, NN, ct))
            d_pairs.append(_ctdot(m, dy_c, TN, ct))
            d_cb = d_cb + _ctdot(dy_c, unit.only(k, xdt), NT, ct) * decay
        pairs, d_pairs = unit.across(pairs), unit.across(d_pairs)
        d_xdt = d_pairs + to_end_u * d_xw
        # (dY and dt x as the products rounded them: the module's docstring)
        to_decay = (dy_c.astype(jnp.float32) * pairs
                    - xdt_c.astype(jnp.float32) * d_pairs
                    + dy * (_ctdot(c, state, NT, ct) * e_cum_u) - d_xw * xw)
        dx_ref[0, :, unit.lanes] = (
            d_xdt * dt_u + d_ref[0, :, unit.lanes] * dy).astype(dx_ref.dtype)
        dd_ref[0, 0, 0, :, unit.lanes] = jnp.sum(dy * x, axis=0,
                                                 keepdims=True)
        through = jnp.sum(d_xw * xw, axis=0, keepdims=True)
        kept = jnp.sum(d_end * state, axis=1, keepdims=True)
        for k, (h, to_dt, to_cum) in enumerate(zip(
                unit.heads, unit.sums(d_xdt * x), unit.sums(to_decay))):
            whole = (jnp.sum(unit.only(k, through), axis=1, keepdims=True)
                     + whole_ref[0, 0, 0, 0, h] * jnp.sum(
                         unit.rows(k, kept), axis=0, keepdims=True))
            to_cum = to_cum + jnp.where(at_last, whole, 0.0)
            d_dt = jnp.where(head_lane == h, to_dt, d_dt)
            d_cum = jnp.where(head_lane == h, to_cum, d_cum)
    dc_ref[0] = (d_c + _ctdot(d_cb, b, NN, ct)).astype(dc_ref.dtype)
    db_ref[0] = (d_b + _ctdot(d_cb, c, TN, ct)).astype(db_ref.dtype)
    ddt_ref[0, 0, 0] = d_dt
    dcum_ref[0, 0, 0] = d_cum


def _specs(chunks, chunk, r, p, n, order):
    """Block specs at grid step (batch, group, chunk), the chunks walked in
    ``order`` (+1 from the first, -1 from the last): ``x``-shaped arrays,
    ``B``-shaped ones, the [chunk, R] columns, the [R, chunk] rows, the
    skip's [1, R * P] row, the heads' scalars (in scalar memory), the
    per-chunk row and the kept states."""
    def at(z):
        return z if order > 0 else chunks - 1 - z

    def per_chunk(*tail):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda i, g, z: (i, at(z), g, 0, 0))

    return (pl.BlockSpec((1, chunk, r * p), lambda i, g, z: (i, at(z), g)),
            pl.BlockSpec((1, chunk, n), lambda i, g, z: (i, at(z), g)),
            per_chunk(chunk, r), per_chunk(r, chunk),
            pl.BlockSpec((1, 1, r * p), lambda i, g, z: (g, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, r),
                         lambda i, g, z: (i, at(z), g, 0, 0),
                         memory_space=pltpu.SMEM),
            per_chunk(1, r * p),
            pl.BlockSpec((1, 1, r, p, n),
                         lambda i, g, z: (i, at(z), g, 0, 0)))


def _per_head(v, groups):
    """``_prepare``'s [b, chunks, chunk, H] as the kernels read it: (columns
    [b, chunks, G, chunk, R], rows [b, chunks, G, R, chunk])."""
    v = v.reshape(v.shape[:3] + (groups, -1))
    return jnp.transpose(v, (0, 1, 3, 2, 4)), jnp.transpose(v, (0, 1, 3, 4, 2))


def _call(kernel, name, operands, in_specs, out_shape, out_specs, grid, p,
          width, n):
    """One kernel over the grid (batch, groups, chunks), the group's states
    (or their gradient) in scratch."""
    return pl.pallas_call(
        functools.partial(kernel, p=p, ct=operands[0].dtype), name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((width, n), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=on_cpu(),
    )(*operands)


def _operands(x, b, c, d, dt, cum, heads, groups):
    """The kernels' common inputs (the last: each chunk's whole decay
    ``e^(cum_C)`` [b, chunks, G, 1, R], which scales a state's rows and is read
    as scalars) and their dimensions (batch, chunks, chunk, R, P, N)."""
    bt, t, e = x.shape
    chunks, chunk = dt.shape[1:3]
    r, p, n = heads // groups, e // heads, b.shape[-1] // groups
    assert t == chunks * chunk, (t, chunks, chunk)
    skip = jnp.repeat(d.astype(jnp.float32), p).reshape(groups, 1, r * p)
    dt_cols, _ = _per_head(dt, groups)
    cum_cols, cum_rows = _per_head(cum, groups)
    whole = jnp.exp(cum[:, :, -1]).reshape(bt, chunks, groups, 1, r)
    return ((x, b.astype(x.dtype), c.astype(x.dtype), dt_cols, cum_cols,
             cum_rows, skip, whole), (bt, chunks, chunk, r, p, n))


@functools.partial(jax.jit, static_argnames=("heads", "groups"))
def ssd_scan_fwd(x, b, c, d, dt, cum, heads, groups):
    """``(out [b, T, H * P] in x's type, states [b, chunks, H, P, N]
    float32: each chunk's starting state)`` from ``_prepare``'s ``dt`` and
    ``cum`` [b, chunks, chunk, H], for T = chunks * chunk. Jitted, so that a
    program's layers share one trace of the kernel."""
    operands, (bt, chunks, chunk, r, p, n) = _operands(
        x, b, c, d, dt, cum, heads, groups)
    tokens, keys, cols, rows, skip, scalars, _, state = _specs(
        chunks, chunk, r, p, n, 1)
    return _call(
        _fwd_kernel, "ssd_scan_fwd", operands,
        [tokens, keys, keys, cols, cols, rows, skip, scalars],
        (jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((bt, chunks, heads, p, n), jnp.float32)),
        (tokens, state), (bt, groups, chunks), p, r * p, n)


@functools.partial(jax.jit, static_argnames=("heads", "groups"))
def ssd_scan_bwd(x, b, c, d, dt, cum, states, d_out, heads, groups):
    """Gradients of ``ssd_scan_fwd``'s ``out`` to (x, b, c, d, dt, cum) from
    the kept ``states``: the first three in x's type, the others float32,
    ``dt``'s and ``cum``'s in ``_prepare``'s layout."""
    operands, (bt, chunks, chunk, r, p, n) = _operands(
        x, b, c, d, dt, cum, heads, groups)
    tokens, keys, cols, rows, skip, scalars, partial, state = _specs(
        chunks, chunk, r, p, n, -1)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    per_head = f32(bt, chunks, groups, chunk, r)
    dx, db, dc, ddt, dcum, dd = _call(
        _bwd_kernel, "ssd_scan_bwd",
        operands + (states, d_out.astype(x.dtype)),
        [tokens, keys, keys, cols, cols, rows, skip, scalars, state, tokens],
        (jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(b.shape, x.dtype),
         jax.ShapeDtypeStruct(c.shape, x.dtype), per_head, per_head,
         f32(bt, chunks, groups, 1, r * p)),
        (tokens, keys, keys, cols, cols, partial), (bt, groups, chunks), p,
        r * p, n)
    ddt, dcum = (jnp.transpose(v, (0, 1, 3, 2, 4)).reshape(dt.shape)
                 for v in (ddt, dcum))
    dd = jnp.sum(dd.reshape(bt * chunks, heads, p), axis=(0, 2))
    return dx, db, dc, dd, ddt, dcum

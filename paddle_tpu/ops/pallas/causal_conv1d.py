"""The mixers' short convolution in one pass over HBM each way: the Pallas
family ``causal_conv1d``.

Two kernels, ``causal_conv1d_fwd`` and ``causal_conv1d_bwd``, compute what
``ops/linear_attention_ops._causal_conv1d`` and its ``jax.vjp`` compute: a
causal depthwise convolution over time (one filter of ``taps`` a channel,
the LAST tap on the current token, zeros before the first), a bias where the
op has one, then SiLU; float32 inside, X's type out, float32 filter and
bias gradients. Arrays keep the model's layout ``[b, T, channels]``.

A grid step holds the WHOLE time axis of a block of ``width`` channels of
one batch row in VMEM (``[1, T, width]`` blocks of ``x``, ``out`` and,
backward, ``Out@GRAD`` and ``X@GRAD``): no block has a neighbour in time,
so no halo crosses HBM, and every array is read once and written once. The
block's width follows the shape (``block_width``: 256 lanes, or 128 where
256 do not divide the channels or do not fit the VMEM budget; on the chip
128 reads 5% slower, 512 faster at 4096 channels and slower at 6144, 1024
slower at both). Inside, a step walks the block one 128-lane column and a
piece of ``ROWS`` tokens at a time (64 and 256 read slower on the chip). A
piece of ``x`` goes to a float32 scratch column once (token t in row t + a
sublane tile, zeros above the first token), and ``x[t - s]`` is a load from
it at a sublane offset: no rotation, no select.

* forward, first token to last: ``y = b + sum_s x[t - s] * w[taps - 1 - s]``,
  ``out = y * sigmoid(y)``.
* backward, hand-derived, LAST token to first: ``y`` again from ``x``;
  ``dy = dout * sig * (1 + y * (1 - sig))`` goes to a second float32
  scratch column (zeros below the last token), and ``X@GRAD[t] = sum_s
  dy[t + s] * w[taps - 1 - s]`` reads it at a sublane offset, the later
  piece's rows written by the step before; ``Filter@GRAD[taps - 1 - s] =
  sum_t dy[t] * x[t - s]`` and ``Bias@GRAD = sum_t dy[t]`` as float32 sums
  of eight sublanes carried through the walk, reduced once a column and
  added into an output block that stays resident over the batch axis of
  the grid (the innermost, ``arbitrary``; the channel axis ``parallel``).
  ``dy`` and the shifted copies never reach HBM.

The jnp twin is ``ops/linear_attention_ops._causal_conv1d`` (a padded
float32 copy, shifted multiply-adds, SiLU) and ``jax.vjp`` of it, which is
also what runs where the family is not on the tier or ``supported`` says
no.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import on_cpu

LANES = 128
ROWS = 128              # tokens of a piece of the walk inside a block
WIDTHS = (256, 128)     # a block's channels, widest first
MAX_TAPS = 8            # the shifted loads reach inside one sublane tile of
                        # zeros above the first token and below the last
VMEM_LIMIT = 64 * 1024 * 1024
VMEM_BUDGET = VMEM_LIMIT * 3 // 4   # what the blocks of a step may take


def _tile(dtype):
    """Sublanes of one tile of ``dtype``: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def vmem_bytes(t, width, dtype, backward):
    """A step's blocks, each twice for the pipeline (``x`` and ``out``
    forward; ``x``, ``Out@GRAD`` and ``X@GRAD`` backward), and its float32
    scratch columns."""
    blocks = 2 * (3 if backward else 2) * t * width \
        * jnp.dtype(dtype).itemsize
    return blocks + (2 if backward else 1) * (t + 16) * LANES * 4


def block_width(x, backward):
    """The widest block of channels that divides them and fits the budget,
    or None."""
    _, t, channels = x.shape
    for width in WIDTHS:
        if channels % width == 0 and vmem_bytes(
                t, width, x.dtype, backward) <= VMEM_BUDGET:
            return width
    return None


def supported(x, w):
    """What the input shows: [b, T, channels] float32 or bfloat16 with the
    channels in whole 128-lane widths and the tokens in whole sublane
    tiles, at most ``MAX_TAPS`` taps, and a 128-lane block of the whole
    time axis inside the VMEM budget, backward as forward (ONE answer for
    the op and its grad op)."""
    return (x.ndim == 3 and x.dtype in (jnp.float32, jnp.bfloat16)
            and x.shape[2] % LANES == 0 and x.shape[1] % _tile(x.dtype) == 0
            and 1 <= w.shape[0] <= MAX_TAPS
            and block_width(x, backward=True) is not None)


def _rows(t, tile):
    """Tokens of a piece: ``ROWS``, or the largest halving of it down to a
    sublane tile that divides the length."""
    rows = ROWS
    while rows > tile and t % rows:
        rows //= 2
    return rows if t % rows == 0 else tile


def _shifted(x_ref, s_ref, start, rows, cols, taps):
    """``x[t - s]`` for s = 0 .. taps - 1 over the piece's tokens ``[start,
    start + rows)`` of the columns ``cols``: float32 [rows, 128] each. The
    piece and the sublane tile before it (zeros before the first token) go
    to the float32 scratch ``s_ref`` (token t in row t + tile), and the
    shifted copies are loads from it at a sublane offset."""
    tile = _tile(x_ref.dtype)
    piece = x_ref[0, pl.ds(start, rows), cols].astype(jnp.float32)
    before = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(start - tile, 0),
                                           tile), tile), cols]
    s_ref[pl.ds(start, tile), :] = jnp.where(
        start > 0, before.astype(jnp.float32), 0.0)
    s_ref[pl.ds(start + tile, rows), :] = piece
    return [piece] + [s_ref[pl.ds(start + tile - s, rows), :]
                      for s in range(1, taps)]


def _pre_activation(shifted, w, bias):
    taps = len(shifted)
    y = sum(shifted[s] * w[taps - 1 - s] for s in range(taps))
    return y if bias is None else y + bias


def _sigmoid(y, exact):
    """The logistic. For bfloat16 arrays as ``(1 + tanh(y / 2)) / 2``: one
    transcendental and no divide (``exp`` and an exact reciprocal are a
    third of the forward's vector operations), whose 1e-5 on a TPU is far
    inside one rounding of the output; for float32 arrays the logistic
    itself."""
    return jax.nn.sigmoid(y) if exact else 0.5 * jnp.tanh(0.5 * y) + 0.5


def _columns(w_ref, b_ref, cols):
    """The filter's taps ([1, 128] float32 each) and the bias of a
    column."""
    w = w_ref[:, cols].astype(jnp.float32)
    bias = None if b_ref is None else b_ref[:, cols].astype(jnp.float32)
    return [w[j:j + 1] for j in range(w.shape[0])], bias


def _fwd_kernel(*refs, has_bias):
    refs = iter(refs)
    x_ref, w_ref = next(refs), next(refs)
    b_ref = next(refs) if has_bias else None
    o_ref, s_ref = refs
    t, width = x_ref.shape[1:]
    taps = w_ref.shape[0]
    rows = _rows(t, _tile(x_ref.dtype))
    exact = x_ref.dtype == jnp.float32
    for lane in range(0, width, LANES):
        cols = pl.ds(lane, LANES)
        w, bias = _columns(w_ref, b_ref, cols)

        def piece(c, carry, cols=cols, w=w, bias=bias):
            start = pl.multiple_of(c * rows, rows)
            y = _pre_activation(
                _shifted(x_ref, s_ref, start, rows, cols, taps), w, bias)
            o_ref[0, pl.ds(start, rows), cols] = (
                y * _sigmoid(y, exact)).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, t // rows, piece, 0)


def _eight(a):
    """[rows, 128] -> [8, 128]: the sum of the array's sublane tiles."""
    return sum(a[k:k + 8] for k in range(0, a.shape[0], 8))


def _bwd_kernel(*refs, has_bias):
    refs = iter(refs)
    x_ref, w_ref = next(refs), next(refs)
    b_ref = next(refs) if has_bias else None
    g_ref, dx_ref, dw_ref = next(refs), next(refs), next(refs)
    db_ref = next(refs) if has_bias else None
    s_ref, d_ref = refs
    t, width = x_ref.shape[1:]
    taps = w_ref.shape[0]
    rows = _rows(t, _tile(x_ref.dtype))
    exact = x_ref.dtype == jnp.float32
    pieces = t // rows

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    zeros = jnp.zeros((8, LANES), jnp.float32)
    d_ref[pl.ds(t, 8), :] = zeros           # no token after the last
    for lane in range(0, width, LANES):
        cols = pl.ds(lane, LANES)
        w, bias = _columns(w_ref, b_ref, cols)

        def piece(i, carry, cols=cols, w=w, bias=bias):
            start = pl.multiple_of((pieces - 1 - i) * rows, rows)
            shifted = _shifted(x_ref, s_ref, start, rows, cols, taps)
            y = _pre_activation(shifted, w, bias)
            sig = _sigmoid(y, exact)
            dy = (g_ref[0, pl.ds(start, rows), cols].astype(jnp.float32)
                  * sig * (1.0 + y * (1.0 - sig)))
            d_ref[pl.ds(start, rows), :] = dy
            dx = dy * w[taps - 1]
            for s in range(1, taps):
                dx += d_ref[pl.ds(start + s, rows), :] * w[taps - 1 - s]
            dx_ref[0, pl.ds(start, rows), cols] = dx.astype(dx_ref.dtype)
            return tuple(a + _eight(dy * xs)
                         for a, xs in zip(carry, shifted)) \
                + ((carry[taps] + _eight(dy),) if has_bias else ())

        sums = jax.lax.fori_loop(
            0, pieces, piece, (zeros,) * (taps + has_bias))
        for s in range(taps):
            dw_ref[pl.ds(taps - 1 - s, 1), cols] += jnp.sum(
                sums[s], axis=0, keepdims=True)
        if has_bias:
            db_ref[:, cols] += jnp.sum(sums[taps], axis=0, keepdims=True)


def _specs(x, w, width):
    """The grid (channel block, batch) and the block specs of ``x``-shaped
    arrays, of the filter and of a [1, channels] row."""
    b, t, channels = x.shape
    whole = pl.BlockSpec((1, t, width), lambda j, i: (i, 0, j))
    taps = pl.BlockSpec((w.shape[0], width), lambda j, i: (0, j))
    row = pl.BlockSpec((1, width), lambda j, i: (0, j))
    return (channels // width, b), whole, taps, row


def _scratch(x, extra):
    """A 128-lane column of the whole time axis and ``extra`` rows, in
    float32."""
    return pltpu.VMEM((x.shape[1] + extra, LANES), jnp.float32)


@functools.partial(jax.jit, static_argnames="width")
def causal_conv1d_fwd(x, w, bias=None, *, width=None):
    """``silu(causal depthwise convolution of x [b, T, channels] with w
    [taps, channels] (+ bias [channels]))`` in x's type; ``width``: the
    block's channels, by default ``block_width``'s."""
    width = width or block_width(x, backward=False)
    grid, whole, taps, row = _specs(x, w, width)
    operands = (x, w) + (() if bias is None else (bias[None],))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, has_bias=bias is not None),
        name="causal_conv1d_fwd", grid=grid,
        in_specs=[whole, taps] + ([] if bias is None else [row]),
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[_scratch(x, _tile(x.dtype))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=on_cpu(),
    )(*operands)


@functools.partial(jax.jit, static_argnames="width")
def causal_conv1d_bwd(x, w, bias, dout, *, width=None):
    """(``X@GRAD`` in x's type, ``Filter@GRAD`` [taps, channels] float32,
    ``Bias@GRAD`` [channels] float32 or None) from the inputs and
    ``Out@GRAD``; the filter's and the bias's sum over batch and time."""
    width = width or block_width(x, backward=True)
    grid, whole, taps, row = _specs(x, w, width)
    channels = x.shape[2]
    has_bias = bias is not None
    operands = (x, w) + ((bias[None],) if has_bias else ()) \
        + (dout.astype(x.dtype),)
    dx, dw, *db = pl.pallas_call(
        functools.partial(_bwd_kernel, has_bias=has_bias),
        name="causal_conv1d_bwd", grid=grid,
        in_specs=[whole, taps] + ([row] if has_bias else []) + [whole],
        out_specs=[whole, taps] + ([row] if has_bias else []),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)]
        + ([jax.ShapeDtypeStruct((1, channels), jnp.float32)]
           if has_bias else []),
        scratch_shapes=[_scratch(x, _tile(x.dtype)), _scratch(x, 8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=on_cpu(),
    )(*operands)
    return dx, dw, (db[0][0] if has_bias else None)

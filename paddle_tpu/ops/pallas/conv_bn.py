"""Fused conv+bn(+relu) Pallas kernels for the ResNet block shapes.

The idea: keep the conv output VMEM-resident through batch statistics,
normalize and activation instead of round-tripping it through HBM. On the
chip it does not pay yet (TPU v5 lite, PR 21, tools/kernel_probe.py): the
kernels lower natively and match their twins, but run at 0.2-0.65x of
XLA's own conv+BN fusions at six of seven ResNet-50 shapes (1x1 64->256
at 56x56 is the exception, 1.5x fwd / 1.36x bwd), and the fused flagship
step takes 318.6 ms against 102.5 ms unfused. So ``conv_bn`` is NOT in
``AUTO_PALLAS``; ``kernel_tier=pallas`` still reaches it. The kernels:

* **forward (training)** — ONE kernel, grid ``(2, N)`` over a sequential
  TPU grid: pass 0 computes each image's conv block in VMEM and
  accumulates the batch Σy/Σy² in scratch (the conv output never touches
  HBM); at the pass boundary the batch mean/var and folded scale/shift
  land in scratch; pass 1 recomputes the conv and writes only the final
  normalized+activated y. The conv runs twice (trading MXU flops for HBM
  round trips), but the [N,H,W,C] intermediate never round-trips.
* **forward (inference)** — single pass: conv + precomputed scale/shift
  (+relu), the classic folded-BN serving epilogue.
* **backward (training)** — same two-pass shape: pass 0 recomputes the
  conv (and the relu mask from it) and accumulates dbias/dscale; pass 1
  forms the BN input-gradient dz in VMEM and emits dx (transposed conv as
  shifted taps against the rotated weights) and the dw tap dots, with dw
  accumulated across images in scratch. Neither dz nor the relu-masked dy
  ever materializes in HBM.

Convs are expressed as unrolled per-tap MXU dots over the padded input
block ("grouped by the conv_1x1_grad_as_dot analysis": a 1x1 conv IS a
channel matmul; a 3x3 conv is nine shifted ones), so only k∈{1,3},
stride 1 (stride-2 supported for 1x1 via pre-subsampling), NHWC, ungrouped,
undilated shapes are fused — everything else routes to the jnp twin via
the tier's fallback counter. Numerics are pinned against the unfused
conv2d+batch_norm(+relu) op chain in tests/test_fused_conv_bn.py
(interpret mode on CPU, native on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import on_cpu as _on_cpu


# The scoped-VMEM limit every kernel here asks of Mosaic
# (``vmem_limit_bytes``) and the budget ``supported()`` admits shapes
# against — one number, so the predicate and the compiler agree. The
# compiler's default scoped limit (16 MiB on a v5e) is far below the
# chip's VMEM (128 MiB on a v5e), and at ResNet-50 shapes the backward
# kernel's working set exceeds it (the 56x56 64->64 3x3 backward ran out
# of VMEM on the chip under the default). 64 MiB is half a v5e's VMEM.
_VMEM_LIMIT = 64 * 1024 * 1024


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _itemsize(dtype):
    return jnp.dtype(dtype).itemsize


def _tile_bytes(rows, cols, itemsize):
    """VMEM bytes of a [rows, cols] slab after tile padding: lanes pad to
    128, sublanes to 8 (f32) / 16 (bf16)."""
    sub = 32 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def _vmem_need(hp, wp, ho, wo, cin, cout, taps, it, backward):
    """Conservative working set of one grid step: streamed blocks double
    buffered, scratch, the f32 temporaries of the epilogue, and — for
    3x3 — the shifted tap slices (sublane-unaligned slices materialize
    as copies). Measured against the chip it over-counts by ~2x (the
    compiler fuses some temporaries), which only costs fallbacks."""
    rows = ho * wo
    x_b = hp * _tile_bytes(wp, cin, it)
    y_b = ho * _tile_bytes(wo, cout, it)
    wt_b = taps * _tile_bytes(cin, cout, it)
    slices = taps * _tile_bytes(rows, cin, it) if taps > 1 else 0
    if not backward:
        return (2 * (x_b + y_b + wt_b) + slices
                + 3 * _tile_bytes(rows, cout, 4))           # acc, zf, y
    prows = (ho + 2 * (hp - ho)) * (wo + 2 * (wp - wo))     # dzp rows
    dw_b = taps * _tile_bytes(cin, cout, 4)
    dzp_b = (ho + 2 * (hp - ho)) * _tile_bytes(wo + 2 * (wp - wo), cout, it)
    return (2 * (x_b + y_b + x_b + 2 * wt_b + dw_b)         # x, dy, dx, w
            + dw_b + dzp_b                                  # scratch
            + 6 * _tile_bytes(rows, cout, 4)                # zf..dz
            + 2 * _tile_bytes(hp * wp, cin, 4)              # dxp
            + 2 * slices
            + (taps * _tile_bytes(prows, cout, it) if taps > 1 else 0))


def supported(x_shape, w_shape, strides, paddings, dilations, groups,
              data_format, x_dtype, backward=False):
    """Is this conv+bn shape fused-kernel eligible? (The op layer passes
    the verdict to ``use_pallas`` so ineligible shapes fall back to the
    jnp twin with a counter bump.)"""
    if data_format != "NHWC" or groups != 1:
        return False
    if tuple(dilations) != (1, 1):
        return False
    if len(x_shape) != 4 or any(d is None for d in x_shape):
        return False
    kh, kw = int(w_shape[2]), int(w_shape[3])
    if (kh, kw) not in ((1, 1), (3, 3)):
        return False
    s = tuple(int(v) for v in strides)
    if s == (2, 2):
        # stride 2 is fused only as the subsampled 1x1 form
        if (kh, kw) != (1, 1) or tuple(paddings) != (0, 0):
            return False
    elif s != (1, 1):
        return False
    if jnp.dtype(x_dtype) not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    n, h, w, cin = (int(d) for d in x_shape)
    cout = int(w_shape[0])
    if s == (2, 2):
        h, w = -(-h // 2), -(-w // 2)
    ph, pw = (int(p) for p in paddings)
    hp, wp = h + 2 * ph, w + 2 * pw
    ho, wo = hp - kh + 1, wp - kw + 1
    if ho <= 0 or wo <= 0:
        return False
    return _vmem_need(hp, wp, ho, wo, cin, cout, kh * kw,
                      _itemsize(x_dtype), backward) <= _VMEM_LIMIT


def _prep(x, w, strides, paddings):
    """Shared input prep: subsample stride-2 1x1, spatially pad, and lay
    the OIHW filter out as per-tap [kh*kw, Cin, Cout] matmul operands."""
    kh, kw = w.shape[2], w.shape[3]
    if tuple(strides) == (2, 2):
        x = x[:, ::2, ::2, :]
    ph, pw = paddings
    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    wt = w.transpose(2, 3, 1, 0).reshape(kh * kw, w.shape[1], w.shape[0])
    return x, wt.astype(x.dtype), kh, kw


def _conv_taps(x, wt_ref, kh, kw, ho, wo):
    """f32 conv accumulator for one image: Σ_taps shifted-slice matmuls.
    ``x`` is the padded [Hp, Wp, Cin] block; taps are unrolled python
    loops (static), each an MXU dot with f32 accumulation."""
    cin = x.shape[-1]
    acc = None
    for a in range(kh):
        for b in range(kw):
            xs = x[a:a + ho, b:b + wo, :].reshape(ho * wo, cin)
            part = jax.lax.dot(xs, wt_ref[a * kw + b],
                               preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
    return acc


# ---------------------------------------------------------------------------
# forward, training mode: conv + batch stats + normalize + act, one kernel
# ---------------------------------------------------------------------------

def _conv_bn_train_kernel(x_ref, wt_ref, sb_ref, y_ref, sm_ref, sv_ref,
                          sum_s, sq_s, ab_s, *, kh, kw, ho, wo, count, eps,
                          act, out_dtype):
    t = pl.program_id(0)
    i = pl.program_id(1)
    n = pl.num_programs(1)

    @pl.when(jnp.logical_and(t == 0, i == 0))
    def _():
        sum_s[...] = jnp.zeros_like(sum_s)
        sq_s[...] = jnp.zeros_like(sq_s)

    # conv block in the COMPUTE dtype (bf16 under AMP): the jnp twin's
    # lax.conv emits the input dtype, and the BN statistics accumulate in
    # f32 FROM that — rounding here keeps the two paths aligned
    z = _conv_taps(x_ref[0], wt_ref, kh, kw, ho, wo).astype(x_ref.dtype)
    zf = z.astype(jnp.float32)

    @pl.when(t == 0)
    def _():
        sum_s[0, :] += jnp.sum(zf, axis=0)
        sq_s[0, :] += jnp.sum(zf * zf, axis=0)

    @pl.when(t == 1)
    def _():
        y = zf * ab_s[0, :][None, :] + ab_s[1, :][None, :]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        y_ref[0] = y.reshape(ho, wo, -1).astype(out_dtype)

    @pl.when(jnp.logical_and(t == 0, i == n - 1))
    def _():
        m = sum_s[0, :] / count
        v = jnp.maximum(sq_s[0, :] / count - m * m, 0.0)
        inv = jax.lax.rsqrt(v + eps)
        a = sb_ref[0, :] * inv
        ab_s[0, :] = a
        ab_s[1, :] = sb_ref[1, :] - m * a
        sm_ref[0, :] = m
        sv_ref[0, :] = v


def conv_bn_train_pallas(x, w, scale, bias, eps, strides, paddings, act):
    """Fused training-mode conv+bn(+act) forward.

    x [N,H,W,Cin] NHWC, w [Cout,Cin,kh,kw] OIHW (stride 1, or stride 2
    for 1x1), scale/bias [C]. Returns (y, batch_mean, batch_var) — the
    momentum blend into the running stats is [C]-cheap and stays in jnp
    at the op layer."""
    from jax.experimental.pallas import tpu as pltpu

    out_dtype = x.dtype
    x, wt, kh, kw = _prep(x, w, strides, paddings)
    n, hp, wp, cin = x.shape
    cout = w.shape[0]
    ho, wo = hp - kh + 1, wp - kw + 1
    count = float(n * ho * wo)
    sb = jnp.stack([scale.astype(jnp.float32).reshape(-1),
                    bias.astype(jnp.float32).reshape(-1)])

    kernel = functools.partial(
        _conv_bn_train_kernel, kh=kh, kw=kw, ho=ho, wo=wo, count=count,
        eps=float(eps), act=act, out_dtype=out_dtype)
    y, sm, sv = pl.pallas_call(
        kernel,
        grid=(2, n),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cin), lambda t, i: (i, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cin, cout), lambda t, i: (0, 0, 0)),
            pl.BlockSpec((2, cout), lambda t, i: (0, 0)),
        ],
        out_specs=[
            # t*i: every pass-0 step parks on block 0 (same block ⇒ the
            # write-back defers), pass 1 walks the real blocks — so the
            # unwritten stats pass never flushes garbage rows to HBM
            pl.BlockSpec((1, ho, wo, cout), lambda t, i: (t * i, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda t, i: (0, 0)),
            pl.BlockSpec((1, cout), lambda t, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, cout), out_dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, cout), jnp.float32),
                        pltpu.VMEM((1, cout), jnp.float32),
                        pltpu.VMEM((2, cout), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_on_cpu(),
    )(x, wt, sb)
    return y, sm[0], sv[0]


# ---------------------------------------------------------------------------
# forward, inference mode: conv + folded scale/shift (+act), single pass
# ---------------------------------------------------------------------------

def _conv_affine_kernel(x_ref, wt_ref, ab_ref, y_ref, *, kh, kw, ho, wo,
                        act, out_dtype):
    z = _conv_taps(x_ref[0], wt_ref, kh, kw, ho, wo).astype(x_ref.dtype)
    y = z.astype(jnp.float32) * ab_ref[0, :][None, :] + ab_ref[1, :][None, :]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.reshape(ho, wo, -1).astype(out_dtype)


def conv_affine_pallas(x, w, a, b, strides, paddings, act):
    """Fused inference conv + y = conv*a + b (+act): the folded-BN serving
    epilogue (a = scale·rsqrt(var+eps), b = bias − mean·a, precomputed)."""
    out_dtype = x.dtype
    x, wt, kh, kw = _prep(x, w, strides, paddings)
    n, hp, wp, cin = x.shape
    cout = w.shape[0]
    ho, wo = hp - kh + 1, wp - kw + 1
    ab = jnp.stack([a.astype(jnp.float32).reshape(-1),
                    b.astype(jnp.float32).reshape(-1)])
    kernel = functools.partial(_conv_affine_kernel, kh=kh, kw=kw, ho=ho,
                               wo=wo, act=act, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cin, cout), lambda i: (0, 0, 0)),
            pl.BlockSpec((2, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, ho, wo, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, cout), out_dtype),
        compiler_params=_compiler_params(),
        interpret=_on_cpu(),
    )(x, wt, ab)


# ---------------------------------------------------------------------------
# backward, training mode: relu-mask + BN grad + both conv grads, one kernel
# ---------------------------------------------------------------------------

def _conv_bn_bwd_kernel(x_ref, wt_ref, wtr_ref, dy_ref, aux_ref,
                        dx_ref, dw_ref, db_ref, ds_ref,
                        db_s, ds_s, dw_s, dzp_s, *, kh, kw, ho, wo, h, wd,
                        ph, pw, count, act):
    t = pl.program_id(0)
    i = pl.program_id(1)
    n = pl.num_programs(1)
    cin = x_ref.shape[-1]
    cout = dy_ref.shape[-1]
    x = x_ref[0]
    # recompute the conv block (the fused forward never materialized it)
    z = _conv_taps(x, wt_ref, kh, kw, ho, wo).astype(x_ref.dtype)
    zf = z.astype(jnp.float32)
    a_row = aux_ref[0, :][None, :]
    b_row = aux_ref[1, :][None, :]
    mean = aux_ref[2, :][None, :]
    inv = aux_ref[3, :][None, :]
    scale = aux_ref[4, :][None, :]
    dyf = dy_ref[0].reshape(ho * wo, cout).astype(jnp.float32)
    if act == "relu":
        pre = zf * a_row + b_row
        dyf = dyf * (pre > 0)
    xhat = (zf - mean) * inv

    @pl.when(jnp.logical_and(t == 0, i == 0))
    def _():
        db_s[...] = jnp.zeros_like(db_s)
        ds_s[...] = jnp.zeros_like(ds_s)

    @pl.when(t == 0)
    def _():
        db_s[0, :] += jnp.sum(dyf, axis=0)
        ds_s[0, :] += jnp.sum(dyf * xhat, axis=0)

    @pl.when(jnp.logical_and(t == 0, i == n - 1))
    def _():
        db_ref[0, :] = db_s[0, :]
        ds_ref[0, :] = ds_s[0, :]

    @pl.when(jnp.logical_and(t == 1, i == 0))
    def _():
        dw_s[...] = jnp.zeros_like(dw_s)
        dzp_s[...] = jnp.zeros_like(dzp_s)

    @pl.when(t == 1)
    def _():
        db = db_s[0, :][None, :]
        ds = ds_s[0, :][None, :]
        # batch_norm_grad closed form (norm_ops bn_backward_math): dz in
        # f32, then cast to the conv compute dtype exactly like the twin's
        # vjp cotangent cast
        dz = (scale * inv / count) * (count * dyf - db - xhat * ds)
        dzc = dz.astype(x_ref.dtype)
        # filter grad taps: dw[a,b] += x_slice^T · dz (f32 accumulation)
        for a in range(kh):
            for b in range(kw):
                xs = x[a:a + ho, b:b + wo, :].reshape(ho * wo, cin)
                dw_s[a * kw + b] += jax.lax.dot_general(
                    xs, dzc, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        # input grad: full correlation of dz against the rotated weights.
        # dzp is dz embedded in a zero border of kh-1/kw-1 (the border was
        # zeroed once at (1,0) and interior rows are overwritten per image)
        dzp_s[kh - 1:kh - 1 + ho, kw - 1:kw - 1 + wo, :] = \
            dzc.reshape(ho, wo, cout)
        hp = ho + kh - 1
        wp = wo + kw - 1
        dxp = None
        for a in range(kh):
            for b in range(kw):
                dzs = dzp_s[a:a + hp, b:b + wp, :].reshape(hp * wp, cout)
                part = jax.lax.dot(dzs, wtr_ref[a * kw + b],
                                   preferred_element_type=jnp.float32)
                dxp = part if dxp is None else dxp + part
        dxp = dxp.reshape(hp, wp, cin)
        dx_ref[0] = dxp[ph:ph + h, pw:pw + wd, :].astype(dx_ref.dtype)

    @pl.when(jnp.logical_and(t == 1, i == n - 1))
    def _():
        dw_ref[...] = dw_s[...]


def conv_bn_bwd_pallas(x, w, dy, scale, bias, mean, var, eps, strides,
                       paddings, act):
    """Fused training-mode backward: (dx, dw OIHW, dscale, dbias) from the
    upstream dy of the fused forward. Stride-2 1x1 is handled by running
    the stride-1 kernel on the subsampled input and scattering dx back
    into the even positions (the subsample trick's exact transpose)."""
    from jax.experimental.pallas import tpu as pltpu

    stride2 = tuple(strides) == (2, 2)
    x_orig_shape = x.shape
    x_dtype = x.dtype
    xp, wt, kh, kw = _prep(x, w, strides, paddings)
    wtr_src = wt.reshape(kh, kw, w.shape[1], w.shape[0])
    # rotate 180° and transpose per tap: dx tap j reads w[kh-1-a, kw-1-b]^T
    wtr = jnp.flip(wtr_src, axis=(0, 1)).transpose(0, 1, 3, 2) \
        .reshape(kh * kw, w.shape[0], w.shape[1])
    n, hp, wp, cin = xp.shape
    cout = w.shape[0]
    ho, wo = hp - kh + 1, wp - kw + 1
    ph, pw = (int(p) for p in paddings)
    h, wd = hp - 2 * ph, wp - 2 * pw
    count = float(n * ho * wo)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + float(eps))
    a_fold = scale.astype(jnp.float32) * inv
    aux = jnp.stack([
        a_fold.reshape(-1),
        bias.astype(jnp.float32).reshape(-1)
        - mean.astype(jnp.float32).reshape(-1) * a_fold.reshape(-1),
        mean.astype(jnp.float32).reshape(-1),
        inv.reshape(-1),
        scale.astype(jnp.float32).reshape(-1),
    ])

    kernel = functools.partial(_conv_bn_bwd_kernel, kh=kh, kw=kw, ho=ho,
                               wo=wo, h=h, wd=wd, ph=ph, pw=pw, count=count,
                               act=act)
    dx, dw, db, ds = pl.pallas_call(
        kernel,
        grid=(2, n),
        in_specs=[
            pl.BlockSpec((1, hp, wp, cin), lambda t, i: (i, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cin, cout), lambda t, i: (0, 0, 0)),
            pl.BlockSpec((kh * kw, cout, cin), lambda t, i: (0, 0, 0)),
            pl.BlockSpec((1, ho, wo, cout), lambda t, i: (i, 0, 0, 0)),
            pl.BlockSpec((5, cout), lambda t, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, wd, cin), lambda t, i: (t * i, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cin, cout), lambda t, i: (0, 0, 0)),
            pl.BlockSpec((1, cout), lambda t, i: (0, 0)),
            pl.BlockSpec((1, cout), lambda t, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cin), x_dtype),
            jax.ShapeDtypeStruct((kh * kw, cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, cout), jnp.float32),
            pltpu.VMEM((1, cout), jnp.float32),
            pltpu.VMEM((kh * kw, cin, cout), jnp.float32),
            # dz embedded in a kh-1/kw-1 zero border ON EACH SIDE (the
            # full-correlation operand for the dx taps)
            pltpu.VMEM((ho + 2 * (kh - 1), wo + 2 * (kw - 1), cout),
                       x_dtype),
        ],
        compiler_params=_compiler_params(),
        interpret=_on_cpu(),
    )(xp, wt, wtr, dy, aux)
    dw_oihw = dw.reshape(kh, kw, cin, cout).transpose(3, 2, 0, 1)
    if stride2:
        dx_full = jnp.zeros(x_orig_shape, dx.dtype)
        dx = dx_full.at[:, ::2, ::2, :].set(dx)
    return dx, dw_oihw, ds[0], db[0]

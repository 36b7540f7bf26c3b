"""Attention ops: causal self-attention and its serving-time split.

``causal_self_attention`` is the model-authoring op (the dense analog of
the reference's scaled_dot_product_attention composition): one op per
transformer layer, Q/K/V already projected by ``fc`` layers. At serving
time the generation engine (serving/generate/decode_engine.py) clones the
saved program and rewrites every causal_self_attention site into one of
two phase ops over a PAGED KV arena (the layer *Ragged Paged Attention*
assumes exists above the kernel):

* ``prefill_attention`` — the same causal attention over the prompt
  window, plus a scatter of every position's K/V rows into the arena at
  ``SlotMapping`` (flat ``block*block_size+offset`` slots; out-of-range
  sentinel slots — padding positions — are dropped by the scatter).
* ``chunked_prefill_attention`` — the PARTIAL prefill: a chunk of the
  prompt whose earlier positions already live in the arena (a cached
  shared prefix, or this prompt's previous chunks). The chunk's K/V rows
  scatter in first, then every chunk query attends over the arena
  context gathered through the sequence's block table, masked causally
  at its ABSOLUTE position (``ChunkStart`` + window index) — so the
  math a tail position sees is element-for-element the full-window
  causal attention, which is what makes cached-prefix token streams
  bitwise equal to cold ones.
* ``paged_attention`` — the fixed-shape ``[max_seqs, 1]`` decode step:
  write the new token's K/V row, then attend its Q against the sequence's
  context gathered THROUGH its block table. Ragged in-flight sequences
  share the one executable: each row sees only its own ``ContextLens``
  prefix, and rows with ``ContextLens == 0`` (inactive slots) write
  nothing (sentinel slot) and emit zeros. The gather-then-attend form
  is the jnp twin of the Pallas ragged paged-attention kernel
  (ops/pallas/paged_attention.py): under a Pallas ``kernel_tier`` the
  decode step attends straight through the arena with scalar-prefetched
  block tables instead of materializing the gathered
  ``[max_seqs, max_ctx]`` context (silent jnp fallback on unsupported
  shapes, like every kernel in the tier).

Both phase ops are row-independent (no cross-row reductions), which is
what makes continuous batching BITWISE equal to one-sequence-at-a-time
decode: a sequence's logits depend only on its own tokens, block table
and the arena rows it wrote, never on which other rows share the batch.

The arena update is functional (the ops output the updated KCache/VCache
under the SAME variable names, the optimizer-op in-place convention); the
engine feeds the arena arrays in and fetches them back as device arrays,
so no host round trip occurs. On TPU the natural next step is donating
the arena buffers; at current arena sizes the copy is noise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.amp import cast_compute
from ..core.registry import OpSpec, register_op
from ..obs.metrics import REGISTRY as _METRICS
from .common import G, data_of

_M_BLOCKS = _METRICS.gauge(
    "paddle_tpu_attention_blocks",
    "the attention kernels' grid a head, as last traced under each window: "
    "kind=scheduled is the band's blocks, a grid step each; kind=skipped "
    "the empty steps a rectangular grid would have walked besides",
    labels=("window", "kind"))


def _split_heads(x, num_heads):
    b, t, e = x.shape
    if e % num_heads:
        raise ValueError(
            f"attention hidden size {e} is not divisible by num_heads "
            f"{num_heads}")
    return x.reshape(b, t, num_heads, e // num_heads)


def _causal_mha(q, k, v, num_heads):
    """Plain causal multi-head attention: [b, T, E] x3 -> [b, T, E]."""
    qh = _split_heads(q, num_heads)
    kh = _split_heads(k, num_heads)
    vh = _split_heads(v, num_heads)
    d = qh.shape[-1]
    scores = jnp.einsum("bthd,bshd->bhts", qh, kh) * (d ** -0.5)
    t = q.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -1e9)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p, vh)
    return out.reshape(q.shape)


def _attention_route(q, heads, kv_heads, window, v=None):
    """"pallas" or "jnp" for this call; the forward op and its grad op ask
    the same question and get the same answer. The kernels are asked about
    the query/key heads as ``_lane_padded`` would hand them over."""
    from .pallas import use_pallas
    from .pallas import attention as att

    d = q.shape[-1] // heads
    wide = jax.ShapeDtypeStruct(
        q.shape[:-1] + (heads * (d + -d % att.LANES),), q.dtype)
    if not use_pallas("attention", att.attention_supported(
            wide, heads, kv_heads, window, v)):
        return "jnp"
    T = q.shape[1]
    sched = att.band_schedule(T, att.kernel_block(T), window)
    _M_BLOCKS.labels(window=window, kind="scheduled").set(len(sched.q))
    _M_BLOCKS.labels(window=window, kind="skipped").set(sched.skipped)
    return "pallas"


def _attention_attrs(ctx, q, k, v):
    heads = int(ctx.attr("num_heads"))
    kv_heads = int(ctx.attr("num_kv_heads", 0) or heads)
    if heads % kv_heads or q.shape[-1] % heads \
            or k.shape[-1] * heads != q.shape[-1] * kv_heads \
            or v.shape[-1] % kv_heads:
        raise ValueError(
            f"attention: {heads} query heads over {kv_heads} key/value "
            f"heads do not fit Q {q.shape}, K {k.shape} and V {v.shape}")
    return heads, kv_heads, int(ctx.attr("window", 0) or 0)


def _lane_padded(x, heads):
    """``x`` [b, T, heads * d] with each head filled up with zeros to whole
    128-lane tiles (192 -> 256). Zeros add nothing to a score, so the
    product is the same; the scale stays the true head size's."""
    from .pallas.attention import LANES
    b, t, e = x.shape
    d = e // heads
    if d % LANES == 0:
        return x
    return jnp.pad(x.reshape(b, t, heads, d),
                   ((0, 0), (0, 0), (0, 0), (0, -d % LANES))) \
        .reshape(b, t, -1)


def _cut_heads(padded, like, heads):
    """``_lane_padded``'s inverse for a gradient: each head's leading
    coordinates, in ``like``'s shape."""
    if padded.shape == like.shape:
        return padded
    b, t, e = like.shape
    return padded.reshape(b, t, heads, -1)[..., :e // heads].reshape(b, t, e)


def _attention_forward(q, k, v, heads, kv_heads, window):
    """(out, residual) by the route's forward (ops/pallas/attention.py)."""
    from .pallas import kernel_span
    from .pallas import attention as att

    q, k, v = cast_compute(q, k, v)
    route = _attention_route(q, heads, kv_heads, window, v)
    with kernel_span(route, "attention"):
        if route == "jnp":
            return att.attention_jnp(q, k, v, heads, kv_heads, window)
        return att.attention_pallas(
            _lane_padded(q, heads), _lane_padded(k, kv_heads), v, heads,
            kv_heads, window, scale=(q.shape[-1] // heads) ** -0.5)


def _attention_grad_maker(op):
    inputs = {s: op.input(s) for s in ("Q", "K", "V")}
    inputs["Out"] = op.output("Out")
    if op.output("LogSumExp"):
        inputs["LogSumExp"] = op.output("LogSumExp")
    inputs["Out@GRAD"] = G(op.output("Out"))
    return [OpSpec("causal_self_attention_grad", inputs,
                   {s + "@GRAD": G(op.input(s)) for s in ("Q", "K", "V")},
                   dict(op.attrs))]


@register_op("causal_self_attention", grad=_attention_grad_maker)
def causal_self_attention(ctx):
    """Causal attention over a [b, T, heads*d] window — the training/export
    form the generation engine's program split rewrites per phase.
    ``num_kv_heads`` (default ``num_heads``) key/value heads serve the query
    heads in blocked groups; ``window`` > 0 lets position i see j only
    where i - j < window. V's heads may be of another size than Q's and
    K's (latent attention: 192 / 128); ``Out`` has V's head size and the
    scores are scaled by Q's. Blocked (no [T, T] scores): the
    ``attention`` Pallas family or its jnp twin. The kernels take whole
    128-lane heads: query/key heads of another size are padded with zeros
    to the next multiple of 128 on the way in (exact: a zero adds nothing
    to a score) and their gradients cut back on the way out. ``LogSumExp``
    is the forward's residual, in the form its route keeps it, for the
    grad op."""
    q = data_of(ctx.input("Q"))
    k = data_of(ctx.input("K"))
    v = data_of(ctx.input("V"))
    out, lse = _attention_forward(q, k, v, *_attention_attrs(ctx, q, k, v))
    ctx.set_output("Out", out)
    ctx.set_output("LogSumExp", lse)


@register_op("causal_self_attention_grad")
def causal_self_attention_grad(ctx):
    from .pallas import kernel_span
    from .pallas import attention as att

    q = data_of(ctx.input("Q"))
    k = data_of(ctx.input("K"))
    v = data_of(ctx.input("V"))
    heads, kv_heads, window = _attention_attrs(ctx, q, k, v)
    if ctx.has_input("LogSumExp"):
        out = data_of(ctx.input("Out"))
        lse = data_of(ctx.input("LogSumExp"))
    else:           # a program built before the op kept its residual
        out, lse = _attention_forward(q, k, v, heads, kv_heads, window)
    qc, kc, vc, out, d = cast_compute(q, k, v, out,
                                      data_of(ctx.input("Out@GRAD")))
    route = _attention_route(qc, heads, kv_heads, window, vc)
    d = d.astype(qc.dtype)
    with kernel_span(route, "attention"):
        if route == "jnp":
            dq, dk, dv = att.attention_jnp_bwd(qc, kc, vc, out, lse, d, heads,
                                               kv_heads, window)
        else:
            dq, dk, dv = att.attention_pallas_bwd(
                _lane_padded(qc, heads), _lane_padded(kc, kv_heads), vc, out,
                lse, d, heads, kv_heads, window,
                scale=(q.shape[-1] // heads) ** -0.5)
            dq, dk = _cut_heads(dq, q, heads), _cut_heads(dk, k, kv_heads)
    ctx.set_output("Q@GRAD", dq.astype(q.dtype))
    ctx.set_output("K@GRAD", dk.astype(k.dtype))
    ctx.set_output("V@GRAD", dv.astype(v.dtype))


# ---------------------------------------------------------------------------
# rotary_embedding — rotate Q and K by their positions' angles
# ---------------------------------------------------------------------------

def rotary_inv_freq(head_dim, theta, rope_type="default", factor=1.0,
                    original_max_position=0, beta_fast=32.0, beta_slow=1.0):
    """Per-frequency inverse wavelengths [head_dim / 2], float64 on the
    host. ``default``: theta^(-2i/d). ``yarn``: each frequency blends
    theta^(-2i/d) (kept: extrapolation) with the same over ``factor``
    (interpolation) by a linear ramp between the two correction
    dimensions, those whose wavelength fits ``beta_fast`` and
    ``beta_slow`` times into ``original_max_position``."""
    import math

    import numpy as np

    pos_freqs = float(theta) ** (np.arange(0, head_dim, 2, dtype=np.float64)
                                 / head_dim)
    if rope_type == "default":
        return 1.0 / pos_freqs
    if rope_type != "yarn":
        raise ValueError(f"rotary_embedding: unknown rope_type {rope_type!r}")

    def correction_dim(rotations):
        return head_dim * math.log(original_max_position
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def _rotary_tables(ctx, length):
    """(cos, sin) [T, head_dim] float32 for positions 0..T-1, the two
    halves alike (the ``rotate_half`` convention), times
    ``attention_factor``."""
    inv = rotary_inv_freq(
        int(ctx.attr("head_dim")), ctx.attr("theta", 10000.0),
        ctx.attr("rope_type", "default"), ctx.attr("factor", 1.0),
        ctx.attr("original_max_position", 0), ctx.attr("beta_fast", 32.0),
        ctx.attr("beta_slow", 1.0))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    scale = float(ctx.attr("attention_factor", 1.0))
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(x, cos, sin, head_dim):
    """x * cos + rotate_half(x) * sin per head, in float32; rotate_half
    maps a head's halves (a, b) to (-b, a). The halves change places in a
    product with a signed permutation matrix: exact (one +-1 a column), a
    few GFLOP on the MXU, where slicing and concatenating 64-lane halves
    cost the rotation 20 x its HBM time on a v5e (PERF.md, PR 28)."""
    b, t, e = x.shape
    xh = x.reshape(b, t, e // head_dim, head_dim)
    half = head_dim // 2
    swap = jnp.zeros((head_dim, head_dim), x.dtype)
    swap = swap.at[jnp.arange(half) + half, jnp.arange(half)].set(-1)
    swap = swap.at[jnp.arange(half), jnp.arange(half) + half].set(1)
    rot = jnp.einsum("bthd,de->bthe", xh, swap,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    out = xh.astype(jnp.float32) * cos[None, :, None, :] \
        + rot * sin[None, :, None, :]
    return out.reshape(x.shape).astype(x.dtype)


def _rotary_grad_maker(op):
    return [OpSpec("rotary_embedding_grad",
                   {"QOut@GRAD": G(op.output("QOut")),
                    "KOut@GRAD": G(op.output("KOut"))},
                   {"Q@GRAD": G(op.input("Q")), "K@GRAD": G(op.input("K"))},
                   dict(op.attrs))]


def _rotary_infer(op, block):
    for src, dst in (("Q", "QOut"), ("K", "KOut")):
        x = block.var(op.input(src)[0])
        for name in op.output(dst):
            out = block.var(name)
            out.shape, out.dtype = x.shape, out.dtype or x.dtype


@register_op("rotary_embedding", infer_shape=_rotary_infer,
             grad=_rotary_grad_maker)
def rotary_embedding(ctx):
    """Rotary positions on projected Q and K ([b, T, heads*head_dim]),
    positions 0..T-1. Attrs: ``head_dim``, ``theta``, ``rope_type``
    (``default`` | ``yarn``), and for YaRN ``factor``,
    ``original_max_position``, ``beta_fast``, ``beta_slow``,
    ``attention_factor`` (on cos and sin)."""
    q, k = data_of(ctx.input("Q")), data_of(ctx.input("K"))
    cos, sin = _rotary_tables(ctx, q.shape[1])
    d = int(ctx.attr("head_dim"))
    ctx.set_output("QOut", _rotate(q, cos, sin, d))
    ctx.set_output("KOut", _rotate(k, cos, sin, d))


@register_op("rotary_embedding_grad")
def rotary_embedding_grad(ctx):
    """The rotation is linear and its two sin halves are alike, so the
    gradient is the rotation by the opposite angle."""
    dq = data_of(ctx.input("QOut@GRAD"))
    dk = data_of(ctx.input("KOut@GRAD"))
    cos, sin = _rotary_tables(ctx, dq.shape[1])
    d = int(ctx.attr("head_dim"))
    ctx.set_output("Q@GRAD", _rotate(dq, cos, -sin, d))
    ctx.set_output("K@GRAD", _rotate(dk, cos, -sin, d))


# ---------------------------------------------------------------------------
# latent_kv_heads — per-head keys and values of latent (MLA) attention
# ---------------------------------------------------------------------------

def _latent_dims(ctx, kv):
    """(heads, nope): ``KV`` holds ``heads`` heads of [nope | value]."""
    heads, nope = int(ctx.attr("num_heads")), int(ctx.attr("nope_dim"))
    if kv.shape[-1] % heads or kv.shape[-1] // heads <= nope:
        raise ValueError(
            f"latent_kv_heads: {heads} heads of [{nope} | value] do not "
            f"fit KV {kv.shape}")
    return heads, nope


def _latent_grad_maker(op):
    return [OpSpec("latent_kv_heads_grad",
                   {"K@GRAD": G(op.output("K")), "V@GRAD": G(op.output("V"))},
                   {"KV@GRAD": G(op.input("KV")),
                    "KRope@GRAD": G(op.input("KRope"))},
                   dict(op.attrs))]


def _latent_infer(op, block):
    kv = block.var(op.input("KV")[0])
    rope = block.var(op.input("KRope")[0])
    if kv.shape is None or rope.shape is None:
        return
    heads, nope = int(op.attrs["num_heads"]), int(op.attrs["nope_dim"])
    for slot, width in (("K", heads * (nope + rope.shape[-1])),
                        ("V", kv.shape[-1] - heads * nope)):
        for name in op.output(slot):
            out = block.var(name)
            out.shape = tuple(kv.shape[:-1]) + (width,)
            out.dtype = out.dtype or kv.dtype


@register_op("latent_kv_heads", infer_shape=_latent_infer,
             grad=_latent_grad_maker)
def latent_kv_heads(ctx):
    """The keys and values a latent-attention (MLA) layer trains on, from
    the up-projected latent ``KV`` [b, T, heads * (nope_dim + v)], each
    head [k_nope | v], and the ONE rope key ``KRope`` [b, T, rope]
    that every head shares: ``K`` [b, T, heads * (nope_dim + rope)], head i
    [k_nope_i | k_rope], and ``V`` [b, T, heads * v]. Attrs ``num_heads``,
    ``nope_dim``."""
    kv, rope = data_of(ctx.input("KV")), data_of(ctx.input("KRope"))
    heads, nope = _latent_dims(ctx, kv)
    b, t, _ = kv.shape
    kvh = kv.reshape(b, t, heads, -1)
    shared = jnp.broadcast_to(rope.astype(kv.dtype)[:, :, None, :],
                              (b, t, heads, rope.shape[-1]))
    ctx.set_output("K", jnp.concatenate([kvh[..., :nope], shared], -1)
                   .reshape(b, t, -1))
    ctx.set_output("V", kvh[..., nope:].reshape(b, t, -1))


@register_op("latent_kv_heads_grad")
def latent_kv_heads_grad(ctx):
    """The pieces go back where they came from; the shared rope key's
    gradient is the sum over the heads, in float32."""
    dk, dv = data_of(ctx.input("K@GRAD")), data_of(ctx.input("V@GRAD"))
    heads = int(ctx.attr("num_heads"))
    nope = int(ctx.attr("nope_dim"))
    b, t, _ = dk.shape
    dkh, dvh = dk.reshape(b, t, heads, -1), dv.reshape(b, t, heads, -1)
    ctx.set_output("KV@GRAD", jnp.concatenate(
        [dkh[..., :nope], dvh.astype(dk.dtype)], -1).reshape(b, t, -1))
    ctx.set_output("KRope@GRAD", jnp.sum(
        dkh[..., nope:].astype(jnp.float32), axis=2).astype(dk.dtype))


def _scatter_rows(cache, slots, rows):
    """Write ``rows`` [n, H, D] into the arena [nb, bs, H, D] at flat slots
    [n] (block*block_size + offset). Out-of-range slots (the padding / idle
    sentinel, ``num_blocks * block_size``) are DROPPED — never a wrapped or
    clamped write into some victim sequence's block."""
    nb, bs = cache.shape[0], cache.shape[1]
    flat = cache.reshape((nb * bs,) + cache.shape[2:])
    flat = flat.at[slots].set(rows, mode="drop")
    return flat.reshape(cache.shape)


@register_op("prefill_attention")
def prefill_attention(ctx):
    """Phase 1 of the serving split: causal attention over the (padded)
    prompt window + K/V scatter into the paged arena. Padding positions map
    to the out-of-range sentinel slot and write nothing; because padding
    sits AFTER the real prompt and the mask is causal, every real position's
    output is independent of the padding, so only slot mapping — not an
    extra length mask — is needed."""
    q = data_of(ctx.input("Q"))
    k = data_of(ctx.input("K"))
    v = data_of(ctx.input("V"))
    h = int(ctx.attr("num_heads"))
    kc = data_of(ctx.input("KCache"))
    vc = data_of(ctx.input("VCache"))
    slots = data_of(ctx.input("SlotMapping")).astype(jnp.int32).reshape(-1)
    kh = _split_heads(k, h).reshape((-1,) + kc.shape[2:])
    vh = _split_heads(v, h).reshape((-1,) + vc.shape[2:])
    ctx.set_output("KCacheOut", _scatter_rows(kc, slots, kh))
    ctx.set_output("VCacheOut", _scatter_rows(vc, slots, vh))
    ctx.set_output("Out", _causal_mha(q, k, v, h))


def _gather_context(cache, bt):
    """Arena rows of every context position a block-table row may see:
    cache [nb, bs, H, D], bt [b, P] -> [b, P*bs, H, D] ordered by
    position (table order x in-block offset). Unused table entries
    gather garbage the caller's mask excludes."""
    nb, bs = cache.shape[0], cache.shape[1]
    idx = (bt[:, :, None] * bs
           + jnp.arange(bs, dtype=jnp.int32)[None, None, :]) \
        .reshape(bt.shape[0], -1)
    flat = cache.reshape((nb * bs,) + cache.shape[2:])
    return flat[idx]


@register_op("chunked_prefill_attention")
def chunked_prefill_attention(ctx):
    """Partial prefill over a prompt CHUNK whose earlier positions are
    already in the arena (cached shared prefix and/or previous chunks).
    Q/K/V are the [b, T, E] chunk window; the chunk's K/V rows scatter in
    at ``SlotMapping`` first (sentinel = padding, no write), then every
    window position i attends over the arena context gathered through
    ``BlockTables``, masked causally at its absolute position
    ``ChunkStart + i``. ChunkStart == 0 and an empty arena reduce this
    to full-window causal prefill (the parity anchor)."""
    q = data_of(ctx.input("Q"))
    k = data_of(ctx.input("K"))
    v = data_of(ctx.input("V"))
    h = int(ctx.attr("num_heads"))
    kc = data_of(ctx.input("KCache"))
    vc = data_of(ctx.input("VCache"))
    bt = data_of(ctx.input("BlockTables")).astype(jnp.int32)   # [b, P]
    start = data_of(ctx.input("ChunkStart")).astype(jnp.int32) \
        .reshape(-1)                                           # [b]
    slots = data_of(ctx.input("SlotMapping")).astype(jnp.int32).reshape(-1)

    kh = _split_heads(k, h).reshape((-1,) + kc.shape[2:])
    vh = _split_heads(v, h).reshape((-1,) + vc.shape[2:])
    kc = _scatter_rows(kc, slots, kh)
    vc = _scatter_rows(vc, slots, vh)
    ctx.set_output("KCacheOut", kc)
    ctx.set_output("VCacheOut", vc)

    kctx = _gather_context(kc, bt)                             # [b, C, H, D]
    vctx = _gather_context(vc, bt)
    qh = _split_heads(q, h)                                    # [b, T, H, D]
    d = qh.shape[-1]
    t = q.shape[1]
    scores = jnp.einsum("bthd,bchd->bhtc", qh, kctx) * (d ** -0.5)
    qpos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # [b, T]
    cpos = jnp.arange(kctx.shape[1], dtype=jnp.int32)
    # same mask value (-1e9) and softmax form as _causal_mha: a masked
    # slot contributes exp(-1e9 - max) == 0.0 exactly, so the extra
    # never-visible arena slots change no real position's output bits
    visible = cpos[None, None] <= qpos[:, :, None]             # [b, T, C]
    scores = jnp.where(visible[:, None], scores, -1e9)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhtc,bchd->bthd", p, vctx)
    ctx.set_output("Out", out.reshape(q.shape))


@register_op("paged_attention")
def paged_attention(ctx):
    """Phase 2 of the serving split: one decode step for every slot of the
    fixed-shape batch. Q/K/V are [max_seqs, 1, E]; the new K/V row is
    written at ``SlotMapping`` [max_seqs] first (sentinel = no write), then
    each row's Q attends over the UPDATED arena gathered through its
    ``BlockTables`` row, masked to its ``ContextLens`` prefix (which counts
    the just-written token). Inactive rows (ContextLens == 0) output
    zeros. Under a Pallas ``kernel_tier`` the attend rides the ragged
    paged-attention kernel (scalar-prefetched block tables, no gathered
    context materialized); unsupported shapes fall back to the jnp twin
    silently with a ``fallback_counts()`` bump."""
    q = data_of(ctx.input("Q"))
    k = data_of(ctx.input("K"))
    v = data_of(ctx.input("V"))
    h = int(ctx.attr("num_heads"))
    kc = data_of(ctx.input("KCache"))
    vc = data_of(ctx.input("VCache"))
    bt = data_of(ctx.input("BlockTables")).astype(jnp.int32)   # [b, P]
    ctx_lens = data_of(ctx.input("ContextLens")).astype(jnp.int32)  # [b]
    slots = data_of(ctx.input("SlotMapping")).astype(jnp.int32).reshape(-1)

    kh = _split_heads(k, h).reshape((-1,) + kc.shape[2:])      # [b, H, D]
    vh = _split_heads(v, h).reshape((-1,) + vc.shape[2:])
    kc = _scatter_rows(kc, slots, kh)
    vc = _scatter_rows(vc, slots, vh)
    ctx.set_output("KCacheOut", kc)
    ctx.set_output("VCacheOut", vc)

    from .pallas import kernel_span, use_pallas
    from .pallas import paged_attention as pa

    qh = _split_heads(q, h)[:, 0]                              # [b, H, D]
    b = bt.shape[0]
    if use_pallas("paged_attention",
                  pa.paged_attention_supported(qh, kc, bt)):
        with kernel_span("pallas", "paged_attention"):
            out = pa.paged_attention_pallas(qh, kc, vc, bt, ctx_lens)
    else:
        with kernel_span("jnp", "paged_attention"):
            out = pa.paged_attention_jnp(qh, kc, vc, bt, ctx_lens)
    ctx.set_output("Out", out.reshape(b, 1, -1))

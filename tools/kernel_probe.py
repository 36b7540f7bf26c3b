"""Per-family evidence for ``ops.pallas.AUTO_PALLAS`` membership, taken on
the attached accelerator in ONE process.

A kernel family belongs in the auto set only if, at the shapes the repo
actually runs, its Pallas kernel (1) lowers natively (Mosaic, not the
interpreter), (2) matches its jnp twin, and (3) is not slower than the
twin in a same-process A/B. This tool takes those three observations per
case and prints one JSON line each (also collected into ``--out``):

    {"case", "family", "lowered", "error", "max_abs_err", "max_rel_err",
     "jnp_ms", "pallas_ms", "speedup", "jnp_device_ms", "pallas_device_ms"}

(``*_ms`` by the host's clock around calls that end in ``block_until_ready``;
``*_device_ms`` from a profiler trace of a few calls: the union of the
intervals in which an operation runs on device 0, a call.)

Cases: conv_bn forward and backward at ResNet-50 bottleneck shapes
(batch 256, bf16 — what ``chip_smoke.py`` dispatches under
``kernel_tier=pallas``); the fused momentum step over ResNet-50's real
parameter census; lstm/gru at the ``bench.py`` RNN-lane shape; ctc and
embedding_sgd at the shapes their parity tests pin, scaled to a workload
size; paged_attention at the generation lane's decode shape; banded
attention at the Mellum2 cell's shape (8192 tokens, 32 / 4 heads of 128,
bfloat16), forward and backward, a window layer and the full one; the
grouped product at that cell's expert shape (8 experts of 2304 x 896) and
the experts' combine at its buffer (18432 rows of 2304 to 8192 tokens);
the gated delta rule at the Kimi-Linear cell's shape (4096 tokens, 32 heads
of 128, chunks of 64), forward and backward; the mixers' short convolution
at the Kimi-Linear cell's shape (4096 tokens x 4096 channels, 4 taps, no
bias) and the Nemotron cell's (x 6144, with a bias), forward and backward;
the Mamba-2 state-space core at the Nemotron cell's shape (4096 tokens, 64
heads of 64 in 8 groups of state 128, chunks of 128), forward and backward.
Every family with a dispatch site in ``paddle_tpu/ops/`` has a case.

A kernel that fails to lower is a RESULT here (``lowered: false`` with the
compiler's message), never a crash. A watchdog ends the process if one
case blocks the device for longer than ``CASE_TIMEOUT_S``.

Usage (on the chip): python tools/kernel_probe.py [--tiny] [--only FAMILY]
``--tiny`` shrinks every case so the tool itself can be checked on the CPU
(kernels then run interpreted and the timings mean nothing).
"""

import argparse
import json
import os
import sys
import threading
import time
from contextlib import contextmanager

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

CASE_TIMEOUT_S = 240.0


def _leaves(out):
    import jax
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(out)]


def _errs(got, want):
    """(max abs err, max err relative to the twin's largest magnitude)."""
    abs_e = rel_e = 0.0
    for a, b in zip(_leaves(got), _leaves(want)):
        d = float(np.max(np.abs(a - b))) if a.size else 0.0
        abs_e = max(abs_e, d)
        rel_e = max(rel_e, d / max(float(np.max(np.abs(b))), 1e-30))
    return abs_e, rel_e


def measure(runners, repeats=3, inner=2):
    """Time each runner: ``repeats`` interleaved windows of ``inner`` calls
    each, best window kept; interleaved across runners so drift (thermal, a
    noisy neighbor) hits every one equally instead of biasing whichever ran
    last. One untimed warmup call per runner absorbs trace+compile. Returns
    ``({name: best ms/call}, {name: "ExcType: text"})``: a runner that
    raises in its warmup call is dropped from the timings (a variant that
    cannot run cannot win) and its exception's text is returned, never
    swallowed."""
    import jax

    repeats, inner = max(1, int(repeats)), max(1, int(inner))
    order, dropped = [], {}
    for name in sorted(runners):
        try:
            jax.block_until_ready(runners[name]())
        except Exception as e:
            dropped[name] = f"{type(e).__name__}: {e}"[:1500]
            continue
        order.append(name)
    best = {}
    for _ in range(repeats):
        for name in order:
            fn = runners[name]
            t0 = time.perf_counter()
            out = None
            for _i in range(inner):
                out = fn()
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) * 1e3 / inner
            if name not in best or ms < best[name]:
                best[name] = ms
    return best, dropped


def device_ms(run, calls=4):
    """Device time of one call of ``run`` (already compiled): ``calls`` of
    them under the profiler, the union of the intervals in which an
    operation runs on device 0 over ``calls``. None without a device
    plane in the trace (the CPU)."""
    import tempfile

    import jax
    from benchmark import trace

    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        try:
            out = None
            for _ in range(calls):
                out = run()
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        loaded = trace.load_xplane(logdir, ())
    ops = (loaded or {}).get("devices", {}).get(0)
    if not ops:
        return None
    return trace.measure(trace.union([e[1:] for e in ops])) * 1e3 / calls


def probe(case, family, runners, repeats=5, inner=4):
    """Run one case: twin first (must work), then the Pallas runner (a
    failure is recorded, not raised), then parity, the interleaved A/B
    (:func:`measure`) and each runner's device time (:func:`device_ms`)."""
    import jax

    rec = {"case": case, "family": family, "lowered": False, "error": None,
           "max_abs_err": None, "max_rel_err": None, "jnp_ms": None,
           "pallas_ms": None, "speedup": None, "jnp_device_ms": None,
           "pallas_device_ms": None}
    want = jax.block_until_ready(runners["jnp"]())
    try:
        got = jax.block_until_ready(runners["pallas"]())
    except Exception as e:          # a kernel that cannot compile: a result
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
        return rec
    rec["lowered"] = True
    rec["max_abs_err"], rec["max_rel_err"] = _errs(got, want)
    ms, dropped = measure(runners, repeats=repeats, inner=inner)
    if dropped:
        rec["error"] = "; ".join(f"{n}: {e}" for n, e in dropped.items())
        return rec
    rec["jnp_ms"] = round(ms["jnp"], 4)
    rec["pallas_ms"] = round(ms["pallas"], 4)
    rec["speedup"] = round(ms["jnp"] / ms["pallas"], 4)
    if jax.default_backend() != "cpu":      # no device plane in a CPU trace
        for name in ("jnp", "pallas"):
            on_device = device_ms(runners[name])
            rec[name + "_device_ms"] = on_device and round(on_device, 4)
    return rec


# ---------------------------------------------------------------------------
# case builders: each returns {"jnp": runner, "pallas": runner}
# ---------------------------------------------------------------------------

def conv_bn_case(n, h, cin, cout, k, stride, backward):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.conv_ops import _conv2d_compute
    from paddle_tpu.ops.norm_ops import bn_forward_math, bn_backward_math
    from paddle_tpu.ops.pallas import conv_bn as cbk

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16
    pad = ((k - 1) // 2,) * 2
    strides = (stride, stride)
    ho = -(-h // stride)
    x = jnp.asarray(rng.normal(0, 1, (n, h, h, cin)), dt)
    w = jnp.asarray(rng.normal(0, 0.05, (cout, cin, k, k)), dt)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, cout), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.2, cout), jnp.float32)
    dy = jnp.asarray(rng.normal(0, 1, (n, ho, ho, cout)), dt)
    rm, rv = jnp.zeros(cout), jnp.ones(cout)
    eps = 1e-5

    def conv(a, b):
        return _conv2d_compute(a, b, strides, pad, (1, 1), 1, "NHWC")

    def twin_fwd(x, w):
        y, _m, _v, sm, sv = bn_forward_math(conv(x, w), scale, bias, rm, rv,
                                            eps, 0.9, "NHWC", False)
        return jnp.maximum(y, 0), sm, sv

    def pallas_fwd(x, w):
        return cbk.conv_bn_train_pallas(x, w, scale, bias, eps, strides,
                                        pad, "relu")

    if not backward:
        tf, pf = jax.jit(twin_fwd), jax.jit(pallas_fwd)
        return {"jnp": lambda: tf(x, w), "pallas": lambda: pf(x, w)}

    y, sm, sv = jax.jit(twin_fwd)(x, w)

    def twin_bwd(x, w, dy):
        # the fused op's jnp backward (ops/fused_ops.py) verbatim
        z, vjp = jax.vjp(conv, x, w)
        dz, ds, db = bn_backward_math(z, scale, sm, sv, dy * (y > 0), eps,
                                      "NHWC", False)
        dx, dw = vjp(dz.astype(z.dtype))
        return dx, dw, ds, db

    def pallas_bwd(x, w, dy):
        return cbk.conv_bn_bwd_pallas(x, w, dy, scale, bias, sm, sv, eps,
                                      strides, pad, "relu")

    tb, pb = jax.jit(twin_bwd), jax.jit(pallas_bwd)
    return {"jnp": lambda: tb(x, w, dy), "pallas": lambda: pb(x, w, dy)}


@contextmanager
def _tier(name):
    """Trace what runs inside under ``kernel_tier=name``."""
    from paddle_tpu.core.flags import get_flag, set_flags
    prev = get_flag("kernel_tier")
    set_flags({"kernel_tier": name})
    try:
        yield
    finally:
        set_flags({"kernel_tier": prev})


def rnn_case(cell, b, L, H):
    """The whole recurrence through the op's own compute function, which
    routes by ``kernel_tier`` when it is traced."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import rnn_ops

    rng = np.random.RandomState(0)
    hx = (4 if cell == "lstm" else 3) * H
    x = jnp.asarray(rng.normal(0, 0.1, (b, L, hx)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.1, (H, hx)), jnp.float32)
    lens = jnp.full((b,), L, jnp.int32)
    zeros = jnp.zeros((b, H), x.dtype)

    def route(tier):
        # a function of its own per route: jit's cache is keyed on it
        def compute(x, lens, w):
            if cell == "lstm":
                return rnn_ops._lstm_scan(x, lens, w, zeros, zeros,
                                          "sigmoid", "tanh", "tanh")
            return rnn_ops._gru_compute(x, lens, w, None, None, {})
        fn = jax.jit(compute)

        def run():
            # the first call traces inside the context and pins the route
            # into the jaxpr; later calls are cache hits
            with _tier(tier):
                return fn(x, lens, w)
        return run

    return {"jnp": route("jnp"), "pallas": route("pallas")}


def embedding_sgd_case(rows, dim, nnz):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import embedding as emb

    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.normal(0, 1, (rows, dim)), jnp.float32)
    vals = jnp.asarray(rng.normal(0, 1, (nnz, dim)), jnp.float32)
    # Knuth-hash row ids: distinct (the op merges rows before the kernel)
    # and spread like a minibatch's
    idx = jnp.asarray((np.arange(nnz) * 2654435761) % rows, jnp.int32)
    lr = jnp.asarray(0.01, jnp.float32)
    tf, pf = jax.jit(emb.embedding_sgd_jnp), jax.jit(emb.embedding_sgd_pallas)
    return {"jnp": lambda: tf(p, idx, vals, lr),
            "pallas": lambda: pf(p, idx, vals, lr)}


def paged_attention_case(s, h, d, nb, bs, p):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.RandomState(0)
    qh = jnp.asarray(rng.normal(0, 1, (s, h, d)), jnp.float32)
    kc, vc = (jnp.asarray(rng.normal(0, 1, (nb, bs, h, d)), jnp.float32)
              for _ in range(2))
    bt = jnp.asarray((np.arange(s * p) % nb).reshape(s, p), jnp.int32)
    ctx = jnp.full((s,), min(p, nb) * bs, jnp.int32)
    tf = jax.jit(pa.paged_attention_jnp)
    pf = jax.jit(pa.paged_attention_pallas)
    return {"jnp": lambda: tf(qh, kc, vc, bt, ctx),
            "pallas": lambda: pf(qh, kc, vc, bt, ctx)}


def grouped_matmul_case(rows_per_expert, held, a, b):
    """``rows [R, a] x w [held, a, b]``: the kernel vs ``ragged_dot`` over
    the same tile-aligned groups (what ops/moe_ops.py runs off the tier)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    R = rows_per_expert * held
    rows = jax.random.normal(keys[0], (R, a), jnp.bfloat16)
    w = jax.random.normal(keys[1], (held, a, b), jnp.bfloat16) * 0.05
    lay = moe_ops.layout(jnp.full((held,), rows_per_expert, jnp.int32), R)
    tf = jax.jit(lambda rows, w: jax.lax.ragged_dot(
        rows, w, lay["sizes"], preferred_element_type=jnp.float32))
    pf = jax.jit(lambda rows, w: gm.gmm(rows, w, lay["tile_expert"],
                                        lay["tiles"]))
    return {"jnp": lambda: tf(rows, w), "pallas": lambda: pf(rows, w)}


def moe_combine_case(n, top_k, held, num_experts, h):
    """``rows [R, h]`` float32 -> ``[n, h]``: the gather-and-sum kernel vs
    the scatter-add over the whole buffer (what ops/moe_ops.py runs off the
    tier), with the weights and the mask of padding folded into both, on a
    routing of random tokens by a random router."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas import moe_combine as mc

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    r = moe_ops.route(jax.random.normal(keys[0], (n, 64)),
                      jax.random.normal(keys[1], (64, num_experts)), held,
                      num_experts, top_k, True, 0, 2.0)
    token = jnp.where(r["assign"] >= 0, r["assign"] // top_k, -1)
    rows = jax.random.normal(keys[2], (token.shape[0], h), jnp.float32)
    assert mc.supported(rows, n, held)
    lay = r["layout"]

    tf = jax.jit(lambda rows, weight: moe_ops.combine_jnp(rows, weight,
                                                          token, n))
    pf = jax.jit(lambda rows, weight: mc.combine(
        rows, weight, token, lay["starts"], lay["tile_expert"], n))
    return {"jnp": lambda: tf(rows, r["weight"]),
            "pallas": lambda: pf(rows, r["weight"])}


def delta_rule_case(T, heads, d, chunk, backward):
    """The gated delta rule's chunked core: the two kernels vs the chunked
    jnp scan, bfloat16 q / k / v, float32 log-decays drawn as the Kimi-Linear
    configuration's initial state draws them (``-A softplus(x + dt_bias)``,
    A in U(1, 16) a head, the step log-uniform in [0.001, 0.1] a channel),
    the backward of either route from the twin's kept states."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import linear_attention_ops as la
    from paddle_tpu.ops.pallas import delta_rule as dr

    keys = jax.random.split(jax.random.PRNGKey(chunk), 8)
    q, k, v, dout = (jax.random.normal(key, (1, T, heads * d), jnp.bfloat16)
                     for key in keys[:4])
    rate = jax.random.uniform(keys[4], (heads, 1), minval=1.0, maxval=16.0)
    step = jnp.exp(jax.random.uniform(
        keys[5], (heads, d), minval=np.log(0.001), maxval=np.log(0.1)))
    x = jax.random.normal(keys[6], (1, T, heads, d))
    g = (-rate * jax.nn.softplus(x + jnp.log(jnp.expm1(step)))) \
        .reshape(1, T, heads * d).astype(jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(keys[7], (1, T, heads))) \
        .astype(jnp.bfloat16)
    assert dr.supported(q, v, g, heads, chunk)
    scale = d ** -0.5
    twin = jax.jit(lambda *a: la.chunked_delta_rule_jnp(*a, heads, chunk,
                                                        scale))
    states = twin(q, k, v, g, beta)[1] if backward else None

    def route(fwd, bwd):
        if not backward:
            forward = jax.jit(lambda *a: fwd(*a, heads, chunk, scale))
            return lambda: forward(q, k, v, g, beta)
        grads = jax.jit(lambda *a: bwd(*a, heads, chunk, scale))
        return lambda: grads(q, k, v, g, beta, states, dout)

    return {"jnp": route(la.chunked_delta_rule_jnp,
                         la.chunked_delta_rule_bwd_jnp),
            "pallas": route(dr.delta_rule_fwd, dr.delta_rule_bwd)}


def causal_conv1d_case(T, channels, taps, bias, backward):
    """The mixers' short convolution with its SiLU: the two kernels vs the
    jnp op and ``jax.vjp`` of it (what the grad op runs off the tier, its
    barrier included), bfloat16 x and ``Out@GRAD``, float32 filter and
    bias."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import linear_attention_ops as la
    from paddle_tpu.ops.pallas import causal_conv1d as cc

    keys = jax.random.split(jax.random.PRNGKey(taps), 4)
    x, dout = (jax.random.normal(k, (1, T, channels), jnp.bfloat16)
               for k in keys[:2])
    w = jax.random.uniform(keys[2], (taps, channels), minval=-0.5, maxval=0.5)
    b = jax.random.uniform(keys[3], (channels,), minval=-0.5, maxval=0.5) \
        if bias else None
    assert cc.supported(x, w)
    args = (x, w) + ((b,) if bias else ())
    if not backward:
        twin = jax.jit(lambda *a: la._causal_conv1d(None, *a))
        return {"jnp": lambda: twin(*args),
                "pallas": lambda: cc.causal_conv1d_fwd(x, w, b)}
    twin = jax.jit(lambda dout, *a: la._vjp_grads(
        la._causal_conv1d, None, list(a), dout))
    return {"jnp": lambda: twin(dout, *args),
            "pallas": lambda: tuple(g for g in cc.causal_conv1d_bwd(
                x, w, b, dout) if g is not None)}


def ssd_scan_case(T, heads, p, groups, n, chunk, backward):
    """The Mamba-2 state-space core through the op's own two functions,
    which route by ``kernel_tier`` when they are traced (``_prepare`` and its
    backward on both sides): bfloat16 x, B, C and raw step, decays drawn as
    the Nemotron configuration's initial state draws them (A in U(1, 16) a
    head, the step log-uniform in [0.001, 0.1]), the backward of either
    route from the twin's kept states."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import state_space_ops as ss

    keys = jax.random.split(jax.random.PRNGKey(chunk), 8)
    x, dout = (jax.random.normal(k, (1, T, heads * p), jnp.bfloat16)
               for k in keys[:2])
    b, c = (jax.random.normal(k, (1, T, groups * n), jnp.bfloat16)
            for k in keys[2:4])
    dt = (0.5 * jax.random.normal(keys[4], (1, T, heads))) \
        .astype(jnp.bfloat16)
    a_log = jnp.log(jax.random.uniform(keys[5], (heads,), minval=1.0,
                                       maxval=16.0))
    dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        keys[6], (heads,), minval=np.log(0.001), maxval=np.log(0.1)))))
    d = 1.0 + 0.3 * jax.random.normal(keys[7], (heads,))
    args = (x, dt, b, c, a_log, dt_bias, d)
    states = jax.jit(lambda *a: ss.ssd_chunked_jnp(
        *a, heads, groups, chunk))(*args)[1] if backward else None

    def route(tier):
        def compute(*a):        # a function of its own per route (jit's key)
            if backward:
                return ss.ssd_chunked_bwd(*a, heads, groups, chunk)
            return ss.ssd_chunked(*a, heads, groups, chunk)
        fn = jax.jit(compute)

        def run():
            with _tier(tier):   # the first call traces, and pins the route
                return fn(*args, states, dout) if backward else fn(*args)
        return run

    return {"jnp": route("jnp"), "pallas": route("pallas")}


def momentum_case(shapes):
    """One fused-momentum step over ``shapes``: the arena megakernel (with
    the concat/split the fused op pays) vs the per-param twin."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.optimizer_ops import _momentum_dense
    from paddle_tpu.ops.pallas import optimizer as opk

    rng = np.random.RandomState(0)
    ps = [jnp.asarray(rng.normal(0, 1, s), jnp.float32) for s in shapes]
    gs = [jnp.asarray(rng.normal(0, 1e-3, s), jnp.float32) for s in shapes]
    vs = [jnp.zeros(s, jnp.float32) for s in shapes]
    lr, mu = 0.1, 0.9

    def fused(ps, gs, vs):
        pa, ga, va = (opk.flatten_arena(t)[0] for t in (ps, gs, vs))
        po, vo = opk.momentum_arena_pallas(pa, ga, va, lr, mu)
        return opk.split_arena(po, shapes), opk.split_arena(vo, shapes)

    def twin(ps, gs, vs):
        out = [_momentum_dense(p, g, v, lr, mu, False)
               for p, g, v in zip(ps, gs, vs)]
        return [o[0] for o in out], [o[1] for o in out]

    ff, tf = jax.jit(fused), jax.jit(twin)
    return {"jnp": lambda: tf(ps, gs, vs), "pallas": lambda: ff(ps, gs, vs)}


def ctc_case(b, t, c, u):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ctc_ops

    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.normal(0, 1, (b, t, c)), jnp.float32)
    labels = jnp.asarray(rng.randint(1, c, (b, u)), jnp.int32)
    x_lens = jnp.asarray(rng.randint(t // 2, t + 1, b), jnp.int32)
    y_lens = jnp.asarray(rng.randint(1, u + 1, b), jnp.int32)
    scan = jax.jit(lambda l: ctc_ops._ctc_loss_scan(l, x_lens, labels,
                                                    y_lens, 0))
    pal = jax.jit(lambda l: ctc_ops._ctc_loss_pallas(l, x_lens, labels,
                                                     y_lens, 0))
    return {"jnp": lambda: scan(logits), "pallas": lambda: pal(logits)}


def attention_case(T, heads, kv_heads, window, backward):
    """Banded grouped-query attention: the kernels vs the blocked twin,
    each route's backward from its own forward's residual."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import attention as att

    d = 128
    keys = jax.random.split(jax.random.PRNGKey(window), 4)
    q, dout = (jax.random.normal(k, (1, T, heads * d), jnp.bfloat16)
               for k in keys[:2])
    k, v = (jax.random.normal(k, (1, T, kv_heads * d), jnp.bfloat16)
            for k in keys[2:])

    def route(fwd, bwd):
        forward = jax.jit(lambda q, k, v: fwd(q, k, v, heads, kv_heads,
                                              window))
        if not backward:
            return lambda: forward(q, k, v)[0]
        out, lse = forward(q, k, v)
        grads = jax.jit(lambda q, k, v, dout: bwd(
            q, k, v, out, lse, dout, heads, kv_heads, window))
        return lambda: grads(q, k, v, dout)

    return {"jnp": route(att.attention_jnp, att.attention_jnp_bwd),
            "pallas": route(att.attention_pallas, att.attention_pallas_bwd)}


def resnet50_param_shapes():
    """The tensors the flagship's optimizer updates, read off the built
    program (the PR-21 run also counted the 106 BN running statistics:
    267 tensors, the same 25.6 M elements)."""
    import bench
    main, _startup, _loss = bench.build(8, 224, 1000)
    return [tuple(p.shape) for p in main.global_block().all_parameters()
            if p.trainable]


def cases(tiny):
    """Yield (case name, family, zero-arg builder)."""
    n = 4 if tiny else 256
    # (h, cin, cout, k, stride): one of each kind per ResNet-50 stage
    convs = [(8, 8, 8, 3, 1), (8, 8, 16, 1, 1)] if tiny else [
        (56, 64, 64, 3, 1), (56, 64, 256, 1, 1), (56, 256, 512, 1, 2),
        (28, 128, 128, 3, 1), (14, 256, 256, 3, 1), (14, 1024, 256, 1, 1),
        (7, 512, 512, 3, 1)]
    for h, cin, cout, k, s in convs:
        for bwd in (False, True):
            yield (f"conv_bn_{'bwd' if bwd else 'fwd'}_{h}x{h}_{cin}to{cout}"
                   f"_k{k}s{s}", "conv_bn",
                   lambda a=(n, h, cin, cout, k, s, bwd): conv_bn_case(*a))
    shapes = [(64, 16)] * 4 + [(16,)] * 4 if tiny \
        else resnet50_param_shapes()
    yield ("optimizer_momentum_resnet50", "optimizer",
           lambda: momentum_case(shapes))
    b, L, H = (4, 6, 128) if tiny else (64, 100, 512)
    for cell in ("lstm", "gru"):
        yield (f"{cell}_b{b}_len{L}_hid{H}", cell,
               lambda a=(cell, b, L, H): rnn_case(*a))
    yield ("ctc", "ctc", lambda: ctc_case(*((2, 6, 8, 2) if tiny
                                            else (32, 128, 96, 24))))
    rows, dim, nnz = (64, 128, 8) if tiny else (30000, 128, 6400)
    yield ("embedding_sgd", "embedding_sgd",
           lambda a=(rows, dim, nnz): embedding_sgd_case(*a))
    s, nb = (2, 8) if tiny else (8, 64)
    yield ("paged_attention", "paged_attention",
           lambda a=(s, 4, 128, nb, 16, 4): paged_attention_case(*a))
    T, heads, kv, win = (384, 2, 1, 256) if tiny else (8192, 32, 4, 1024)
    for window in (win, 0):
        for bwd in (False, True):
            yield (f"attention_{'bwd' if bwd else 'fwd'}_len{T}_window"
                   f"{window}", "attention",
                   lambda a=(T, heads, kv, window, bwd): attention_case(*a))
    # the Mellum2 cell's experts, then the Nemotron cell's: a width of 14.5
    # lane tiles (tiny: 1.5)
    for gm in ([(256, 2, 128, 128), (256, 2, 128, 192)] if tiny
               else [(1024, 8, 2304, 896), (256, 8, 2688, 1856)]):
        yield ("grouped_matmul_{1}x{0}rows_{2}x{3}".format(*gm),
               "grouped_matmul", lambda a=gm: grouped_matmul_case(*a))
    mc = (256, 2, 4, 8, 128) if tiny else (8192, 8, 8, 64, 2304)
    yield ("moe_combine_{0}tokens_top{1}_{2}of{3}_width{4}".format(*mc),
           "moe_combine", lambda a=mc: moe_combine_case(*a))
    T, heads = (128, 2) if tiny else (4096, 32)
    for bwd in (False, True):
        yield (f"delta_rule_{'bwd' if bwd else 'fwd'}_len{T}_{heads}x128"
               "_chunk64", "delta_rule",
               lambda a=(T, heads, 128, 64, bwd): delta_rule_case(*a))
    # the Kimi-Linear cell's three convolutions a KDA layer, then the
    # Nemotron cell's one a mixer
    for conv in ([(64, 256, 4, True)] if tiny
                 else [(4096, 4096, 4, False), (4096, 6144, 4, True)]):
        for bwd in (False, True):
            yield ("causal_conv1d_{4}_len{0}_{1}ch_{2}taps{3}".format(
                *conv[:3], "_bias" if conv[3] else "",
                "bwd" if bwd else "fwd"), "causal_conv1d",
                lambda a=conv + (bwd,): causal_conv1d_case(*a))
    # the Nemotron cell's Mamba-2 core
    ssd = (256, 4, 64, 2, 128, 128) if tiny else (4096, 64, 64, 8, 128, 128)
    for bwd in (False, True):
        yield ("ssd_scan_{6}_len{0}_{1}x{2}_{3}groups_state{4}_chunk{5}"
               .format(*ssd, "bwd" if bwd else "fwd"), "ssd_scan",
               lambda a=ssd + (bwd,): ssd_scan_case(*a))


def lstm_lane_step(tiny, rounds=3):
    """The bench.py LSTM text-cls lane's whole TRAINING step, scan path vs
    the Pallas recurrence, by the lane's own runner, interleaved (jnp,
    pallas, pallas, jnp) x ``rounds`` — the family's end-to-end A/B.
    Reports the best of each and every run (``runs_ms``) so the spread is
    on the record next to the difference."""
    import bench
    kw = dict(batch=4, seq_len=6, hidden=128, steps=2, warmup=1,
              vocab=64) if tiny else {}
    rec = {"case": "lstm_textcls_train_step", "family": "lstm",
           "lowered": False, "error": None, "jnp_ms": None,
           "pallas_ms": None, "speedup": None}
    ms = {False: [], True: []}
    try:
        for use_pallas in (False, True, True, False) * rounds:
            ms[use_pallas].append(
                bench.run_lstm_lane(use_pallas=use_pallas, **kw))
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:1500]
        return rec
    rec.update(lowered=True, jnp_ms=round(min(ms[False]), 4),
               pallas_ms=round(min(ms[True]), 4),
               speedup=round(min(ms[False]) / min(ms[True]), 4),
               runs_ms={"jnp": [round(v, 4) for v in ms[False]],
                        "pallas": [round(v, 4) for v in ms[True]]})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default=None, help="one kernel family")
    ap.add_argument("--out", default="chiprun_out/kernel_probe.json")
    args = ap.parse_args()

    import jax
    from paddle_tpu.ops.pallas import on_cpu
    dev = jax.devices()[0]
    head = {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices()), "interpret": on_cpu(),
            "tiny": args.tiny}
    print(json.dumps(head), flush=True)

    current = {"case": None, "deadline": None}

    def watchdog():
        while True:
            time.sleep(1.0)
            d = current["deadline"]
            if d is not None and time.monotonic() > d:
                print(json.dumps({"case": current["case"],
                                  "error": "watchdog: case blocked the "
                                           "device past CASE_TIMEOUT_S"}),
                      flush=True)
                os._exit(4)

    threading.Thread(target=watchdog, daemon=True).start()
    results = []
    todo = [(name, family,
             lambda n=name, f=family, b=build: probe(n, f, b()))
            for name, family, build in cases(args.tiny)]
    todo.append(("lstm_textcls_train_step", "lstm",
                 lambda: lstm_lane_step(args.tiny)))
    for name, family, run in todo:
        if args.only and family != args.only:
            continue
        current.update(case=name,
                       deadline=time.monotonic() + CASE_TIMEOUT_S)
        rec = run()
        current["deadline"] = None
        results.append(rec)
        print(json.dumps(rec), flush=True)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": head, "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

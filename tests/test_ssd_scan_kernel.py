"""The Pallas family ``ssd_scan`` (paddle_tpu/ops/pallas/ssd_scan.py): the
Mamba-2 state-space core's two kernels, interpreted on the CPU under
``kernel_tier=pallas``, through the op's own dispatch
(``state_space_ops.ssd_chunked`` / ``ssd_chunked_bwd``), against the jnp twin
AND against the token-by-token recurrence: ``Out``, the kept ``States`` and
all seven gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import state_space_ops as ss


@pytest.fixture
def kernel_tier():
    """``kernel_tier=pallas`` for one test (the kernels then run interpreted
    on the CPU), the fallback counters zeroed before and after."""
    from paddle_tpu.ops import pallas as tier
    tier.reset_fallback_counts()
    fluid.set_flags({"kernel_tier": "pallas"})
    yield tier
    fluid.set_flags({"kernel_tier": "auto"})
    tier.reset_fallback_counts()


def _interpreted(tier):
    return tier.dispatch_counts().get("ssd_scan", {}).get("interpret", 0)


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def _inputs(t, rate, bt, heads, groups, p, n, dtype=jnp.float32, seed=0):
    """The op's seven inputs: steps of 0.001..0.1 through the inverse
    softplus, decays up to ``rate`` nats a token at the largest step (1.6 is
    the fastest the configuration's initialiser draws)."""
    rng = np.random.RandomState(seed)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    a = rng.uniform(1.0, 16.0, heads) * rate / 1.6
    return (rand(bt, t, heads * p).astype(dtype),
            (0.5 * rand(bt, t, heads)).astype(dtype),
            rand(bt, t, groups * n).astype(dtype),
            rand(bt, t, groups * n).astype(dtype),
            jnp.asarray(np.log(a), jnp.float32),
            jnp.asarray(np.log(np.expm1(step)), jnp.float32),
            jnp.asarray(1.0 + 0.3 * rng.randn(heads), jnp.float32))


def _recurrence(x, dt, b, c, a_log, dt_bias, d, heads, groups, chunk):
    """Token by token from a zero state: (y [bt, T, H * P], the state
    before each chunk's first token [bt, chunks, H, P, N])."""
    bt, t, _ = x.shape
    group = jnp.arange(heads) // (heads // groups)

    def one(x, dt, b, c):
        step = jax.nn.softplus(dt + dt_bias)                    # [T, H]
        decay = jnp.exp(-jnp.exp(a_log) * step)
        xs = x.reshape(t, heads, -1)
        bs, cs = (v.reshape(t, groups, -1)[:, group] for v in (b, c))

        def token(state, inputs):
            x_t, step_t, decay_t, b_t, c_t = inputs
            new = decay_t[:, None, None] * state + (
                step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            y = jnp.einsum("hpn,hn->hp", new, c_t,
                           precision=jax.lax.Precision.HIGHEST)
            return new, (y + d[:, None] * x_t, state)
        zero = jnp.zeros((heads, xs.shape[2], bs.shape[2]), jnp.float32)
        _, (y, before) = jax.lax.scan(token, zero,
                                      (xs, step, decay, bs, cs))
        return y.reshape(t, -1), before[::chunk]
    return jax.vmap(one)(x, dt, b, c)


# name: (tokens, chunk, fastest decay in nats a token, batch, heads, groups,
# P, N). The cell's ratios (8 heads of 64 a group, state 128, chunks of 128)
# over two chunks; a last partial chunk (padded around the kernels); one
# chunk longer than the sequence; two batch rows; thirty times the fastest
# decay (a head wiped at every token: no exponent is ever positive, nothing
# overflows); a group a head, heads of 128 (a unit is one head); four heads
# of 32 a lane tile at chunks of 64 over three groups
CASES = {
    "cell_ratios": (256, 128, 1.6, 1, 8, 1, 64, 128),
    "partial_chunk": (200, 128, 1.6, 1, 4, 2, 64, 128),
    "one_chunk": (96, 128, 1.6, 1, 2, 1, 64, 128),
    "batch_2": (128, 128, 0.3, 2, 4, 2, 64, 128),
    "thirty_times": (256, 128, 50.0, 1, 4, 2, 64, 128),
    "group_a_head": (256, 128, 1.6, 1, 2, 2, 128, 128),
    "heads_of_32": (192, 64, 1.6, 1, 12, 3, 32, 256),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_recurrence_and_the_twin(kernel_tier, case):
    t, chunk, rate, bt, heads, groups, p, n = CASES[case]
    args = _inputs(t, rate, bt, heads, groups, p, n)
    dout = jnp.asarray(np.random.RandomState(1).randn(bt, t, heads * p),
                       jnp.float32)
    before = _interpreted(kernel_tier)
    out, states = ss.ssd_chunked(*args, heads, groups, chunk)
    grads = ss.ssd_chunked_bwd(*args, states, dout, heads, groups, chunk)
    assert _interpreted(kernel_tier) == before + 2
    assert kernel_tier.fallback_counts() == {}
    assert states.shape == (bt, -(-t // chunk), heads, p, n)
    assert not np.asarray(states[:, 0]).any()           # from a zero state
    assert len(grads) == 7                 # x, dt, B, C, A_log, dt_bias, D

    loose = rate > 10
    want, want_states = _recurrence(*args, heads, groups, chunk)
    assert out.shape == want.shape and bool(jnp.isfinite(out).all())
    assert _err(out, want) < 1e-5
    assert _err(states, want_states) < 1e-5
    wants = jax.grad(
        lambda *a: jnp.sum(_recurrence(*a, heads, groups, chunk)[0] * dout),
        argnums=tuple(range(7)))(*args)
    for a, b in zip(grads, wants):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all())
        assert _err(a, b) < (1e-3 if loose else 1e-4)

    twin_out, twin_states = ss.ssd_chunked_jnp(*args, heads, groups, chunk)
    twin_grads = ss.ssd_chunked_bwd_jnp(*args, twin_states, dout, heads,
                                        groups, chunk)
    assert _err(out, twin_out) < 1e-5
    # (the twin's pass over the chunk states subtracts sums of log-decays,
    # thousands of nats at thirty times the decay; the kernel's carried
    # state does not, and is the nearer to the recurrence)
    assert _err(states, twin_states) < (1e-3 if loose else 2e-5)
    for a, b in zip(grads, twin_grads):
        assert _err(a, b) < (3e-3 if loose else 1e-4)


def test_the_kernels_take_bfloat16_as_the_twin_does(kernel_tier):
    """bfloat16 x, B, C and raw step (the AMP types): the kernels round
    where the twin rounds (the operands of a chunk's four products), so
    ``Out`` is one rounding from the twin's, the states agree, every
    gradient is no further from the float32 core than the twin's is, and
    the gradients leave in their inputs' types."""
    t, chunk, heads, groups, p, n = 256, 128, 8, 1, 64, 128
    args = _inputs(t, 1.6, 1, heads, groups, p, n, dtype=jnp.bfloat16)
    dout = jnp.asarray(np.random.RandomState(1).randn(1, t, heads * p),
                       jnp.bfloat16)
    out, states = ss.ssd_chunked(*args, heads, groups, chunk)
    grads = ss.ssd_chunked_bwd(*args, states, dout, heads, groups, chunk)
    twin_out, twin_states = ss.ssd_chunked_jnp(*args, heads, groups, chunk)
    twin_grads = ss.ssd_chunked_bwd_jnp(*args, twin_states, dout, heads,
                                        groups, chunk)
    exact = [a.astype(jnp.float32) for a in args]
    _, exact_states = ss.ssd_chunked_jnp(*exact, heads, groups, chunk)
    exact_grads = ss.ssd_chunked_bwd_jnp(
        *exact, exact_states, dout.astype(jnp.float32), heads, groups, chunk)
    assert out.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    assert _err(out, twin_out) < 4e-3 and _err(states, twin_states) < 1e-5
    for x, a, b, c in zip(args, grads, twin_grads, exact_grads):
        assert a.shape == x.shape
        assert _err(a, c) < max(1.5 * _err(b, c), 8e-3)
        assert _err(a, b) < 1.2e-2


def _run_ops(build, feed, wanted):
    """Build a small program around ``build(vars) -> out``, take the loss
    ``sum(out * out)``, and fetch ``out``, ``wanted``'s gradients (names)
    and the parameters' gradients."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        data = {name: fluid.layers.data(name, shape=list(v.shape),
                                        append_batch_size=False)
                for name, v in feed.items()}
        for var in data.values():
            var.stop_gradient = False
        out = build(data)
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, out)))
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    got = exe.run(main, feed=feed, scope=scope,
                  fetch_list=[out.name] + [w + "@GRAD" for w in wanted]
                  + [q.name + "@GRAD" for q in params])
    return got[0], got[1:]


def test_the_op_in_a_program_runs_the_kernels_and_fills_every_grad_slot(
        kernel_tier):
    """``ssd_scan`` and its grad op through the executor under
    ``kernel_tier=pallas``: one dispatch a direction, ``Out`` and all seven
    ``@GRAD`` slots (the four inputs', ``ALog``'s, ``DtBias``'s, ``D``'s)
    equal to the same program on the twin."""
    t, heads, groups = 200, 4, 2
    names = ("x", "dt", "b", "c")
    feed = {k: np.asarray(v) for k, v in zip(
        names, _inputs(t, 1.6, 1, heads, groups, 64, 128, seed=5))}

    def run():
        return _run_ops(lambda v: fluid.layers.ssd_scan(
            *(v[k] for k in names), heads, n_groups=groups, chunk_size=128),
            feed, names)
    before = _interpreted(kernel_tier)
    out, grads = run()
    assert _interpreted(kernel_tier) == before + 2
    assert kernel_tier.fallback_counts() == {}
    fluid.set_flags({"kernel_tier": "jnp"})
    twin_out, twin_grads = run()
    assert _interpreted(kernel_tier) == before + 2
    assert out.shape == (1, t, heads * 64) and _err(out, twin_out) < 1e-5
    assert len(grads) == len(twin_grads) == 7
    for a, b in zip(grads, twin_grads):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert _err(a, b) < 1e-4


# heads of 48 (three to a lane tile and a half); a state of 64 (half a lane
# tile); chunks of 512 (over the kernels' 256)
@pytest.mark.parametrize("p,n,chunk", [(48, 128, 128), (64, 64, 128),
                                       (64, 128, 512)],
                         ids=["P48", "N64", "chunk512"])
def test_shapes_the_kernels_do_not_take_run_the_twin_and_are_counted(
        kernel_tier, p, n, chunk):
    """Under ``kernel_tier=pallas`` the predicate reads the shapes, the twin
    runs to the bit, and the ``ssd_scan`` fallback counter moves once a
    direction; no kernel is dispatched."""
    from paddle_tpu.ops.pallas import ssd_scan as kernels
    heads, groups, t = 8, 1, 70
    args = _inputs(t, 0.5, 1, heads, groups, p, n)
    assert not kernels.supported(args[0], args[2], heads, groups, chunk)
    dout = jnp.ones((1, t, heads * p), jnp.float32)
    before = _interpreted(kernel_tier)
    out, states = ss.ssd_chunked(*args, heads, groups, chunk)
    assert kernel_tier.fallback_counts() == {"ssd_scan": 1}
    grads = ss.ssd_chunked_bwd(*args, states, dout, heads, groups, chunk)
    assert kernel_tier.fallback_counts() == {"ssd_scan": 2}
    assert _interpreted(kernel_tier) == before
    twin_out, twin_states = ss.ssd_chunked_jnp(*args, heads, groups, chunk)
    assert np.array_equal(out, twin_out)
    assert np.array_equal(states, twin_states)
    for a, b in zip(grads, ss.ssd_chunked_bwd_jnp(
            *args, twin_states, dout, heads, groups, chunk)):
        assert np.array_equal(a, b)


def test_supported_reads_shapes_alone():
    """Whole 128-lane widths of a group's channels and of the state, heads
    that fill or evenly share a lane tile, chunks of whole sublane tiles of
    the array's type and at most 256 tokens, a backward step's blocks inside
    the VMEM budget."""
    from paddle_tpu.ops.pallas import ssd_scan as kernels

    def ok(heads, groups, p, n, chunk, dtype=jnp.bfloat16):
        x = jax.ShapeDtypeStruct((1, 4096, heads * p), dtype)
        b = jax.ShapeDtypeStruct((1, 4096, groups * n), dtype)
        return kernels.supported(x, b, heads, groups, chunk)
    assert ok(64, 8, 64, 128, 128)                      # the Nemotron cell
    assert ok(64, 8, 64, 128, 128, jnp.float32)
    assert ok(8, 8, 128, 128, 64) and ok(8, 4, 256, 256, 256)
    assert ok(16, 2, 32, 128, 16)
    assert not ok(64, 8, 64, 128, 128, jnp.float16)
    assert not ok(8, 8, 64, 128, 128)       # one head of 64: half a tile
    assert not ok(8, 1, 48, 128, 128) and not ok(8, 1, 64, 64, 128)
    assert not ok(8, 1, 64, 128, 512) and not ok(8, 1, 64, 128, 8)
    assert ok(8, 1, 64, 128, 8, jnp.float32)
    assert not ok(8, 1, 64, 128, 100)
    assert not ok(64, 1, 256, 512, 256)     # 16384 x 512 states: 32 MiB each
    assert kernels.vmem_bytes(128, 512, 128, jnp.bfloat16) < 8 << 20

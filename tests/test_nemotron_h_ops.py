"""The Mamba-2 state-space core in chunks (``ssd_scan``) with its
convolution (one filter a channel and a bias) and gated group norm,
single-mixer layers, un-gated squared-ReLU experts under sigmoid routing with
a selection bias, the shared expert and grouped-query attention without
positions as Fluid ops, against the plain reference
(paddle_tpu/testing/reference/nemotron_h.py) at a tiny size on the CPU:
hidden 48; Mamba 8 heads of 8 in 4 groups of state 16, conv 4, chunks of 16;
attention 4 / 2 heads of 16; 32 experts top 4 (8 held in the share) of width
24, one shared of 40; the layers ``MEMEM*E`` of the pattern; 40 tokens (two
chunks and a half), seeded random weights and a seeded non-zero selection
bias."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import state_space_ops as ss
from paddle_tpu.testing.models import build_nemotron_h_lm
from paddle_tpu.testing.reference import nemotron_h as ref

T = 40
TINY = dict(
    hidden_size=48, layer_norm_epsilon=1e-5, num_hidden_layers=7,
    hybrid_override_pattern="MEMEM*EMEMEM*E", mamba_num_heads=8,
    mamba_head_dim=8, n_groups=4, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, use_conv_bias=True, time_step_min=0.001,
    time_step_max=0.1, time_step_floor=1e-4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_experts_routed=32,
    n_routed_experts=32, expert_offset=0, num_experts_per_tok=4,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    rescale_prenorm_residual=True, published={"num_hidden_layers": 52},
    vocab_size=96, init_std=0.3, mamba_out_init_std=1.5,
    attention_out_init_std=1.5, selection_bias_init_std=0.1,
    bias_update_rate=0.001, balance_loss_coef=1e-4, row_buffer_factor=2.0)
# the chip's share: experts 8..15 of 32
SHARE = dict(TINY, n_routed_experts=8, expert_offset=8,
             row_buffer_factor=4.0)
TOLERANCE = 1e-4            # float32 on the CPU: roundings only
N_PARAMS = 1 + 3 * 9 + 5 + 3 * 7 + 2


def _tokens(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64),
            rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64))


def _system(cfg, seed=3):
    """One step's loss, logits, loads and gradients from the program's own
    seeded start-up weights; those weights, in creation order; and the
    selection biases after the step."""
    main, startup, loss, logits, loads = build_nemotron_h_lm(cfg, T)
    startup.random_seed = main.random_seed = seed
    pairs = fluid.backward.append_backward(loss)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    names = [p.name for p in params]
    weights = [np.asarray(scope.find_var(n)) for n in names]
    tok, lab = _tokens()
    out = exe.run(main, feed={"tokens": tok, "labels": lab},
                  fetch_list=[loss, logits] + loads + [g for _, g in pairs],
                  scope=scope)
    n = len(loads)
    grads = dict(zip([p.name for p, _ in pairs], out[2 + n:]))
    return dict(loss=float(out[0]), logits=np.asarray(out[1])[0],
                loads=[np.asarray(x) for x in out[2:2 + n]], grads=grads,
                names=names, weights=weights, params=params, main=main,
                biases_after={p.name: np.asarray(scope.find_var(p.name))
                              for p in params if not p.trainable},
                tokens=tok[0, :, 0], labels=lab[0, :, 0])


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.fixture(scope="module")
def share():
    return _system(SHARE)


@pytest.mark.parametrize("cfg", [TINY, SHARE], ids=["whole", "share"])
def test_program_matches_reference_loss_logits_and_every_gradient(cfg):
    """All three kinds of mixer, the shared expert and the balance term; the
    selection bias has no gradient in the program and a zero one in the
    reference."""
    got = _system(cfg)
    loss, logits, loads, _, grads, _ = ref.run(
        cfg, got["weights"], got["tokens"], got["labels"], with_grads=True)
    assert abs(got["loss"] - float(loss)) < TOLERANCE * float(loss)
    assert _err(got["logits"], logits) < TOLERANCE
    for a, b in zip(got["loads"], loads):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert len(got["names"]) == len(grads) == N_PARAMS
    for p, b in zip(got["params"], grads):
        if p.trainable:
            a = got["grads"][p.name]
            assert a.shape == b.shape and _err(a, b) < TOLERANCE, p.name
        else:
            assert p.name not in got["grads"] and not np.asarray(b).any()


@pytest.mark.parametrize("mutation", ref.MUTATIONS)
def test_a_mutated_reference_fails_the_same_tolerance(share, mutation):
    """Each piece of the mathematics is visible at the tolerance: the
    reference with the piece broken is further from the system than it
    allows."""
    logits = ref.run(SHARE, share["weights"], share["tokens"],
                     share["labels"], mutate=mutation)[1]
    assert _err(share["logits"], logits) > 100 * TOLERANCE, mutation


def test_the_layer_kinds_are_read_from_the_pattern(share):
    """The first ``num_hidden_layers`` letters, ``MEMEM*E``: three Mamba-2
    mixers, three expert layers, one attention, ONE mixer a layer."""
    assert ref.layer_kinds(SHARE) == [
        "mamba", "experts", "mamba", "experts", "mamba", "attention",
        "experts"]
    types = collections.Counter(
        op.type for op in share["main"].global_block().ops)
    assert (types["ssd_scan"], types["causal_conv1d"],
            types["gated_rms_norm"], types["causal_self_attention"],
            types["routed_experts"], types["expert_bias_update"],
            types["rms_norm"]) == (3, 3, 3, 1, 3, 3, 8)
    assert not types["rotary_embedding"] and not types["gated_delta_rule"]
    assert ref.layer_kinds(dict(SHARE, num_hidden_layers=9))[-2:] == [
        "mamba", "experts"]
    with pytest.raises(ValueError, match="holds 'X'"):
        ref.layer_kinds(dict(SHARE, hybrid_override_pattern="MEXEMEME"))
    with pytest.raises(ValueError, match="layers of a pattern of 3"):
        ref.layer_kinds(dict(SHARE, hybrid_override_pattern="MEM"))
    experts = [op for op in share["main"].global_block().ops
               if op.type == "routed_experts"]
    assert all(op.attr("expert_form") == "relu2" and not op.input("WGate")
               and not op.output("Gate") for op in experts)


def test_the_initial_state_is_the_familys(share):
    """``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a step
    in [0.001, 0.1], ``D = 1``, the filter and its bias uniform in +-1 /
    sqrt(taps), the Mamba and attention out-projections drawn at 1 /
    sqrt(52) of their base."""
    by_name = dict(zip(share["names"], share["weights"]))
    ops = share["main"].global_block().ops
    cores = [op for op in ops if op.type == "ssd_scan"]
    convs = [op for op in ops if op.type == "causal_conv1d"]
    assert len(cores) == len(convs) == 3
    for core, conv in zip(cores, convs):
        a_log, dt_bias, skip = (by_name[core.input(s)[0]]
                                for s in ("ALog", "DtBias", "D"))
        assert a_log.shape == dt_bias.shape == skip.shape == (8,)
        assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
        step = np.log1p(np.exp(dt_bias))
        assert (step > 0.00099).all() and (step < 0.1001).all()
        assert (skip == 1).all()
        filt, bias = (by_name[conv.input(s)[0]] for s in ("Filter", "Bias"))
        assert filt.shape == (4, 64 + 2 * 64) and bias.shape == (192,)
        assert np.abs(filt).max() <= 0.5 and np.abs(bias).max() <= 0.5
        assert bias.std() > 0.2
    shapes = [w.shape for w in share["weights"]]
    w_in, w_out = share["weights"][2], share["weights"][9]
    assert (w_in.shape, w_out.shape) == ((48, 2 * 64 + 128 + 8), (64, 48))
    assert abs(w_in.std() - 0.3) < 0.02
    assert abs(w_out.std() - 1.5 / 52 ** 0.5) < 0.02
    assert shapes.count((8, 48, 24)) == 3 and shapes.count((8, 24, 48)) == 3


def test_the_bias_moves_by_the_rate_as_the_loads_say(share):
    """After one step every expert layer's bias has moved by +-rate against
    that step's assignments, counted over ALL router outputs."""
    counts = ref.run(SHARE, share["weights"], share["tokens"],
                     share["labels"])[5]
    biases = [(n, w) for n, w in zip(share["names"], share["weights"])
              if n in share["biases_after"]]
    assert len(biases) == len(counts) == 3
    for (name, before), c in zip(biases, counts):
        want = ref.bias_update(before, c, SHARE["bias_update_rate"])
        np.testing.assert_allclose(share["biases_after"][name], want,
                                   rtol=0, atol=1e-7)
        assert np.asarray(c).sum() == T * SHARE["num_experts_per_tok"]


# ------------------------------------------------------------- the shares
def _experts_program(cfg, shares, tokens=64):
    """``shares`` un-gated routed_experts layers and ONE shared expert on one
    input x [1, tokens, hidden]; returns a function of the reference's layer
    dict (all experts) -> ([(out, load) per share], the shared expert's
    out)."""
    hidden, routed = cfg["hidden_size"], cfg["num_experts_routed"]
    held = routed // shares
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, tokens, hidden],
                              append_batch_size=False)
        outs = [fluid.layers.routed_experts(
            x, routed, cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], held_experts=held,
            expert_offset=i * held, scoring_func="sigmoid",
            routed_scaling_factor=cfg["routed_scaling_factor"],
            selection_bias=True, row_buffer_factor=cfg["row_buffer_factor"],
            expert_form="relu2") for i in range(shares)]
        shared = fluid.layers.relu2_mlp(
            x, cfg["moe_shared_expert_intermediate_size"])
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]
    assert len(names) == 4 * shares + 2          # no gate matrix anywhere

    def run(x_value, layer):
        for i in range(shares):
            lo, hi = i * held, (i + 1) * held
            for name, value in zip(names[4 * i:4 * i + 4], (
                    layer["router"], layer["bias"], layer["e_up"][lo:hi],
                    layer["e_down"][lo:hi])):
                scope.set(name, jnp.asarray(value))
        for name, key in zip(names[4 * shares:], ("s_up", "s_down")):
            scope.set(name, jnp.asarray(layer[key]))
        got = exe.run(main, feed={"x": x_value}, scope=scope,
                      fetch_list=[v for o in outs for v in o[:2]] + [shared])
        return list(zip(got[0:-1:2], got[1:-1:2])), got[-1]
    return run


def _layer(cfg, seed=0, tokens=64):
    rng = np.random.RandomState(seed)
    h, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_routed"])
    fs = cfg["moe_shared_expert_intermediate_size"]

    def w(*shape):
        return rng.randn(*shape).astype(np.float32) * 0.3
    return rng.randn(1, tokens, h).astype(np.float32), dict(
        router=w(h, e), bias=w(e) * 0.5, e_up=w(e, h, f), e_down=w(e, f, h),
        s_up=w(h, fs), s_down=w(fs, h))


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The configuration's cut at a small size: 16 experts of the router's
    128 -> two ops holding experts 0-7 and 8-15 of 16 (top 4) give parts
    that, with the shared expert (which every chip computes alike) counted
    ONCE, sum to the whole layer as the uncut reference has it."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=24, num_experts_routed=16,
               row_buffer_factor=3.0)
    x, layer = _layer(cfg)
    parts, shared = _experts_program(cfg, shares=2)(x, layer)
    whole, _, load, _, _ = ref.experts(cfg, layer, jnp.asarray(x[0]),
                                       ref._Precision("exact"), None)
    total = sum(np.asarray(o)[0] for o, _ in parts) + np.asarray(shared)[0]
    assert _err(total, whole) < TOLERANCE
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(l) for _, l in parts]), np.asarray(load))
    assert int(np.asarray(load).sum()) == 64 * 4
    # counted twice it is not the layer, and no share alone is
    assert _err(total + np.asarray(shared)[0], whole) > 0.1
    assert _err(np.asarray(parts[0][0])[0], whole) > 0.1


@pytest.mark.parametrize("held,offset", [(16, 0), (8, 8)],
                         ids=["whole", "share"])
def test_ungated_experts_match_the_references_loop_with_gradients(held,
                                                                   offset):
    """``routed_experts(expert_form="relu2")`` forward and its hand-written
    grad (two products and their four gradient products) against
    ``jax.grad`` of the reference's loop over the held experts."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               num_experts_routed=16, n_routed_experts=held,
               expert_offset=offset, row_buffer_factor=4.0)
    x, layer = _layer(cfg, seed=1, tokens=48)
    lo, hi = offset, offset + held
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=list(x.shape),
                               append_batch_size=False)
        xv.stop_gradient = False
        out, _, _ = fluid.layers.routed_experts(
            xv, 16, 4, 16, held_experts=held, expert_offset=offset,
            scoring_func="sigmoid", routed_scaling_factor=2.5,
            selection_bias=True, row_buffer_factor=4.0, expert_form="relu2")
        fluid.backward.append_backward(fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, out)))
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    router, bias, up, down = (p.name for p in
                              main.global_block().all_parameters())
    held_w = dict(router=layer["router"], bias=layer["bias"],
                  e_up=layer["e_up"][lo:hi], e_down=layer["e_down"][lo:hi])
    for name, key in ((router, "router"), (bias, "bias"), (up, "e_up"),
                      (down, "e_down")):
        scope.set(name, jnp.asarray(held_w[key]))
    got = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[
        out.name, "x@GRAD", router + "@GRAD", up + "@GRAD", down + "@GRAD"])

    def plain(x, router, e_up, e_down):
        y = ref.experts(cfg, dict(layer, router=router, e_up=e_up,
                                  e_down=e_down), x[0],
                        ref._Precision("exact"), "shared_expert_left_out")[0]
        return y[None]
    args = [jnp.asarray(v) for v in (x, held_w["router"], held_w["e_up"],
                                     held_w["e_down"])]
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2),
                     argnums=(0, 1, 2, 3))(*args)
    assert _err(got[0], plain(*args)) < TOLERANCE
    for a, b in zip(got[1:], wants):
        assert np.asarray(a).shape == b.shape and _err(a, b) < TOLERANCE


def _relu2_step(tier, x_value, router, width):
    """Un-gated ``routed_experts`` of ``width`` (8 experts, top 2, hidden
    128) and its gradient under ``tier``: Out, ExpertLoad, X@GRAD and the
    parameters' gradients."""
    fluid.set_flags({"kernel_tier": tier})
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 4
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=list(x_value.shape),
                                  append_batch_size=False)
            x.stop_gradient = False
            out, load, _ = fluid.layers.routed_experts(
                x, 8, 2, width, scoring_func="sigmoid",
                row_buffer_factor=2.0, expert_form="relu2")
            pairs = fluid.backward.append_backward(fluid.layers.mean(
                fluid.layers.elementwise_mul(out, out)))
        exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
        exe.run(startup, scope=scope)
        names = [p.name for p in main.global_block().all_parameters()]
        scope.set(names[0], jnp.asarray(router))
        return exe.run(main, feed={"x": x_value}, scope=scope,
                       fetch_list=[out, load, "x@GRAD"]
                       + [g for _, g in pairs])
    finally:
        fluid.set_flags({"kernel_tier": "auto"})


@pytest.mark.parametrize("width,kernels,swapped", [
    (192, True, 1), (256, True, 0), (96, False, 0)],
    ids=["a_tile_and_a_half", "two_tiles", "no_whole_half"])
def test_ungated_experts_reach_the_grouped_kernels_at_half_a_lane_tile(
        width, kernels, swapped):
    """The cell's expert width is 14.5 lane tiles (1856). The
    ``grouped_matmul`` kernels' blocks span a whole width, so a width in
    whole HALVES of a tile is theirs: at 1.5 tiles the un-gated layer runs
    its six grouped products on the kernels (interpreted here), ``WUp``
    [8, 128, 192] handed over swapped, as the chip stores it, and equals
    the ``ragged_dot`` route in ``Out`` and every gradient; at two whole
    tiles both weights go over as stored; at three quarters of a tile it
    falls back, counted, and no orientation is counted."""
    from paddle_tpu.obs.metrics import REGISTRY
    from paddle_tpu.ops import pallas as tier

    rng = np.random.RandomState(2)
    router = rng.randn(128, 8).astype(np.float32) * 0.3
    router[:, 3] = -4.0                      # an expert without a row
    x_value = np.abs(rng.randn(1, 512, 128)).astype(np.float32)

    def interpreted():
        return tier.dispatch_counts().get("grouped_matmul", {}).get(
            "interpret", 0)

    def orientations():
        counter = REGISTRY.get("paddle_tpu_grouped_matmul_weights")
        return [counter.labels(orientation=o).value
                for o in ("stored", "swapped")]
    tier.reset_fallback_counts()
    before, counted = interpreted(), orientations()
    kernel = _relu2_step("pallas", x_value, router, width)
    assert interpreted() == before + (6 if kernels else 0)
    assert ("grouped_matmul" in tier.fallback_counts()) == (not kernels)
    assert [b - a for a, b in zip(counted, orientations())] == (
        [2 - swapped, swapped] if kernels else [0, 0])
    twin = _relu2_step("jnp", x_value, router, width)
    assert int(np.asarray(twin[1])[3]) == 0 and len(kernel) == 6
    for a, b in zip(kernel, twin):
        assert _err(a, b) < 1e-5


# ---------------------------------------------------------------- the core
def _recurrence(x, dt, b, c, a_log, dt_bias, d, heads, groups):
    """The reference's token-by-token recurrence on the op's own inputs."""
    bt, t, _ = x.shape

    def one(x, dt, b, c):
        step = jax.nn.softplus(dt + dt_bias)
        y = ref.ssd_recurrence(
            x.reshape(t, heads, -1), step, -jnp.exp(a_log),
            b.reshape(t, groups, -1), c.reshape(t, groups, -1), d,
            ref._Precision("exact"))
        return y.reshape(t, -1)
    return jax.vmap(one)(x, dt, b, c)


def _core_inputs(t, rate, seed=0, bt=2, heads=8, groups=4, p=8, n=16):
    """Steps of 0.001..0.1 through the inverse softplus, and decays up to
    ``rate`` nats a token at the largest step."""
    rng = np.random.RandomState(seed)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)
    step = np.exp(rng.uniform(np.log(0.001), np.log(0.1), heads))
    a = rng.uniform(1.0, 16.0, heads) * rate / 1.6
    return (rand(bt, t, heads * p), 0.5 * rand(bt, t, heads),
            rand(bt, t, groups * n), rand(bt, t, groups * n),
            jnp.asarray(np.log(a), jnp.float32),
            jnp.asarray(np.log(np.expm1(step)), jnp.float32),
            jnp.asarray(1.0 + 0.3 * rng.randn(heads), jnp.float32))


# (tokens, chunk, the largest decay at a step of 0.1 in nats a token): five
# whole chunks of 32; a length that is no multiple of the chunk (padded
# inside the op); one chunk longer than the sequence; the configuration's own
# chunk of 128 at the fastest decay the initialiser can draw (A = 16 at a
# step of 0.1: e^-205 over a chunk, past float32's smallest number), and
# thirty times that (a head wiped at every token: no exponent of the op is
# ever positive)
@pytest.mark.parametrize("t,chunk,rate", [
    (160, 32, 0.3), (100, 32, 0.3), (40, 64, 0.3), (256, 128, 1.6),
    (256, 128, 50.0)])
def test_the_chunked_core_is_the_recurrence_forward_and_backward(t, chunk,
                                                                 rate):
    args = _core_inputs(t, rate)
    heads, groups = 8, 4
    out, states = ss.ssd_chunked(*args, heads, groups, chunk)
    want = _recurrence(*args, heads, groups)
    assert out.shape == want.shape and bool(jnp.isfinite(out).all())
    assert states.shape == (2, -(-t // chunk), heads, 8, 16)
    assert not np.asarray(states[:, 0]).any()       # from a zero state
    assert _err(out, want) < 1e-5
    dout = jnp.asarray(np.random.RandomState(1).randn(*out.shape),
                       jnp.float32)
    grads = ss.ssd_chunked_bwd(*args, states, dout, heads, groups, chunk)
    wants = jax.grad(
        lambda *a: jnp.sum(_recurrence(*a, heads, groups) * dout),
        argnums=tuple(range(7)))(*args)
    assert len(grads) == 7                 # x, dt, B, C, A_log, dt_bias, D
    for a, b in zip(grads, wants):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all())
        assert _err(a, b) < (1e-4 if rate < 10 else 1e-3)


def test_the_kept_states_are_the_recurrences_at_the_chunk_starts():
    """``States`` [b, chunks, heads, P, N]: what the recurrence holds before
    each chunk's first token."""
    args = _core_inputs(96, 0.3, bt=1)
    x, dt, b, c, a_log, dt_bias, _ = args
    _, states = ss.ssd_chunked(*args, 8, 4, 32)
    step = jax.nn.softplus(dt + dt_bias)[0]
    decay = jnp.exp(-jnp.exp(a_log) * step)
    group = ref.head_groups(8, 4)
    xs, bs = x[0].reshape(96, 8, 8), b[0].reshape(96, 4, 16)
    state = jnp.zeros((8, 8, 16))
    for tok in range(64):
        state = decay[tok][:, None, None] * state + (
            step[tok][:, None] * xs[tok])[:, :, None] * bs[tok][group][
                :, None, :]
        if tok + 1 in (32, 64):
            assert _err(states[0, (tok + 1) // 32], state) < 1e-5


def test_the_core_reports_its_gauge_and_runs_under_its_kernel_span():
    """``paddle_tpu_ssd_scan{kind=}`` as last traced, and the span the
    kernels are counted under: ``jnp/ssd_scan`` on the CPU (and at heads of 8
    anywhere), no Pallas dispatch."""
    from paddle_tpu.core import profiler
    from paddle_tpu.obs.metrics import REGISTRY
    from paddle_tpu.ops import pallas as tier

    feed = {n: np.asarray(v) for n, v in zip(
        ("x", "dt", "b", "c"), _core_inputs(70, 0.3, bt=1)[:4])}
    before = dict(tier.dispatch_counts())
    profiler.enable_profiler()
    try:
        out, grads, params, pgrads = _run_ops(
            lambda v: fluid.layers.ssd_scan(v["x"], v["dt"], v["b"], v["c"],
                                            8, n_groups=4, chunk_size=32),
            feed, list(feed))
        events = profiler.events()
    finally:
        profiler.disable_profiler(sorted_key=None)
    names = [(kind, name) for kind, name, *_ in events]
    assert names.count(("kernel", "jnp/ssd_scan")) == 2    # op and grad op
    assert tier.dispatch_counts() == before
    assert ss._route(feed["x"], feed["b"], 8, 4, 32)[1] == "jnp"
    gauge = {k[0]: c.value for k, c in
             REGISTRY.get("paddle_tpu_ssd_scan").children().items()}
    assert gauge == {"chunk": 32, "chunks": 3, "heads": 8, "state": 8 * 16}
    assert out.shape == (1, 70, 64) and len(params) == len(pgrads) == 3
    assert [g.shape for g in grads] == [feed[n].shape for n in feed]
    assert all(np.isfinite(g).all() and np.abs(g).max() > 0
               for g in list(grads) + list(pgrads))


def _run_ops(build, feed, wanted):
    """Build a small program around ``build(vars) -> out``, take the loss
    ``sum(out * out)``, and fetch ``wanted`` (names)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        data = {n: fluid.layers.data(n, shape=list(v.shape),
                                     append_batch_size=False)
                for n, v in feed.items()}
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
                  + [p.name for p in params]
                  + [p.name + "@GRAD" for p in params])
    n = 1 + len(wanted)
    return got[0], got[1:n], got[n:n + len(params)], got[n + len(params):]


def _parents_causal_conv1d(x, w):
    """``causal_conv1d`` as the parent commit computed it (no bias)."""
    taps = w.shape[0]
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    t = x.shape[1]
    back = jnp.pad(xf, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(back[:, j:j + t] * wf[j] for j in range(taps))
    return jax.nn.silu(y).astype(x.dtype)


def test_the_convolution_takes_a_bias_and_without_one_is_what_it_was():
    """With ``Bias``: the reference's ``short_conv`` and ``jax.grad`` of it,
    the bias's gradient included. Without: the op has no ``Bias`` slot, its
    grad op none either, and what it traces is the parent's formula,
    equation for equation."""
    rng = np.random.RandomState(2)
    x = rng.randn(1, 12, 24).astype(np.float32)
    pr = ref._Precision("exact")
    def uniform():
        return fluid.ParamAttr(
            initializer=fluid.initializer.Uniform(-0.5, 0.5))
    out, (dx,), (w, bias), (dw, db) = _run_ops(
        lambda v: fluid.layers.causal_conv1d(
            v["x"], 4, param_attr=uniform(), bias_attr=uniform()), {"x": x},
        ["x"])
    assert w.shape == (4, 24) and bias.shape == (24,) and bias.any()
    plain = lambda x, w, b: ref.short_conv(       # noqa: E731
        x[0], w, b, pr, None)[None]
    assert _err(out, plain(x, w, bias)) < 1e-6
    # the first token sees only itself, through the LAST tap, and the bias
    assert _err(out[0, 0], jax.nn.silu(x[0, 0] * w[3] + bias)) < 1e-6
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias))
    for a, b in zip((dx, dw, db), wants):
        assert _err(a, b) < 1e-5
    assert _err(out, ref.short_conv(x[0], w, bias, pr,
                                    "conv_bias_left_out")[None]) > 0.05

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=list(x.shape),
                               append_batch_size=False)
        xv.stop_gradient = False
        y = fluid.layers.causal_conv1d(xv, 4, param_attr=uniform())
        fluid.backward.append_backward(fluid.layers.reduce_sum(y))
    ops = {op.type: op for op in main.global_block().ops}
    assert sorted(ops["causal_conv1d"].inputs) == ["Filter", "X"]
    assert sorted(ops["causal_conv1d_grad"].outputs) == ["Filter@GRAD",
                                                         "X@GRAD"]
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    (filt,) = main.global_block().all_parameters()
    from paddle_tpu.ops import linear_attention_ops as la
    for dtype in (jnp.float32, jnp.bfloat16):
        feed = jnp.asarray(x, dtype)
        w = scope.find_var(filt.name)
        # the same traced program, equation for equation: the compiler is
        # handed what the parent handed it
        assert str(jax.make_jaxpr(_parents_causal_conv1d)(feed, w)) == str(
            jax.make_jaxpr(lambda x, w: la._causal_conv1d(None, x, w))(
                feed, w))
    got, = exe.run(main, feed={"x": x}, scope=scope, fetch_list=[y])
    assert _err(got, jax.jit(_parents_causal_conv1d)(jnp.asarray(x),
                                                     w)) < 1e-6


@pytest.mark.parametrize("form", ["heads_then_sigmoid", "gate_first"])
def test_the_gated_norm_in_both_forms(form):
    """Kimi's form (the default: RMSNorm per head, one [d] scale, then
    ``sigmoid(gate)``) and the Mamba form (``silu(gate)`` first, RMSNorm per
    GROUP of channels, a scale a channel), each against its formula and
    ``jax.grad`` of it; the default's op carries no new attribute."""
    rng = np.random.RandomState(3)
    t, groups, d = 12, 4, 16
    x = rng.randn(1, t, groups * d).astype(np.float32)
    gate = rng.randn(1, t, groups * d).astype(np.float32)
    first = form == "gate_first"
    main_ops = []

    def build(v):
        out = fluid.layers.gated_rms_norm(
            v["x"], v["gate"], d, epsilon=1e-5, gate_first=first,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(1.0, 0.3)))
        main_ops.extend(out.block.ops)
        return out
    out, (dx, dg), (scale,), (ds,) = _run_ops(build, {"x": x, "gate": gate},
                                              ["x", "gate"])
    (op,) = [o for o in main_ops if o.type == "gated_rms_norm"]

    def plain(x, gate, scale):
        if first:
            return ref.gated_group_norm(x[0], gate[0], scale, groups, 1e-5,
                                        ref._Precision("exact"))[None]
        xh = x.reshape(1, t, groups, d)
        y = xh * jax.lax.rsqrt(jnp.mean(xh * xh, -1, keepdims=True) + 1e-5)
        return (y * scale).reshape(x.shape) * jax.nn.sigmoid(gate)
    assert scale.shape == ((groups * d,) if first else (d,))
    assert sorted(op.attrs) == (["epsilon", "gate_first", "group_size"]
                                if first else ["epsilon"])
    assert _err(out, plain(x, gate, scale)) < 1e-6
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(gate), jnp.asarray(scale))
    for a, b in zip((dx, dg, ds), wants):
        assert _err(a, b) < 1e-5
    other = ref.gated_group_norm(
        x[0], gate[0], jnp.ones(groups * d), groups, 1e-5,
        ref._Precision("exact"), None if not first else "gate_after_norm")
    assert _err(out / np.tile(scale, groups * d // scale.shape[0]),
                other[None]) > 0.05


def test_attention_at_two_key_value_heads_reads_blocked_groups():
    """4 query heads over 2 key/value heads: head q reads ``q // 2``, as the
    reference has it, and the ``q % 2`` mutation is far off."""
    rng = np.random.RandomState(5)
    t, hidden = 24, 48
    cfg = dict(TINY)
    layer = {n: rng.randn(*s).astype(np.float32) * 0.3 for n, s in (
        ("w_q", (hidden, 64)), ("w_k", (hidden, 32)), ("w_v", (hidden, 32)),
        ("w_o", (64, hidden)))}
    u = rng.randn(t, hidden).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q, k, v = (fluid.layers.data(n, shape=[1, t, w],
                                     append_batch_size=False)
                   for n, w in (("q", 64), ("k", 32), ("v", 32)))
        out = fluid.layers.causal_self_attention(q, k, v, num_heads=4,
                                                 num_kv_heads=2)
    got, = fluid.Executor(mode="jit").run(
        main, feed={n: (u @ layer["w_" + n])[None] for n in "qkv"},
        scope=fluid.Scope(), fetch_list=[out])
    pr = ref._Precision("exact")
    want = ref.attention(cfg, layer, jnp.asarray(u), pr, None)
    assert _err(got[0] @ layer["w_o"], want) < 1e-5
    wrong = ref.attention(cfg, layer, jnp.asarray(u), pr,
                          "kv_head_by_modulo")
    assert _err(got[0] @ layer["w_o"], wrong) > 0.05


def test_the_program_trains_under_amp_near_the_stated_precision():
    """The normal path (``Executor(mode="jit", donate=True, amp=True)``,
    Adam, global-norm clipping): the first logits are near the reference at
    the stated precision, and the loss falls."""
    main, startup, loss, logits, _ = build_nemotron_h_lm(SHARE, T)
    startup.random_seed = main.random_seed = 5
    with fluid.program_guard(main, startup):
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(1.0))
        fluid.optimizer.Adam(3e-3).minimize(loss, startup)
    exe = fluid.Executor(mode="jit", donate=True, amp=True)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    weights = [np.asarray(scope.find_var(p.name))
               for p in main.global_block().all_parameters()]
    tok, lab = _tokens()
    feed = {"tokens": tok, "labels": lab}
    first, lg = exe.run(main, feed=feed, fetch_list=[loss, logits],
                        scope=scope)
    stated = ref.run(SHARE, weights, tok[0, :, 0], lab[0, :, 0],
                     precision="stated")[1]
    rows = np.abs(np.asarray(lg, np.float32)[0] - np.asarray(stated)).max(1)
    assert np.median(rows) < 0.05 * np.abs(stated).max()
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * float(first)


def test_generation_engine_refuses_the_new_op_and_expert_form(tmp_path):
    """No recurrent-state cache: a program with ``ssd_scan``, a short
    convolution or an un-gated ``routed_experts`` is refused by name instead
    of decoded wrongly."""
    from paddle_tpu.serving.generate import GenerationEngine

    def bundle(dirname, mixer):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            tokens = fluid.layers.data("tokens", shape=[1, 8, 1],
                                       dtype="int64",
                                       append_batch_size=False)
            x = fluid.layers.embedding(tokens, size=[32, 16])
            q = fluid.layers.fc(x, size=16, num_flatten_dims=2)
            x = fluid.layers.elementwise_add(
                x, fluid.layers.causal_self_attention(q, q, q, num_heads=2))
            logits = fluid.layers.fc(mixer(x), size=32, num_flatten_dims=2)
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(str(dirname), ["tokens"], [logits],
                                      exe, main, scope=scope)
        return str(dirname)

    with pytest.raises(ValueError, match="ssd_scan.*recurrent-state cache"):
        GenerationEngine(bundle(tmp_path / "ssm", lambda x: (
            fluid.layers.ssd_scan(x, fluid.layers.fc(
                x, size=2, num_flatten_dims=2), x, x, 2, chunk_size=8))))
    with pytest.raises(ValueError, match="causal_conv1d.*last taps"):
        GenerationEngine(bundle(tmp_path / "conv", lambda x: (
            fluid.layers.causal_conv1d(x, 4))))
    with pytest.raises(ValueError, match="expert_form='relu2'"):
        GenerationEngine(bundle(tmp_path / "relu2", lambda x: (
            fluid.layers.routed_experts(x, 4, 2, 8,
                                        expert_form="relu2")[0])))


def test_wrong_shapes_and_names_are_refused_by_name():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, 8, 30], append_batch_size=False)
        dt = fluid.layers.data("dt", shape=[1, 8, 4], append_batch_size=False)
        b = fluid.layers.data("b", shape=[1, 8, 16], append_batch_size=False)
        fluid.layers.ssd_scan(x, dt, b, b, num_heads=4, n_groups=2)
        with pytest.raises(ValueError, match="unknown expert_form 'glu'"):
            fluid.layers.routed_experts(x, 8, 2, 16, expert_form="glu")
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    with pytest.raises(ValueError, match="4 heads in 2 groups do not fit"):
        exe.run(main, feed={"x": np.zeros((1, 8, 30), np.float32),
                            "dt": np.zeros((1, 8, 4), np.float32),
                            "b": np.zeros((1, 8, 16), np.float32)},
                scope=scope, fetch_list=[])

"""Kimi Delta Attention (the gated delta rule in chunks, its convolutions,
decay gate and gated norm), latent attention without positions at two head
sizes, sigmoid routing with a selection bias and its update, the shared
expert and the leading dense block as Fluid ops, against the plain reference
(paddle_tpu/testing/reference/kimi_linear.py) at a tiny size on the CPU:
hidden 64; KDA 4 heads of 16, conv 4, chunks of 16; MLA 4 heads of 24 + 8 /
16, latent 16; 32 experts top 4 (8 held in the share), 1 shared; one dense +
three sparse blocks in the order KDA, KDA, MLA, KDA; 40 tokens (two chunks
and a half), seeded random weights and a seeded non-zero selection bias."""

import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import linear_attention_ops as la
from paddle_tpu.testing.models import build_kimi_linear_lm, build_mellum2_lm
from paddle_tpu.testing.reference import kimi_linear as ref

T = 40
TINY = dict(
    hidden_size=64, rms_norm_eps=1e-5, num_hidden_layers=4,
    first_k_dense_replace=1,
    linear_attn_config=dict(kda_layers=[1, 2, 4], full_attn_layers=[3],
                            head_dim=16, num_heads=4,
                            short_conv_kernel_size=4),
    kda_chunk_size=16, num_attention_heads=4, q_lora_rank=None,
    kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
    mla_use_nope=True, intermediate_size=96, moe_intermediate_size=32,
    num_experts_routed=32, num_experts=32, expert_offset=0,
    num_experts_per_token=4, num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    vocab_size=96, init_std=0.3, selection_bias_init_std=0.1,
    bias_update_rate=0.001, balance_loss_coef=1e-4, row_buffer_factor=2.0)
# the chip's share: experts 8..15 of 32
SHARE = dict(TINY, num_experts=8, expert_offset=8, row_buffer_factor=4.0)
TOLERANCE = 1e-4            # float32 on the CPU: roundings only
N_PARAMS = 1 + 3 * 17 + 7 + 3 + 3 * 8 + 2


def _tokens(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64),
            rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64))


def _system(cfg, seed=3):
    """One step's loss, logits, loads and gradients from the program's own
    seeded start-up weights; those weights, in creation order; and the
    selection biases after the step."""
    main, startup, loss, logits, loads = build_kimi_linear_lm(cfg, T)
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


@pytest.mark.parametrize("cfg", [
    TINY, SHARE, dict(SHARE, moe_router_activation_func="softmax",
                      moe_renormalize=False)],
    ids=["whole", "share", "share_softmax_scaled"])
def test_program_matches_reference_loss_logits_and_every_gradient(cfg):
    """Both kinds of attention, both kinds of MLP and both score functions,
    the balance term included; the selection bias has no gradient in the
    program and a zero one in the reference."""
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


def test_the_layer_kinds_are_read_from_the_published_lists(share):
    """Layers are counted from 1; the op types of the program follow
    ``linear_attn_config`` and ``first_k_dense_replace``."""
    assert ref.layer_kinds(SHARE) == [
        ("kda", "dense"), ("kda", "sparse"), ("mla", "sparse"),
        ("kda", "sparse")]
    types = collections.Counter(
        op.type for op in share["main"].global_block().ops)
    assert (types["gated_delta_rule"], types["causal_self_attention"],
            types["routed_experts"], types["expert_bias_update"],
            types["causal_conv1d"], types["kda_decay_gate"],
            types["gated_rms_norm"], types["latent_kv_heads"]) == (
                3, 1, 3, 3, 9, 3, 3, 1)
    assert not types["rotary_embedding"]
    both = dict(SHARE, linear_attn_config=dict(
        SHARE["linear_attn_config"], full_attn_layers=[3, 4]))
    with pytest.raises(ValueError, match="layer 4 is not exactly one"):
        ref.layer_kinds(both)


def test_the_bias_moves_by_the_rate_as_the_loads_say_and_takes_no_gradient(
        share):
    """After one step every sparse layer's bias has moved by +-rate (0
    where an expert got exactly the mean) against that step's assignments,
    counted over ALL router outputs; the optimizer holds no state for it."""
    counts = ref.run(SHARE, share["weights"], share["tokens"],
                     share["labels"])[5]
    biases = [(n, w) for n, w in zip(share["names"], share["weights"])
              if n in share["biases_after"]]
    assert len(biases) == len(counts) == 3
    for (name, before), c in zip(biases, counts):
        want = ref.bias_update(before, c, SHARE["bias_update_rate"])
        np.testing.assert_allclose(share["biases_after"][name], want,
                                   rtol=0, atol=1e-7)
        moved = np.asarray(share["biases_after"][name]) - before
        c = np.asarray(c)
        assert c.sum() == T * SHARE["num_experts_per_token"]
        assert (np.sign(moved) == np.sign(c.mean() - c)).all()
        assert np.abs(moved).max() == pytest.approx(0.001, rel=1e-3)

    main, startup, loss, _, _ = build_kimi_linear_lm(SHARE, T)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)
    for op in main.global_block().ops:
        for name, _ in biases:
            assert name not in op.input_arg_names() or op.type in (
                "routed_experts", "expert_bias_update"), op.type


def _experts_program(cfg, shares, tokens=64):
    """``shares`` routed_experts layers and ONE shared expert on one input
    x [1, tokens, hidden]; returns a function of the reference's layer dict
    (all experts) -> ([(out, load) per share], the shared expert's out)."""
    hidden, routed = cfg["hidden_size"], cfg["num_experts_routed"]
    held = routed // shares
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, tokens, hidden],
                              append_batch_size=False)
        outs = [fluid.layers.routed_experts(
            x, routed, cfg["num_experts_per_token"],
            cfg["moe_intermediate_size"], held_experts=held,
            expert_offset=i * held, scoring_func="sigmoid",
            routed_scaling_factor=cfg["routed_scaling_factor"],
            selection_bias=True, row_buffer_factor=cfg["row_buffer_factor"])
            for i in range(shares)]
        shared = fluid.layers.gated_mlp(x, cfg["moe_intermediate_size"])
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]

    def run(x_value, layer):
        for i in range(shares):
            lo, hi = i * held, (i + 1) * held
            for name, value in zip(names[5 * i:5 * i + 5], (
                    layer["router"], layer["bias"], layer["e_gate"][lo:hi],
                    layer["e_up"][lo:hi], layer["e_down"][lo:hi])):
                scope.set(name, jnp.asarray(value))
        for name, key in zip(names[5 * shares:],
                             ("s_gate", "s_up", "s_down")):
            scope.set(name, jnp.asarray(layer[key]))
        got = exe.run(main, feed={"x": x_value}, scope=scope,
                      fetch_list=[v for o in outs for v in o[:2]] + [shared])
        return list(zip(got[0:-1:2], got[1:-1:2])), got[-1]
    return run


def _layer(cfg, seed=0, tokens=64):
    rng = np.random.RandomState(seed)
    h, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_routed"])

    def w(*shape):
        return rng.randn(*shape).astype(np.float32) * 0.3
    return rng.randn(1, tokens, h).astype(np.float32), dict(
        router=w(h, e), bias=w(e) * 0.5, e_gate=w(e, h, f), e_up=w(e, h, f),
        e_down=w(e, f, h), s_gate=w(h, f), s_up=w(h, f), s_down=w(f, h))


def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """32 experts top 4 as the tiny model routes; four ops holding experts
    0-7, 8-15, 16-23, 24-31 give parts that, with the shared expert (which
    every chip computes alike) counted ONCE, sum to the whole layer."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               row_buffer_factor=3.0)
    x, layer = _layer(cfg)
    parts, shared = _experts_program(cfg, shares=4)(x, layer)
    whole, _, load, _, _ = ref.experts(cfg, layer, jnp.asarray(x[0]),
                                       ref._Precision("exact"), None)
    total = sum(np.asarray(o)[0] for o, _ in parts) + np.asarray(shared)[0]
    assert _err(total, whole) < TOLERANCE
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(l) for _, l in parts]), np.asarray(load))
    assert int(np.asarray(load).sum()) == 64 * 4
    # counted four times it is not the layer, and no share alone is
    assert _err(total + 3 * np.asarray(shared)[0], whole) > 0.1
    assert _err(np.asarray(parts[0][0])[0], whole) > 0.1


def test_the_bias_selects_and_does_not_weigh():
    """A bias of +10 on expert 5 puts it in every token's top 4 whatever
    its score; its weight is still its own score over the four scores' sum,
    times the scaling factor, as the reference has it, and the
    ``bias_in_weights`` mutation is far off."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16)
    x, layer = _layer(cfg)
    layer["bias"] = np.zeros(32, np.float32)
    layer["bias"][5] = 10.0
    (out, load), = _experts_program(cfg, shares=1)(x, layer)[0]
    assert int(np.asarray(load)[5]) == 64

    def routed(mutate):
        y = ref.experts(cfg, layer, jnp.asarray(x[0]),
                        ref._Precision("exact"), mutate)[0]
        return np.asarray(y) - np.asarray(ref.gated_mlp(
            jnp.asarray(x[0]), layer["s_gate"], layer["s_up"],
            layer["s_down"], ref._Precision("exact")))
    assert _err(np.asarray(out)[0], routed(None)) < TOLERANCE
    assert _err(np.asarray(out)[0], routed("bias_in_weights")) > 0.1


# ---------------------------------------------------------------- the core
def _recurrence(q, k, v, g, beta, heads):
    """The reference's token-by-token recurrence on [b, T, heads * d]
    arrays, queries and keys normalised as the op does."""
    b, t, e = q.shape
    d = e // heads

    def one(q, k, v, g, beta):
        qh, kh, gh = (x.reshape(t, heads, d) for x in (q, k, g))
        o = ref.delta_rule(ref._l2(qh) * d ** -0.5, ref._l2(kh),
                           v.reshape(t, heads, -1), gh, beta,
                           ref._Precision("exact"), None)
        return o.reshape(t, -1)
    return jax.vmap(one)(q, k, v, g, beta)


def _core_inputs(t, rate, seed=0, b=2, heads=3, d=16, dv=8):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(b, t, heads * d), jnp.float32)
            for _ in range(2))
    return (q, k, jnp.asarray(rng.randn(b, t, heads * dv), jnp.float32),
            -rate * jnp.asarray(rng.rand(b, t, heads * d), jnp.float32),
            jnp.asarray(rng.rand(b, t, heads), jnp.float32)), heads


# (tokens, the largest decay in nats a token): five whole chunks of 32; a
# length that is no multiple of the chunk (padded inside the op); the
# strongest assumed decay (A = 16 at a step of 0.1) over 64-token chunks,
# where exp(G_i) * exp(-G_j) would overflow; thirty times that (a channel
# wiped at every token: no exponent of the op is ever positive); and 16
# heads, whose chunk terms are built (and checkpointed) ``la.GROUP`` at a time
@pytest.mark.parametrize("t,rate,chunk,heads", [
    (160, 0.3, 32, 3), (100, 0.3, 32, 3), (128, 1.6, 64, 3),
    (128, 50.0, 64, 3), (70, 0.5, 32, 16)])
def test_the_chunked_core_is_the_recurrence_forward_and_backward(t, rate,
                                                                 chunk, heads):
    args, heads = _core_inputs(t, rate, heads=heads)
    assert heads % la.GROUP == 0 or heads < la.GROUP
    scale = 16 ** -0.5
    out, states = la.chunked_delta_rule(*args, heads, chunk, scale)
    want = _recurrence(*args, heads)
    assert out.shape == want.shape and bool(jnp.isfinite(out).all())
    assert states.shape == (2, -(-t // chunk), heads, 16, 8)
    assert _err(out, want) < (1e-5 if rate < 10 else 1e-4)
    dout = jnp.asarray(np.random.RandomState(1).randn(*out.shape),
                       jnp.float32)
    grads = la.chunked_delta_rule_bwd(*args, states, dout, heads, chunk,
                                      scale)
    wants = jax.grad(lambda *a: jnp.sum(_recurrence(*a, heads) * dout),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(grads, wants):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all())
        assert _err(a, b) < (1e-4 if rate < 10 else 1e-3)


def test_the_strongest_assumed_decay_overflows_the_naive_factoring():
    """What the sub-blocks are for: at 1.6 nats a token, exp(-G_j) at a
    64-token chunk's end is exp(102), past float32."""
    (_, _, _, g, _), _ = _core_inputs(128, 1.6)
    cum = jnp.cumsum(jnp.full((64,), -1.6, jnp.float32))
    assert not bool(jnp.isfinite(jnp.exp(-cum)).all())
    assert float(jnp.min(g)) < -1.5


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
    return tier.dispatch_counts().get("delta_rule", {}).get("interpret", 0)


def _kernel_inputs(t, rate, beta=(0.0, 1.0), dtype=jnp.float32, seed=0,
                   heads=2):
    """The core's inputs at the kernels' shape: heads of 128 (two are one
    group of the kernels, whose systems are inverted as one), step sizes
    uniform in ``beta``, the largest decay ``rate`` nats a token."""
    rng = np.random.RandomState(seed)
    (q, k, v, g, _), heads = _core_inputs(t, rate, seed, b=1, heads=heads,
                                          d=128, dv=128)
    lo, hi = beta
    step = jnp.asarray(lo + (hi - lo) * rng.rand(1, t, heads), jnp.float32)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, step.astype(dtype))


# (tokens, the largest decay in nats a token, the step sizes' range, heads):
# whole chunks of 64; a length that is no multiple of 64 (padded around the
# kernels) at three heads (no pair: each head a group of its own); the
# strongest assumed decay and thirty times it (no exponent of
# the kernels is ever positive); steps near 0 (nothing is written) and near
# 1 (every key's old value is replaced: the triangular system at its
# strongest)
@pytest.mark.parametrize("t,rate,beta,heads", [
    (192, 0.3, (0.0, 1.0), 2), (100, 0.3, (0.0, 1.0), 3),
    (128, 1.6, (0.0, 1.0), 2), (128, 50.0, (0.0, 1.0), 2),
    (128, 0.3, (0.0, 0.02), 2), (128, 0.3, (0.98, 1.0), 2)])
def test_the_kernels_are_the_recurrence_and_the_twin(kernel_tier, t, rate,
                                                     beta, heads):
    """``delta_rule_fwd`` / ``delta_rule_bwd`` interpreted on the CPU, through
    the op's own dispatch: ``Out``, the kept states and all five gradients
    against the token-by-token recurrence AND against the jnp twin."""
    args, scale = _kernel_inputs(t, rate, beta, heads=heads), 128 ** -0.5
    dout = jnp.asarray(np.random.RandomState(1).randn(1, t, heads * 128),
                       jnp.float32)
    before = _interpreted(kernel_tier)
    out, states = la.chunked_delta_rule(*args, heads, 64, scale)
    grads = la.chunked_delta_rule_bwd(*args, states, dout, heads, 64, scale)
    assert _interpreted(kernel_tier) == before + 2
    assert kernel_tier.fallback_counts() == {}

    loose = rate > 10
    want = _recurrence(*args, heads)
    assert out.shape == want.shape and states.shape == (
        1, -(-t // 64), heads, 128, 128)
    assert _err(out, want) < (1e-4 if loose else 1e-5)
    wants = jax.grad(lambda *a: jnp.sum(_recurrence(*a, heads) * dout),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(grads, wants):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all())
        assert _err(a, b) < (1e-3 if loose else 1e-4)

    twin_out, twin_states = la.chunked_delta_rule_jnp(*args, heads, 64, scale)
    twin_grads = la.chunked_delta_rule_bwd_jnp(*args, twin_states, dout,
                                               heads, 64, scale)
    assert _err(out, twin_out) < (1e-4 if loose else 1e-5)
    assert _err(states, twin_states) < (1e-3 if loose else 1e-5)
    for a, b in zip(grads, twin_grads):
        assert _err(a, b) < (1e-3 if loose else 1e-4)


def test_the_kernels_take_bfloat16_as_the_twin_does(kernel_tier):
    """bfloat16 q, k, v and steps (the AMP types), float32 log-decays: the
    kernels round where the twin rounds (the four products with the state
    and with U), so the two agree far inside bfloat16's own error against
    the float32 core, and the gradients leave in their inputs' types."""
    t, heads, scale = 128, 2, 128 ** -0.5
    args = _kernel_inputs(t, 0.3, dtype=jnp.bfloat16)
    dout = jnp.asarray(np.random.RandomState(1).randn(1, t, heads * 128),
                       jnp.bfloat16)
    out, states = la.chunked_delta_rule(*args, heads, 64, scale)
    grads = la.chunked_delta_rule_bwd(*args, states, dout, heads, 64, scale)
    twin_out, twin_states = la.chunked_delta_rule_jnp(*args, heads, 64, scale)
    twin_grads = la.chunked_delta_rule_bwd_jnp(*args, twin_states, dout,
                                               heads, 64, scale)
    exact = [x.astype(jnp.float32) for x in args]
    exact_out, exact_states = la.chunked_delta_rule_jnp(*exact, heads, 64,
                                                        scale)
    exact_grads = la.chunked_delta_rule_bwd_jnp(
        *exact, exact_states, dout.astype(jnp.float32), heads, 64, scale)
    assert out.dtype == jnp.bfloat16 and states.dtype == jnp.float32
    assert _err(out, twin_out) < 4e-3 and _err(states, twin_states) < 1e-3
    for x, a, b, c in zip(args, grads, twin_grads, exact_grads):
        assert a.dtype == (jnp.float32 if x is args[3] else jnp.bfloat16)
        # no further from the float32 core than the twin is, and near it
        assert _err(a, c) < max(1.5 * _err(b, c), 8e-3)
        assert _err(a, b) < 1.2e-2


def test_the_op_in_a_program_runs_the_kernels_and_fills_every_grad_slot(
        kernel_tier):
    """``gated_delta_rule`` and its grad op through the executor under
    ``kernel_tier=pallas``: one dispatch a direction, every ``@GRAD`` slot
    equal to the same program on the twin."""
    t, heads = 100, 2
    names = ("q", "k", "v", "g", "beta")
    feed = {n: np.asarray(x)
            for n, x in zip(names, _kernel_inputs(t, 0.5, seed=5))}

    def run():
        return _run_ops(lambda v: fluid.layers.gated_delta_rule(
            *(v[n] for n in names), heads, chunk_size=64), feed, names)
    before = _interpreted(kernel_tier)
    out, grads, _, _ = run()
    assert _interpreted(kernel_tier) == before + 2
    assert kernel_tier.fallback_counts() == {}
    fluid.set_flags({"kernel_tier": "jnp"})
    twin_out, twin_grads, _, _ = run()
    assert _interpreted(kernel_tier) == before + 2
    assert out.shape == (1, t, heads * 128) and _err(out, twin_out) < 1e-5
    for n, a, b in zip(names, grads, twin_grads):
        assert a.shape == feed[n].shape and _err(a, b) < 1e-4


def test_heads_the_kernels_do_not_take_run_the_twin_and_are_counted(
        kernel_tier):
    """Heads of 16 x 8 under ``kernel_tier=pallas``: the predicate reads the
    shapes, the twin runs to the bit, and the ``delta_rule`` fallback counter
    moves once a direction; no kernel is dispatched."""
    args, heads = _core_inputs(70, 0.5)
    scale = 16 ** -0.5
    dout = jnp.ones((2, 70, heads * 8), jnp.float32)
    before = _interpreted(kernel_tier)
    out, states = la.chunked_delta_rule(*args, heads, 32, scale)
    assert kernel_tier.fallback_counts() == {"delta_rule": 1}
    grads = la.chunked_delta_rule_bwd(*args, states, dout, heads, 32, scale)
    assert kernel_tier.fallback_counts() == {"delta_rule": 2}
    assert _interpreted(kernel_tier) == before
    twin_out, twin_states = la.chunked_delta_rule_jnp(*args, heads, 32, scale)
    assert np.array_equal(out, twin_out)
    assert np.array_equal(states, twin_states)
    for a, b in zip(grads, la.chunked_delta_rule_bwd_jnp(
            *args, twin_states, dout, heads, 32, scale)):
        assert np.array_equal(a, b)


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


def test_the_small_ops_match_their_formulas_with_gradients():
    """``causal_conv1d`` (+ SiLU), ``kda_decay_gate`` and ``gated_rms_norm``
    against the reference's own functions and ``jax.grad`` of them, the
    parameters' gradients included; the gate's parameters are initialised
    as the family has them (A in [1, 16], a step in [0.001, 0.1])."""
    rng = np.random.RandomState(2)
    t, heads, d = 12, 4, 16
    pr = ref._Precision("exact")
    x = rng.randn(1, t, heads * d).astype(np.float32)
    gate = rng.randn(1, t, heads * d).astype(np.float32)

    out, (dx,), (w,), (dw,) = _run_ops(
        lambda v: fluid.layers.causal_conv1d(
            v["x"], 4, param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(0.0, 0.5))),
        {"x": x}, ["x"])
    plain = lambda x, w: ref.short_conv(x[0], w, pr, None)[None]  # noqa: E731
    assert w.shape == (4, heads * d)
    assert _err(out, plain(x, w)) < 1e-6
    # the first token sees only itself, through the LAST tap
    assert _err(out[0, 0], jax.nn.silu(x[0, 0] * w[3])) < 1e-6
    gx, gw = jax.grad(lambda x, w: jnp.sum(plain(x, w) ** 2),
                      argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    assert _err(dx, gx) < 1e-5 and _err(dw, gw) < 1e-5

    out, (dx,), (a_log, dt_bias), (da, db) = _run_ops(
        lambda v: fluid.layers.kda_decay_gate(v["x"], heads), {"x": x},
        ["x"])
    assert a_log.shape == (heads,) and dt_bias.shape == (heads * d,)
    assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
    step = np.log1p(np.exp(dt_bias))
    assert (step > 0.00099).all() and (step < 0.1001).all()

    def plain(x, a_log, dt_bias):                           # noqa: E306
        s = jax.nn.softplus(x + dt_bias).reshape(1, t, heads, d)
        return (-jnp.exp(a_log)[:, None] * s).reshape(1, t, -1)
    assert out.dtype == np.float32 and (out < 0).all()
    assert _err(out, plain(x, a_log, dt_bias)) < 1e-6
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(a_log), jnp.asarray(dt_bias))
    for a, b in zip((dx, da, db), wants):
        assert _err(a, b) < 1e-5

    out, (dx, dg), (scale,), (ds,) = _run_ops(
        lambda v: fluid.layers.gated_rms_norm(
            v["x"], v["gate"], d, epsilon=1e-5,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(1.0, 0.3))),
        {"x": x, "gate": gate}, ["x", "gate"])

    def plain(x, gate, scale):                              # noqa: E306
        xh = x.reshape(1, t, heads, d)
        y = xh * jax.lax.rsqrt(jnp.mean(xh * xh, -1, keepdims=True) + 1e-5)
        return (y * scale).reshape(x.shape) * jax.nn.sigmoid(gate)
    assert scale.shape == (d,)
    assert _err(out, plain(x, gate, scale)) < 1e-6
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(gate), jnp.asarray(scale))
    for a, b in zip((dx, dg, ds), wants):
        assert _err(a, b) < 1e-5


def test_latent_heads_share_one_key_and_its_gradient_is_the_heads_sum():
    heads, nope, rope, dv, t = 4, 24, 8, 16, 10
    rng = np.random.RandomState(4)
    feed = {"kv": rng.randn(1, t, heads * (nope + dv)).astype(np.float32),
            "kr": rng.randn(1, t, rope).astype(np.float32)}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        kv = fluid.layers.data("kv", shape=list(feed["kv"].shape),
                               append_batch_size=False)
        kr = fluid.layers.data("kr", shape=list(feed["kr"].shape),
                               append_batch_size=False)
        kv.stop_gradient = kr.stop_gradient = False
        k, v = fluid.layers.latent_kv_heads(kv, kr, heads, nope)
        assert k.shape == (1, t, heads * (nope + rope))
        assert v.shape == (1, t, heads * dv)
        loss = fluid.layers.elementwise_add(
            fluid.layers.reduce_sum(fluid.layers.elementwise_mul(k, k)),
            fluid.layers.reduce_sum(fluid.layers.elementwise_mul(v, v)))
        fluid.backward.append_backward(loss)
    got = fluid.Executor(mode="jit").run(
        main, feed=feed, scope=fluid.Scope(),
        fetch_list=[k, v, "kv@GRAD", "kr@GRAD"])

    def plain(kv, kr):
        kvh = kv[0].reshape(t, heads, nope + dv)
        k = jnp.concatenate(
            [kvh[..., :nope], jnp.broadcast_to(kr[0][:, None, :],
                                               (t, heads, rope))], -1)
        return k.reshape(1, t, -1), kvh[..., nope:].reshape(1, t, -1)
    args = [jnp.asarray(feed[n]) for n in ("kv", "kr")]
    grads = jax.grad(lambda *a: sum(jnp.sum(x * x) for x in plain(*a)),
                     argnums=(0, 1))(*args)
    for a, b in zip(got, list(plain(*args)) + list(grads)):
        assert np.asarray(a).shape == b.shape and _err(a, b) < 1e-5
    # every head's last 8 key coordinates are the one shared key
    k = np.asarray(got[0]).reshape(t, heads, nope + rope)
    assert all((k[:, i, nope:] == feed["kr"][0]).all() for i in range(heads))


def _attention(q, k, v, heads, kv_heads=None):
    """(out, dq, dk, dv) of ``causal_self_attention`` under the loss
    ``mean(out^2)``, through the op and its grad op (the jnp twin here)."""
    feed = {"q": q, "k": k, "v": v}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv, kv, vv = (fluid.layers.data(n, shape=list(feed[n].shape),
                                        append_batch_size=False)
                      for n in "qkv")
        for var in (qv, kv, vv):
            var.stop_gradient = False
        out = fluid.layers.causal_self_attention(
            qv, kv, vv, num_heads=heads, num_kv_heads=kv_heads)
        assert out.shape == (q.shape[0], q.shape[1],
                             v.shape[-1] // (kv_heads or heads) * heads)
        fluid.backward.append_backward(fluid.layers.mean(
            fluid.layers.elementwise_mul(out, out)))
    return fluid.Executor(mode="jit").run(
        main, feed=feed, scope=fluid.Scope(),
        fetch_list=[out.name, "q@GRAD", "k@GRAD", "v@GRAD"])


def _plain_attention(q, k, v, heads):
    """Softmax attention by the definition: scores scaled by the QUERY
    heads' size, a [T, T] score matrix."""
    b, t, _ = q.shape
    qh, kh, vh = (x.reshape(b, t, heads, -1) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                   precision="highest") * qh.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vh,
                      precision="highest").reshape(b, t, -1)


def test_attention_takes_value_heads_of_another_size_and_one_size_as_before():
    """4 heads of 32 for queries and keys and 16 for values, against the
    definition, with all three gradients; and at one head size the op's
    results are what they are with the values padded to the keys' size and
    cut back (the same softmax, so nothing of the old call moved)."""
    rng = np.random.RandomState(5)
    t, heads = 24, 4
    q, k = (rng.randn(2, t, heads * 32).astype(np.float32) for _ in "qk")
    v = rng.randn(2, t, heads * 16).astype(np.float32)
    got = _attention(q, k, v, heads)
    assert got[0].shape == (2, t, heads * 16)
    args = [jnp.asarray(x) for x in (q, k, v)]
    wants = jax.grad(lambda *a: jnp.mean(_plain_attention(*a, heads) ** 2),
                     argnums=(0, 1, 2))(*args)
    for a, b in zip(got, [_plain_attention(*args, heads)] + list(wants)):
        assert a.shape == b.shape and _err(a, b) < 1e-5
    one = _attention(q, k, q, heads)
    for a, b in zip(one, [_plain_attention(*args[:2], args[0], heads)]):
        assert _err(a, b) < 1e-5
    with pytest.raises(ValueError, match="do not fit"):
        _attention(q, k, v[..., :-1], heads)


def test_wrong_shapes_and_names_are_refused_by_name():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, 8, 64], append_batch_size=False)
        with pytest.raises(NotImplementedError, match="rotated slice"):
            fluid.layers.latent_attention(x, 4, 16, 24, 8, 16,
                                          rope_theta=10000.0)
        with pytest.raises(ValueError, match="without selection_bias"):
            fluid.layers.routed_experts(x, 8, 2, 16, bias_update_rate=0.1)
        fluid.layers.routed_experts(x, 8, 2, 16, scoring_func="tanh")
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    with pytest.raises(ValueError, match="scoring_func 'tanh'"):
        exe.run(main, feed={"x": np.zeros((1, 8, 64), np.float32)},
                scope=scope, fetch_list=[])
    with pytest.raises(ValueError, match="mla_use_nope"):
        build_kimi_linear_lm(dict(TINY, mla_use_nope=False), T)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data("q", shape=[1, 8, 30], append_batch_size=False)
        b = fluid.layers.data("b", shape=[1, 8, 4], append_batch_size=False)
        fluid.layers.gated_delta_rule(q, q, q, q, b, num_heads=4)
    with pytest.raises(ValueError, match="4 heads do not fit"):
        fluid.Executor(mode="jit").run(
            main, feed={"q": np.zeros((1, 8, 30), np.float32),
                        "b": np.zeros((1, 8, 4), np.float32)},
            scope=fluid.Scope(), fetch_list=[])


def test_the_low_rank_query_of_latent_attention_has_its_norm():
    """``q_lora_rank``: W_qa, the latent's norm and W_qb before W_kva (no
    cell runs it; the layer is one more ``fc`` and ``rms_norm``)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, 8, 64], append_batch_size=False)
        out = fluid.layers.latent_attention(x, 4, 16, 24, 8, 16,
                                            q_lora_rank=12)
    assert out.shape == (1, 8, 64)
    shapes = [tuple(p.shape) for p in main.global_block().all_parameters()]
    assert shapes == [(64, 12), (12,), (12, 128), (64, 24), (16,),
                      (16, 160), (64, 64)]


# the Mellum2 cell's program at a tiny size, the chip's share with the
# router's task gradient off, forward + backward + Adam under AMP: how often
# each primitive occurred in its jaxpr at the parent of this PR (PR 32's
# tree, before ``routed_experts`` had score functions, a bias and a scaling
# factor and ``causal_self_attention`` a second head size)
CELL5_PRIMITIVES = {
    "abs": 1, "add": 496, "and": 38, "broadcast_in_dim": 520,
    "concatenate": 40, "convert_element_type": 424, "cos": 8, "cumsum": 12,
    "div": 181, "dot_general": 107, "dynamic_slice": 8, "eq": 17, "exp": 14,
    "gather": 46, "ge": 28, "gt": 4, "iota": 125, "is_finite": 1,
    "jit": 165, "le": 8, "log": 5, "logistic": 8, "lt": 137, "lt_to": 8,
    "max": 29, "min": 12, "mul": 682, "ne": 48, "neg": 25,
    "ragged_dot_general": 36, "reduce_max": 9, "reduce_sum": 99, "rem": 24,
    "reshape": 382, "rsqrt": 18, "scan": 8, "scatter": 32,
    "scatter-add": 17, "select_n": 220, "sign": 49, "sin": 8, "slice": 12,
    "sort": 4, "sqrt": 86, "squeeze": 20, "stop_gradient": 5, "sub": 219,
    "top_k": 4, "transpose": 90}


def _primitives(cfg, length):
    from paddle_tpu.obs.perf import program_jaxpr

    main, startup, loss, _, _ = build_mellum2_lm(cfg, length)
    with fluid.program_guard(main, startup):
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    tok = np.zeros((1, length, 1), np.int64)
    jaxpr = program_jaxpr(main, {"tokens": tok, "labels": tok}, [loss],
                          executor=exe, scope=scope)

    def count(j, into):
        for eqn in j.eqns:
            into[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub, into)
        return into
    return main, count(jaxpr.jaxpr, collections.Counter())


def test_a_program_built_with_cell_5s_attributes_is_unchanged():
    """``routed_experts`` and ``causal_self_attention`` under their defaults
    lower to the computation they were: the ops carry no new attribute or
    input, and every primitive occurs in the step's jaxpr as often as it
    did."""
    from test_mellum2_ops import SHARE as MELLUM2_SHARE

    main, now = _primitives(dict(MELLUM2_SHARE, router_task_gradient=False),
                            32)
    for op in main.global_block().ops:
        if op.type.startswith("routed_experts"):
            assert "SelectBias" not in op.inputs
            assert not {"scoring_func", "routed_scaling_factor"} \
                & set(op.attrs)
    assert dict(now) == CELL5_PRIMITIVES


def test_trains_under_amp_near_the_stated_precision_and_the_biases_move():
    """Executor(amp=True): bfloat16 products, float32 islands. The first
    step agrees with the reference at the stated precision far better than
    a wrong piece would, Adam with global-norm clipping brings the loss
    down, and after 12 steps every bias has moved by at most 12 rates."""
    main, startup, loss, logits, _ = build_kimi_linear_lm(SHARE, T)
    # a seed at which no token's top 4 flips at a near-tie between the two:
    # at this size one flipped token is a fifth of the logits' range
    startup.random_seed = main.random_seed = 13
    with fluid.program_guard(main, startup):
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(1.0))
        fluid.optimizer.Adam(learning_rate=3e-3).minimize(loss, startup)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    weights = [np.asarray(scope.find_var(p.name)) for p in params]
    tok, lab = _tokens(5)
    feed = {"tokens": tok, "labels": lab}
    first, lg = exe.run(main, feed=feed, fetch_list=[loss, logits],
                        scope=scope)
    want, stated = ref.run(SHARE, weights, tok[0, :, 0], lab[0, :, 0],
                           precision="stated")[:2]
    assert abs(float(first) - float(want)) < 0.02 * float(want)
    rows = np.abs(np.asarray(lg, np.float32)[0] - np.asarray(stated)).max(1)
    assert np.median(rows) < 0.05 * np.abs(stated).max()
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < 0.7 * float(first)
    for p, before in zip(params, weights):
        if not p.trainable:
            moved = np.abs(np.asarray(scope.find_var(p.name)) - before)
            assert 0 < moved.max() <= 13 * 0.001 + 1e-6

"""rms_norm, rotary_embedding, grouped-query window/full causal attention and
routed_experts as Fluid ops, against the plain reference
(paddle_tpu/testing/reference/mellum2.py) at a tiny size on the CPU: hidden
64, 4 query / 2 key-value heads of 16, 8 experts top 2, window 8, 32 tokens,
YaRN with an original length of 16, seeded random weights."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.testing.models import build_mellum2_lm
from paddle_tpu.testing.reference import mellum2 as ref

T = 32
TINY = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=8, rms_norm_eps=1e-6, num_experts_routed=8,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, vocab_size=96, balance_loss_coef=0.001,
    expert_offset=0, init_std=0.3, row_buffer_factor=2.0,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}})
# the chip's share: experts 4..7 of 8
SHARE = dict(TINY, num_experts=4, expert_offset=4, row_buffer_factor=4.0)
TOLERANCE = 1e-4            # float32 on the CPU: roundings only


def _tokens(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64),
            rng.randint(0, TINY["vocab_size"], (1, T, 1)).astype(np.int64))


def _system(cfg, amp=False, seed=3):
    """One step's loss, logits, loads and gradients from the program's own
    seeded start-up weights; also those weights, in creation order."""
    main, startup, loss, logits, loads = build_mellum2_lm(cfg, T)
    startup.random_seed = main.random_seed = seed
    pairs = fluid.backward.append_backward(loss)
    exe, scope = fluid.Executor(mode="jit", amp=amp), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]
    weights = [np.asarray(scope.find_var(n)) for n in names]
    tok, lab = _tokens()
    out = exe.run(main, feed={"tokens": tok, "labels": lab},
                  fetch_list=[loss, logits] + loads + [g for _, g in pairs],
                  scope=scope)
    n = len(loads)
    grads = dict(zip([p.name for p, _ in pairs], out[2 + n:]))
    return dict(loss=float(out[0]), logits=np.asarray(out[1])[0],
                loads=[np.asarray(x) for x in out[2:2 + n]],
                grads=[grads[name] for name in names], weights=weights,
                tokens=tok[0, :, 0], labels=lab[0, :, 0])


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.fixture(scope="module")
def share():
    return _system(SHARE)


@pytest.mark.parametrize("cfg", [TINY, SHARE], ids=["whole", "share"])
def test_program_matches_reference_loss_logits_and_every_gradient(cfg):
    got = _system(cfg)
    loss, logits, loads, _, grads = ref.run(
        cfg, got["weights"], got["tokens"], got["labels"], with_grads=True)
    assert abs(got["loss"] - float(loss)) < TOLERANCE * float(loss)
    assert _err(got["logits"], logits) < TOLERANCE
    for a, b in zip(got["loads"], loads):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert len(got["grads"]) == len(grads) == 3 + 10 * 4
    for i, (a, b) in enumerate(zip(got["grads"], grads)):
        assert a.shape == b.shape and _err(a, b) < TOLERANCE, i


@pytest.mark.parametrize("mutation", ref.MUTATIONS)
def test_a_mutated_reference_fails_the_same_tolerance(share, mutation):
    """Each piece of the mathematics is visible at the tolerance: the
    reference with the piece broken is further from the system than it
    allows. A softmax over the top k alone IS the renormalised top k of the
    softmax over all, so that mutation can only show where the published
    ``norm_topk_prob`` is switched off: there the system keeps the
    softmax's own weights and the mutation renormalises."""
    cfg, got = SHARE, share
    if mutation == "softmax_over_topk":
        same = ref.run(SHARE, share["weights"], share["tokens"],
                       share["labels"], mutate=mutation)[1]
        assert _err(share["logits"], same) < TOLERANCE
        cfg = dict(SHARE, norm_topk_prob=False)
        got = _system(cfg)
        true = ref.run(cfg, got["weights"], got["tokens"], got["labels"])[1]
        assert _err(got["logits"], true) < TOLERANCE
    logits = ref.run(cfg, got["weights"], got["tokens"], got["labels"],
                     mutate=mutation)[1]
    assert _err(got["logits"], logits) > 100 * TOLERANCE, mutation


def _experts_program(cfg, shares, tokens=64):
    """``shares`` routed_experts layers on one input x [1, tokens, hidden];
    returns a function (x, router, w_gate, w_up, w_down of all experts) ->
    [(out, load) per share], each share holding its slice."""
    hidden, routed = cfg["hidden_size"], cfg["num_experts_routed"]
    held = routed // shares
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, tokens, hidden],
                              append_batch_size=False)
        outs = [fluid.layers.routed_experts(
            x, routed, cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], held_experts=held,
            expert_offset=i * held,
            row_buffer_factor=cfg["row_buffer_factor"])
            for i in range(shares)]
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]

    def run(x_value, router, w_gate, w_up, w_down):
        for i in range(shares):
            lo, hi = i * held, (i + 1) * held
            for name, value in zip(names[4 * i:4 * i + 4], (
                    router, w_gate[lo:hi], w_up[lo:hi], w_down[lo:hi])):
                scope.set(name, jnp.asarray(value))
        got = exe.run(main, feed={"x": x_value},
                      fetch_list=[v for o in outs for v in o[:2]],
                      scope=scope)
        return list(zip(got[0::2], got[1::2]))
    return run


def _expert_weights(cfg, seed=0, tokens=64):
    rng = np.random.RandomState(seed)
    h, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts_routed"])
    return (rng.randn(1, tokens, h).astype(np.float32),
            rng.randn(h, e).astype(np.float32) * 0.3,
            rng.randn(e, h, f).astype(np.float32) * 0.3,
            rng.randn(e, h, f).astype(np.float32) * 0.3,
            rng.randn(e, f, h).astype(np.float32) * 0.3)


def _reference_layer(cfg, x, router, w_gate, w_up, w_down):
    layer = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}
    y, _, load, _ = ref.experts(dict(cfg, expert_offset=0), layer,
                                jnp.asarray(x[0]), ref._Precision("exact"),
                                None)
    return np.asarray(y), np.asarray(load)


def test_eight_shares_add_up_to_the_uncut_layer():
    """64 experts top 8 as the model routes; eight ops holding experts
    0-7, 8-15, ..., 56-63 give parts that sum to the whole layer."""
    cfg = dict(TINY, num_experts_routed=64, num_experts_per_tok=8,
               hidden_size=32, moe_intermediate_size=16,
               row_buffer_factor=3.0)
    args = _expert_weights(cfg)
    parts = _experts_program(cfg, shares=8)(*args)
    whole, load = _reference_layer(cfg, *args)
    assert _err(sum(np.asarray(o)[0] for o, _ in parts), whole) < TOLERANCE
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(l) for _, l in parts]), load)
    assert load.sum() == 64 * 8            # every assignment is someone's
    # no single share is the whole: the sum is what the test is about
    assert _err(np.asarray(parts[0][0])[0], whole) > 0.1


def test_a_rigged_router_drops_no_row_and_an_overflow_is_loud():
    """Every token's first choice is expert 5: its group is 64 rows, eight
    times an even share, and none is dropped. With two experts held and
    every token routed to both, the rows pass the buffer (twice the
    expectation): the output is NaN, never a silent drop."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16)
    x, router, w_gate, w_up, w_down = _expert_weights(cfg)
    x = np.abs(x)
    router = router * 0.01
    router[:, 5] = 1.0                      # x > 0: expert 5 wins everywhere
    args = (x, router, w_gate, w_up, w_down)
    (out, load), = _experts_program(cfg, shares=1)(*args)
    whole, want = _reference_layer(cfg, *args)
    assert int(np.asarray(load)[5]) == 64 == int(want[5])
    assert _err(np.asarray(out)[0], whole) < TOLERANCE
    # 512 tokens, experts 0 and 1 held, every token routed to both: 1024
    # rows against a buffer of 2 x the expectation of 256
    x, router, w_gate, w_up, w_down = _expert_weights(cfg, tokens=512)
    router = router * 0.01
    router[:, 0], router[:, 1] = 1.0, 0.9
    parts = _experts_program(cfg, shares=4, tokens=512)(
        np.abs(x), router, w_gate, w_up, w_down)
    assert np.asarray(parts[0][1]).tolist() == [512, 512]
    assert np.isnan(np.asarray(parts[0][0])).all()
    assert np.isfinite(np.asarray(parts[1][0])).all()      # holds no row
    assert not np.asarray(parts[1][0]).any()


# (T, window, query heads, key/value heads, query/key head size, value head
# size): the block follows from T (256, 256; then 256 under a window no
# multiple of it and one equal to it, 128 under a window wider than T, one
# block of 128, 512 with whole blocks under the diagonal); the last is latent
# attention's expanded form, as many key/value heads as query heads, queries
# and keys of 192 (the op pads them to 256 lanes) and values of 128
@pytest.mark.parametrize("length,window,heads,kv_heads,d,dv", [
    (256, 0, 4, 2, 128, 128), (256, 100, 4, 2, 128, 128),
    (768, 300, 4, 2, 128, 128), (768, 256, 4, 2, 128, 128),
    (384, 4096, 4, 2, 128, 128), (128, 0, 4, 2, 128, 128),
    (1536, 0, 4, 2, 128, 128), (512, 0, 2, 2, 192, 128)])
def test_attention_kernels_match_the_twin_through_the_op(length, window,
                                                         heads, kv_heads, d,
                                                         dv):
    """kernel_tier=pallas runs the attention family's three kernels in the
    interpreter; outputs, the log-sum-exp and all three gradients match
    the blocked twin."""
    from paddle_tpu.ops.pallas import dispatch_counts

    batch = 2 if length <= 256 else 1
    shapes = {"q": (batch, length, heads * d),
              "k": (batch, length, kv_heads * d),
              "v": (batch, length, kv_heads * dv)}

    def run(tier):
        fluid.set_flags({"kernel_tier": tier})
        try:
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                q, k, v = (fluid.layers.data(n, shape=list(shapes[n]),
                                             append_batch_size=False)
                           for n in "qkv")
                for var in (q, k, v):
                    var.stop_gradient = False
                out = fluid.layers.causal_self_attention(
                    q, k, v, num_heads=heads, num_kv_heads=kv_heads,
                    window=window)
                loss = fluid.layers.mean(fluid.layers.elementwise_mul(
                    out, out))
                fluid.backward.append_backward(loss)
            lse, = main.global_block().ops[0].output("LogSumExp")
            rng = np.random.RandomState(1)
            feed = {n: rng.randn(*shapes[n]).astype(np.float32)
                    for n in "qkv"}
            return fluid.Executor(mode="jit").run(
                main, feed=feed, scope=fluid.Scope(),
                fetch_list=[out.name, lse, "q@GRAD", "k@GRAD", "v@GRAD"])
        finally:
            fluid.set_flags({"kernel_tier": "auto"})

    before = dispatch_counts().get("attention", {}).get("interpret", 0)
    kernel, twin = run("pallas"), run("jnp")
    assert dispatch_counts()["attention"]["interpret"] == before + 2
    kernel[1] = np.asarray(kernel[1])[..., 0]       # lane-replicated
    for a, b in zip(kernel, twin):
        assert _err(a, b) < 1e-5


def test_attention_blocks_gauge_reads_the_schedule_as_last_traced():
    """paddle_tpu_attention_blocks after the op's dispatch at the Mellum2
    cell's length (nothing runs): the full layer, then a window layer."""
    from paddle_tpu.obs.metrics import REGISTRY
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import attention as att

    q = jax.ShapeDtypeStruct((1, 8192, 4 * 128), jnp.bfloat16)
    assert att.kernel_block(8192) == 512
    fluid.set_flags({"kernel_tier": "pallas"})
    try:
        for window, blocks in ((0, [136, 120]), (1024, [45, 3])):
            assert attention_ops._attention_route(q, 4, 1, window) == "pallas"
            gauge = REGISTRY.get("paddle_tpu_attention_blocks")
            assert [int(gauge.labels(window=window, kind=kind).value)
                    for kind in ("scheduled", "skipped")] == blocks
    finally:
        fluid.set_flags({"kernel_tier": "auto"})


def _experts_step(tier, x_value, router, num_experts=8, top_k=2, **layer):
    """``routed_experts`` (width 128) and its gradient under ``tier`` on
    one input and one router: Out, ExpertLoad, X@GRAD and the parameters'
    gradients."""
    fluid.set_flags({"kernel_tier": tier})
    try:
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 4
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=list(x_value.shape),
                                  append_batch_size=False)
            x.stop_gradient = False
            out, load, aux = fluid.layers.routed_experts(
                x, num_experts, top_k, 128,
                **dict(dict(row_buffer_factor=2.0), **layer))
            loss = fluid.layers.elementwise_add(
                fluid.layers.mean(fluid.layers.elementwise_mul(out, out)),
                fluid.layers.mean(aux))
            pairs = fluid.backward.append_backward(loss)
        exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
        exe.run(startup, scope=scope)
        names = [p.name for p in main.global_block().all_parameters()]
        scope.set(names[0], jnp.asarray(router))
        return exe.run(main, feed={"x": x_value}, scope=scope,
                       fetch_list=[out, load, "x@GRAD"]
                       + [g for _, g in pairs])
    finally:
        fluid.set_flags({"kernel_tier": "auto"})


def _interpreted(family):
    from paddle_tpu.ops.pallas import dispatch_counts
    return dispatch_counts().get(family, {}).get("interpret", 0)


def test_grouped_matmul_kernels_match_ragged_dot_through_the_op():
    """kernel_tier=pallas runs the grouped_matmul family's three kernels in
    the interpreter (hidden and width of 128 lanes, 512 tokens, one expert
    without a row): the layer's output and every gradient match the
    ragged_dot route over the same aligned groups."""
    rng = np.random.RandomState(2)
    router = rng.randn(128, 8).astype(np.float32) * 0.3
    router[:, 3] = -1.0
    x_value = np.abs(rng.randn(1, 512, 128)).astype(np.float32)

    before = _interpreted("grouped_matmul")
    kernel = _experts_step("pallas", x_value, router)
    twin = _experts_step("jnp", x_value, router)
    assert _interpreted("grouped_matmul") == before + 9
    assert int(np.asarray(twin[1])[3]) == 0         # an expert with no tile
    for a, b in zip(kernel, twin):
        assert _err(a, b) < 1e-5


def _combine_case(name):
    """(x [1, 256 or 512, 128], router, layer attributes, what the loads
    must read): routings that reach each edge of the combine's walk."""
    rng = np.random.RandomState(5)
    x = np.abs(rng.randn(1, 256, 128)).astype(np.float32)
    share = dict(num_experts=64, top_k=8, held_experts=8, expert_offset=8)
    if name == "share":                     # the cell's geometry: 8 of 64
        router = rng.randn(128, 64).astype(np.float32) * 0.3
        return x, router, share, lambda load: load.sum() > 0
    if name == "whole":                     # held == num_experts, top 2
        router = rng.randn(128, 8).astype(np.float32) * 0.3
        return x, router, {}, lambda load: load.sum() == 512
    if name == "all_eight_and_none":
        # feature 0 is token 0's alone and names the held experts 8..15,
        # feature 1 token 1's and names eight others
        router = rng.randn(128, 64).astype(np.float32) * 0.05
        x[0, :, :2] = 0.0
        x[0, 0, 0] = x[0, 1, 1] = 50.0
        router[0, 8:16], router[1, 24:32] = 1.0, 1.0
        return x, router, dict(share, router_task_gradient=False), None
    if name == "an_expert_without_a_row":
        router = rng.randn(128, 8).astype(np.float32) * 0.3
        router[:, 3] = -1.0
        return x, router, {}, lambda load: load[3] == 0 and load[2] > 0
    if name == "one_row_in_a_last_tile":
        # 257 tokens carry feature 0, which names expert 5; no other token
        # comes near it: its group is a tile and one row
        x = np.abs(rng.randn(1, 512, 128)).astype(np.float32)
        router = rng.randn(128, 8).astype(np.float32) * 0.3
        x[0, :, 0] = 0.0
        x[0, :257, 0] = 50.0
        router[0, 5], router[1:, 5] = 10.0, -1.0
        return x, router, {}, lambda load: load[5] == 257
    assert name == "overflow"
    # 512 tokens, experts 0 and 1 held, every token routed to both: 1024
    # rows against a buffer of 2 x the expectation of 256
    x = np.abs(rng.randn(1, 512, 128)).astype(np.float32)
    router = rng.randn(128, 8).astype(np.float32) * 0.003
    router[:, 0], router[:, 1] = 1.0, 0.9
    return (x, router, dict(held_experts=2),
            lambda load: load.tolist() == [512, 512])


@pytest.mark.parametrize("case", [
    "share", "whole", "all_eight_and_none", "an_expert_without_a_row",
    "one_row_in_a_last_tile", "overflow"])
def test_combine_kernel_matches_the_scatter_add_through_the_op(case):
    """kernel_tier=pallas runs ``moe_combine`` in the interpreter, once in
    the forward and once in the backward: ``Out`` and ``X@GRAD`` (and every
    other gradient) match the route that scatter-adds over the whole
    buffer; an overflow is NaN on both."""
    from paddle_tpu.ops.pallas import fallback_counts

    x_value, router, layer, loads_ok = _combine_case(case)
    before = _interpreted("moe_combine")
    kernel = _experts_step("pallas", x_value, router, **layer)
    assert _interpreted("moe_combine") == before + 2
    assert "moe_combine" not in fallback_counts()
    twin = _experts_step("jnp", x_value, router, **layer)
    assert _interpreted("moe_combine") == before + 2
    load = np.asarray(twin[1])
    np.testing.assert_array_equal(np.asarray(kernel[1]), load)
    if case == "overflow":
        assert loads_ok(load)
        for got in kernel[0], kernel[2], twin[0]:
            assert np.isnan(np.asarray(got)).all()
        return
    if case == "all_eight_and_none":
        # token 0 has a row in every held group, token 1 in none: its
        # output is zeros and its input's gradient the balance term's
        top = np.argsort(-(x_value[0] @ router), axis=-1)[:2, :8]
        assert sorted(top[0]) == list(range(8, 16))
        assert not set(top[1]) & set(range(8, 16))
        assert np.abs(np.asarray(kernel[0])[0, 0]).max() > 0
        assert not np.asarray(kernel[0])[0, 1].any()
    else:
        assert loads_ok(load)
    for a, b in zip(kernel, twin):
        assert np.isfinite(np.asarray(a)).all()
        assert _err(a, b) < 1e-5


def test_router_task_gradient_off_leaves_the_router_to_the_balance_term():
    """A layer that holds a share of the experts can switch the task loss's
    path through the top k's weights off: the experts' gradients stay, the
    router's is the balance term's alone."""
    def grads(task_gradient, use_aux):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[1, 64, 32],
                                  append_batch_size=False)
            out, _, aux = fluid.layers.routed_experts(
                x, 8, 2, 16, held_experts=4, expert_offset=2,
                row_buffer_factor=4.0, router_task_gradient=task_gradient)
            loss = fluid.layers.mean(fluid.layers.elementwise_mul(out, out))
            if use_aux:
                loss = fluid.layers.mean(aux)
            pairs = fluid.backward.append_backward(loss)
        exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
        exe.run(startup, scope=scope)
        feed = {"x": np.random.RandomState(3).randn(1, 64, 32)
                .astype(np.float32)}
        return exe.run(main, feed=feed, scope=scope,
                       fetch_list=[g for _, g in pairs])

    on, off = grads(True, False), grads(False, False)
    assert np.abs(on[0]).max() > 0 and not np.asarray(off[0]).any()
    for a, b in zip(on[1:], off[1:]):               # the experts' weights
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    for a, b in zip(grads(True, True), grads(False, True)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
        assert np.abs(a).max() > 0 or a.ndim == 3   # aux reaches the router


def test_trains_under_amp_and_stays_near_the_stated_precision():
    """Executor(amp=True): bfloat16 products, float32 islands. The first
    step agrees with the reference at the stated precision far better than
    a wrong piece would, and Adam with global-norm clipping brings the loss
    down."""
    main, startup, loss, logits, _ = build_mellum2_lm(TINY, T)
    startup.random_seed = main.random_seed = 11
    with fluid.program_guard(main, startup):
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(1.0))
        fluid.optimizer.Adam(learning_rate=3e-3).minimize(loss, startup)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]
    weights = [np.asarray(scope.find_var(n)) for n in names]
    tok, lab = _tokens(5)
    feed = {"tokens": tok, "labels": lab}
    first, lg = exe.run(main, feed=feed, fetch_list=[loss, logits],
                        scope=scope)
    want, stated, _, _, _ = ref.run(TINY, weights, tok[0, :, 0],
                                    lab[0, :, 0], precision="stated")
    assert abs(float(first) - float(want)) < 0.02 * float(want)
    # rounding noise of four bfloat16 layers at this size (a router's
    # near-tie that rounds the other way moves a row by more): the typical
    # row is within a few percent, a wrong piece is not (the mutations
    # above read 0.2 to 1.0 in float32)
    rows = np.abs(np.asarray(lg, np.float32)[0] - np.asarray(stated)).max(1)
    assert np.median(rows) < 0.05 * np.abs(stated).max()
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < 0.7 * float(first)


def test_generation_engine_refuses_grouped_heads_and_windows(tmp_path):
    """The serving rewrite knows equal heads and full causal attention
    only; a program with the new attributes is refused by name."""
    from paddle_tpu.serving.generate import GenerationEngine

    def bundle(dirname, **attention):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            tokens = fluid.layers.data("tokens", shape=[-1, 1],
                                       dtype="int64")
            positions = fluid.layers.data("positions", shape=[-1, 1],
                                          dtype="int64")
            x = fluid.layers.elementwise_add(
                fluid.layers.embedding(tokens, size=[32, 16]),
                fluid.layers.embedding(positions, size=[64, 16]))
            kv = 16 * attention.get("num_kv_heads", 2) // 2
            q = fluid.layers.fc(x, size=16, num_flatten_dims=2)
            k = fluid.layers.fc(x, size=kv, num_flatten_dims=2)
            v = fluid.layers.fc(x, size=kv, num_flatten_dims=2)
            a = fluid.layers.causal_self_attention(q, k, v, num_heads=2,
                                                   **attention)
            logits = fluid.layers.fc(a, size=32, num_flatten_dims=2)
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(str(dirname), ["tokens", "positions"],
                                      [logits], exe, main, scope=scope)
        return str(dirname)

    with pytest.raises(ValueError, match="num_kv_heads"):
        GenerationEngine(bundle(tmp_path / "gqa", num_kv_heads=1))
    with pytest.raises(ValueError, match="window"):
        GenerationEngine(bundle(tmp_path / "win", window=4))

"""Rotated latent attention (a low-rank query, the rope slice of every head
and the ONE shared key turned over adjacent pairs), the multi-token-
prediction module with its own loss, ``fluid.name_scope`` and the reader of
a module's device time, against the plain reference
(paddle_tpu/testing/reference/joyai_llm_flash.py) at a tiny size on the CPU:
hidden 64; MLA 4 heads of 16 + 8 / 16, query rank 24, latent 16; 32 experts
top 4 (8 held in the share), 1 shared; one dense + two sparse blocks and the
MTP module; 40 tokens, seeded random weights and a seeded non-zero selection
bias."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework
from paddle_tpu.testing.models import build_joyai_llm_flash_lm
from paddle_tpu.testing.reference import joyai_llm_flash as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 40
TINY = dict(
    hidden_size=64, rms_norm_eps=1e-6, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=32000000, rope_interleave=True, intermediate_size=96,
    moe_intermediate_size=32, num_experts_routed=32, n_routed_experts=32,
    expert_offset=0, num_experts_per_tok=4, n_shared_experts=1,
    norm_topk_prob=True, scoring_func="sigmoid", routed_scaling_factor=2.5,
    vocab_size=96, num_nextn_predict_layers=1, mtp_loss_weight=0.3,
    init_std=0.3, selection_bias_init_std=0.1, bias_update_rate=0.001,
    balance_loss_coef=1e-4, row_buffer_factor=2.0)
# the chip's share: experts 8..15 of 32
SHARE = dict(TINY, n_routed_experts=8, expert_offset=8, row_buffer_factor=4.0)
TOLERANCE = 1e-4            # float32 on the CPU: roundings only
# embedding, 3 blocks (norm, 7 MLA, norm, 3 dense or 8 sparse), final norm,
# head; the module: enorm, hnorm, W_eh, a sparse block, its norm
N_PARAMS = 1 + (9 + 3) + 2 * (9 + 8) + 2 + 3 + (9 + 8) + 1


def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, TINY["vocab_size"], T + 2).astype(np.int64)
    return {"tokens": ids[None, :T, None], "labels": ids[None, 1:T + 1, None],
            "mtp_labels": ids[None, 2:, None]}


def _system(cfg, seed=3):
    """One step's loss, both logits and every gradient from the program's
    own seeded start-up weights, and those weights in creation order."""
    main, startup, loss, logits, mtp, loads = build_joyai_llm_flash_lm(
        cfg, T)
    startup.random_seed = main.random_seed = seed
    pairs = fluid.backward.append_backward(loss)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    weights = [np.asarray(scope.find_var(p.name)) for p in params]
    feed = _feeds()
    fetch = [loss, logits] + ([mtp] if mtp is not None else []) + loads
    out = exe.run(main, feed=feed, fetch_list=fetch + [g for _, g in pairs],
                  scope=scope)
    n = len(fetch)
    return dict(loss=float(np.asarray(out[0]).reshape(())),
                logits=np.asarray(out[1])[0],
                mtp=np.asarray(out[2])[0] if mtp is not None else None,
                loads=[np.asarray(x) for x in out[n - len(loads):n]],
                grads=dict(zip([p.name for p, _ in pairs], out[n:])),
                params=params, weights=weights, main=main,
                feed={k: v[0, :, 0] for k, v in feed.items()})


def _err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


def _ref(cfg, got, **kw):
    f = got["feed"]
    return ref.run(cfg, got["weights"], f["tokens"], f["labels"],
                   f["mtp_labels"], **kw)


@pytest.fixture(scope="module")
def share():
    return _system(SHARE)


# ----------------------------------------------------------- the rotation
def _rotary_op(q, k, **attrs):
    """The op's forward and grad lowerings, called directly."""
    from paddle_tpu.ops import attention_ops as att

    class Ctx:
        def __init__(self, env):
            self.env, self.out = env, {}
            self.op = type("Op", (), {"has_attr": lambda _, a: a in attrs})()

        def input(self, slot):
            return self.env[slot]

        def attr(self, name, default=None):
            return attrs.get(name, default)

        def set_output(self, slot, value):
            self.out[slot] = value

    fwd = Ctx({"Q": q, "K": k})
    att.rotary_embedding(fwd)
    return fwd, lambda dq, dk: (lambda c: (att.rotary_embedding_grad(c),
                                           c.out)[1])(
        Ctx({"QOut@GRAD": dq, "KOut@GRAD": dk}))


def _pairs(x, theta, start=0):
    """The pair formula over the whole last axis of x [T, n, d]."""
    t, _, d = x.shape
    w = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = (np.arange(start, start + t)[:, None] * w)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = a * np.cos(ang) - b * np.sin(ang)
    out[..., 1::2] = a * np.sin(ang) + b * np.cos(ang)
    return out


@pytest.mark.parametrize("theta", [10000.0, 3.2e7])
def test_a_rotated_slice_turns_adjacent_pairs_and_leaves_the_rest(theta):
    """``rotary_dim`` 8 of heads of 24, ``interleaved``, K of ONE head of 8
    (``k_head_dim``): each query head's trailing 8 coordinates and the key
    turn by the pair formula, the leading 16 are left exactly; the grad op
    is the op's transpose."""
    rng = np.random.RandomState(0)
    q = rng.randn(1, 12, 3 * 24).astype(np.float32)
    k = rng.randn(1, 12, 8).astype(np.float32)
    attrs = dict(head_dim=24, theta=theta, rotary_dim=8, interleaved=True,
                 k_head_dim=8)
    fwd, grad = _rotary_op(q, k, **attrs)
    qh = q[0].reshape(12, 3, 24)
    want_q = np.concatenate([qh[..., :16], _pairs(qh[..., 16:], theta)], -1)
    np.testing.assert_array_equal(
        np.asarray(fwd.out["QOut"])[0].reshape(12, 3, 24)[..., :16],
        qh[..., :16])
    assert _err(fwd.out["QOut"][0].reshape(12, 3, 24), want_q) < 1e-5
    assert _err(fwd.out["KOut"][0], _pairs(k[0][:, None], theta)[:, 0]) \
        < 1e-5
    dq, dk = rng.randn(*q.shape).astype(np.float32), \
        rng.randn(*k.shape).astype(np.float32)
    back = grad(dq, dk)
    _, vjp = jax.vjp(lambda a, b: tuple(_rotary_op(a, b, **attrs)[0]
                                        .out.values()), q, k)
    want_dq, want_dk = vjp((dq, dk))
    assert _err(back["Q@GRAD"], want_dq) < 1e-5
    assert _err(back["K@GRAD"], want_dk) < 1e-5


def test_adjacent_pairs_score_as_the_familys_deinterleave_and_rotate_half():
    """The family's code permutes a slice to [evens | odds] and turns halves:
    the rotated query and key differ from the pair form by that permutation,
    which they share, so every score is the same."""
    rng = np.random.RandomState(1)
    q = rng.randn(1, 16, 2 * 8).astype(np.float32)
    k = rng.randn(1, 16, 8).astype(np.float32)
    pairs, _ = _rotary_op(q, k, head_dim=8, theta=3.2e7, interleaved=True,
                          k_head_dim=8)
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    qp = q.reshape(1, 16, 2, 8)[..., perm].reshape(q.shape)
    half, _ = _rotary_op(qp, k[..., perm], head_dim=8, theta=3.2e7)
    s_pairs = np.einsum("thd,sd->hts",
                        np.asarray(pairs.out["QOut"])[0].reshape(16, 2, 8),
                        np.asarray(pairs.out["KOut"])[0])
    s_half = np.einsum("thd,sd->hts",
                       np.asarray(half.out["QOut"])[0].reshape(16, 2, 8),
                       np.asarray(half.out["KOut"])[0])
    assert _err(s_half, s_pairs) < 1e-5
    assert _err(np.asarray(half.out["KOut"])[0],
                np.asarray(pairs.out["KOut"])[0]) > 0.1


def _parent_rotation(x, length, head_dim, theta, inverse):
    """``rotary_embedding``'s default rotation as PR 39 wrote it, verbatim
    but for the context: the whole head, ``rotate_half``."""
    from paddle_tpu.ops.attention_ops import rotary_inv_freq

    inv = rotary_inv_freq(head_dim, theta)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    cos, sin = jnp.cos(angles) * 1.0, jnp.sin(angles) * 1.0
    sin = -sin if inverse else sin
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


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_the_default_rotation_is_bit_equal_to_the_parents(dtype):
    """At the default attributes (the Mellum2 cell's) the op and its grad
    give the parent's outputs to the bit, and the op carries none of the
    new attributes."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 20, 4 * 16), dtype)
    k = jnp.asarray(rng.randn(1, 20, 2 * 16), dtype)
    attrs = dict(head_dim=16, theta=500000.0, rope_type="default")
    fwd, grad = _rotary_op(q, k, **attrs)
    back = grad(q, k)
    for got, x, inverse in ((fwd.out["QOut"], q, False),
                            (fwd.out["KOut"], k, False),
                            (back["Q@GRAD"], q, True),
                            (back["K@GRAD"], k, True)):
        want = _parent_rotation(x, 20, 16, 500000.0, inverse)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        a = fluid.layers.data("a", shape=[1, 4, 32], append_batch_size=False)
        fluid.layers.rotary_embedding(a, a, head_dim=16)
    (op,) = main.global_block().ops
    assert not {"rotary_dim", "interleaved", "k_head_dim"} & set(op.attrs)


# The tiny programs of cells 5-7 (their rehearsal tests' sizes) at PR 39:
# sha256 of the ProgramDesc (``to_dict``) and of the train step's jaxpr
# under AMP. Equal hashes mean this PR's edits to ``rotary_embedding``, the
# executor's scopes and the Kimi-Linear builder traced nothing new there. A
# PR that changes one of these programs on purpose writes its new hashes.
CELL_PROGRAMS = {
    "mellum2_12b_ep8.staged_len8192_b1":
        ("test_perf_rehearsal_mellum2", "02ebb3e08a23c6f2",
         "e97741f74d9b01be"),
    "kimi_linear_48b_ep32.staged_len4096_b1":
        ("test_perf_rehearsal_kimi_linear", "af2d75476fd3ca19",
         "cc87e806c0f4002c"),
    "nemotron3_nano_30b_ep16.staged_len4096_b1":
        ("test_perf_rehearsal_nemotron_h", "35840fc5a4036b87",
         "25025d552f13bbf3"),
}


@pytest.mark.parametrize("cell", sorted(CELL_PROGRAMS))
def test_the_other_model_cells_trace_what_they_traced(cell):
    import importlib

    from paddle_tpu.obs.perf import program_jaxpr

    module, desc_hash, jaxpr_hash = CELL_PROGRAMS[cell]
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tests", "perf_harness"))
    try:
        from benchmark import harness
        tiny = importlib.import_module(module)
    finally:
        sys.path.remove(ROOT)
        sys.path.remove(os.path.join(ROOT, "tests", "perf_harness"))
    counters = dict(framework._name_counters)
    framework.reset_unique_name()
    try:
        c = harness.load_cell(harness.load_manifest(), cell, tiny.TINY_CFG,
                              tiny.TINY_TRAFFIC)
        main, startup, loss, probe = c.model.build(c.cfg)
        desc = json.dumps(main.to_dict(), sort_keys=True, default=str)
    finally:
        framework._name_counters.clear()
        framework._name_counters.update(counters)
    exe, scope = fluid.Executor(mode="jit", amp=True), fluid.Scope()
    exe.run(startup, scope=scope)
    tok = np.zeros((1, int(tiny.TINY_TRAFFIC["length"]), 1), np.int64)
    jaxpr = str(program_jaxpr(main, {"tokens": tok, "labels": tok},
                              [loss, probe], executor=exe, scope=scope))
    assert hashlib.sha256(desc.encode()).hexdigest()[:16] == desc_hash
    assert hashlib.sha256(jaxpr.encode()).hexdigest()[:16] == jaxpr_hash
    assert "op_namescope" not in desc


# ------------------------------------------------------- latent attention
def _latent_program(rope_theta, q_lora_rank, heads=4, nope=16, rope=8):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1, T, 64], append_batch_size=False)
        x.stop_gradient = False
        out = fluid.layers.latent_attention(
            x, heads, 16, nope, rope, 16, q_lora_rank=q_lora_rank,
            rope_theta=rope_theta, rope_interleave=True,
            param_attr=fluid.ParamAttr(
                initializer=fluid.initializer.Normal(0.0, 0.3)))
        w = fluid.layers.data("w", shape=[1, T, 64], append_batch_size=False)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, w))
    pairs = fluid.backward.append_backward(loss)
    return main, startup, x, out, pairs


@pytest.mark.parametrize("q_lora_rank", [24, None])
def test_rotated_latent_attention_matches_the_reference_with_gradients(
        q_lora_rank):
    """``latent_attention(rope_theta=, q_lora_rank=)``: forward, the input's
    gradient and every weight's against the reference's ``mla`` (the pair
    formula); the query's low rank runs with its norm, and only one
    ``rotary_embedding`` (and no extra ``split`` / ``concat``) is added."""
    main, startup, x, out, pairs = _latent_program(3.2e7, q_lora_rank)
    startup.random_seed = 5
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    params = main.global_block().all_parameters()
    weights = [np.asarray(scope.find_var(p.name)) for p in params]
    rng = np.random.RandomState(4)
    xv = rng.randn(1, T, 64).astype(np.float32)
    wv = rng.randn(1, T, 64).astype(np.float32)
    got = exe.run(main, feed={"x": xv, "w": wv},
                  fetch_list=[out, fluid.framework.grad_var_name(x.name)]
                  + [g for _, g in pairs], scope=scope)
    types = [op.type for op in main.global_block().ops]
    assert types.count("rotary_embedding") == 1
    assert types.count("split") == 1 and "concat" not in types[:types.index(
        "fill_constant")]
    names = ("w_qa", "q_norm", "w_qb") if q_lora_rank else ("w_qb",)
    cfg = dict(TINY, q_lora_rank=q_lora_rank or 0)

    def f(w, xx):
        layer = dict(zip(names + ref.MLA[3:], w))
        if not q_lora_rank:              # the reference's low rank: none
            layer.update(w_qa=jnp.eye(64), q_norm=None)
        return ref.mla(cfg, layer, xx, ref._Precision("exact"),
                       "q_norm_left_out" if not q_lora_rank else None)

    with jax.default_matmul_precision("highest"):
        want, vjp = jax.vjp(f, [jnp.asarray(a) for a in weights],
                            jnp.asarray(xv[0]))
        dw, dx = vjp(jnp.asarray(wv[0]))
    assert _err(got[0][0], want) < TOLERANCE
    assert _err(got[1][0], dx) < TOLERANCE
    grads = dict(zip([p.name for p, _ in pairs], got[2:]))
    for p, b in zip(params, dw):
        assert _err(grads[p.name], b) < TOLERANCE, p.name


def test_nope_latent_attention_is_what_it_was():
    """Without ``rope_theta`` no rotation is added (the Kimi-Linear cell's
    form), and ``q_lora_rank`` puts W_qa and its norm first."""
    main, _, _, _, _ = _latent_program(None, 12)
    assert "rotary_embedding" not in [op.type
                                      for op in main.global_block().ops]
    assert [tuple(p.shape) for p in main.global_block().all_parameters()] \
        == [(64, 12), (12,), (12, 96), (64, 24), (16,), (16, 128), (64, 64)]


# ------------------------------------------------------- the whole model
@pytest.mark.parametrize("cfg", [TINY, SHARE], ids=["whole", "share"])
def test_program_matches_reference_loss_both_logits_and_every_gradient(cfg):
    """Both losses, both logits, every held expert's rows and every
    gradient; the selection biases have none in the program and a zero one
    in the reference."""
    got = _system(cfg)
    loss, logits, mtp, loads, _, grads, _ = _ref(cfg, got, with_grads=True)
    assert abs(got["loss"] - float(loss)) < TOLERANCE * float(loss)
    assert _err(got["logits"], logits) < TOLERANCE
    assert _err(got["mtp"], mtp) < TOLERANCE
    assert len(loads) == len(got["loads"]) == 3
    for a, b in zip(got["loads"], loads):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert len(got["params"]) == len(grads) == N_PARAMS
    for p, b in zip(got["params"], grads):
        if p.trainable:
            a = got["grads"][p.name]
            assert a.shape == b.shape and _err(a, b) < TOLERANCE, p.name
        else:
            assert p.name not in got["grads"] and not np.asarray(b).any()


def test_the_mtp_loss_reaches_the_embedding_the_head_and_the_main_blocks(
        share):
    """The MTP's part of the gradient (the whole less that at lambda = 0)
    is non-zero in the shared embedding, the shared head and the main
    blocks, and matches the reference's; at lambda = 0 (and no balance
    term) the loss is the main model's own."""
    cfg0 = dict(SHARE, mtp_loss_weight=0.0)
    got0 = _system(cfg0)
    grads0 = _ref(cfg0, got0, with_grads=True)[5]
    full = _ref(SHARE, share, with_grads=True)[5]
    names = [p.name for p in share["params"]]
    names0 = [p.name for p in got0["params"]]
    head = 1 + 12 + 2 * 17 + 1
    assert tuple(share["params"][head].shape) == (64, 96)
    # the embedding, the head, the first (dense) and a sparse main block
    for i in (0, head, 1, 2, 8, 11, 14, 20):
        part_sys = share["grads"][names[i]] - got0["grads"][names0[i]]
        part_ref = np.asarray(full[i]) - np.asarray(grads0[i])
        assert np.abs(part_ref).max() > 1e-4, names[i]
        assert _err(part_sys, part_ref) < 1e-3, names[i]
    plain = dict(cfg0, balance_loss_coef=0.0)
    got = _system(plain)
    mtp_params = 3 + 17 + 1
    main_only = _ref(dict(plain, num_nextn_predict_layers=0),
                     dict(got, weights=got["weights"][:-mtp_params]))
    assert abs(got["loss"] - float(main_only[0])) < 1e-6 * got["loss"]
    assert _err(got["logits"], main_only[1]) < TOLERANCE


@pytest.mark.parametrize("mutation", ref.MUTATIONS)
def test_a_mutated_reference_is_further_than_the_tolerance(share, mutation):
    """Each piece of the mathematics is visible: the reference with the
    piece broken is further from the system, in a logits or the loss, than
    the tolerance allows."""
    loss, logits, mtp = _ref(SHARE, share, mutate=mutation)[:3]
    worst = max(_err(share["logits"], logits), _err(share["mtp"], mtp),
                abs(share["loss"] - float(loss)) / share["loss"])
    assert worst > 10 * TOLERANCE, mutation


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_block():
    """16 experts top 4 as the tiny model routes: two ``routed_experts``
    ops holding experts 0-7 and 8-15 give parts that, with the shared expert
    (which every chip computes alike) counted ONCE, sum to the reference's
    uncut layer; each share's loads are its slice of the whole's."""
    cfg = dict(TINY, hidden_size=32, moe_intermediate_size=16,
               num_experts_routed=16, n_routed_experts=16,
               row_buffer_factor=4.0)
    rng = np.random.RandomState(7)
    h, f = 32, 16
    layer = {"router": rng.randn(h, 16).astype(np.float32) * 0.3,
             "bias": rng.randn(16).astype(np.float32) * 0.1}
    for n in ("e_gate", "e_up"):
        layer[n] = rng.randn(16, h, f).astype(np.float32) * 0.3
    layer["e_down"] = rng.randn(16, f, h).astype(np.float32) * 0.3
    for n, shape in (("s_gate", (h, f)), ("s_up", (h, f)),
                     ("s_down", (f, h))):
        layer[n] = rng.randn(*shape).astype(np.float32) * 0.3
    x = rng.randn(1, T, h).astype(np.float32)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=[1, T, h], append_batch_size=False)
        parts = [fluid.layers.routed_experts(
            xv, num_experts=16, top_k=4, expert_width=f, held_experts=8,
            expert_offset=o, row_buffer_factor=4.0,
            scoring_func="sigmoid", routed_scaling_factor=2.5,
            selection_bias=True)[:2] for o in (0, 8)]
        shared = fluid.layers.gated_mlp(xv, f)
    exe, scope = fluid.Executor(mode="jit"), fluid.Scope()
    exe.run(startup, scope=scope)
    names = [p.name for p in main.global_block().all_parameters()]
    values = []
    for o in (0, 8):
        values += [layer["router"], layer["bias"]] + [
            layer[n][o:o + 8] for n in ("e_gate", "e_up", "e_down")]
    values += [layer["s_gate"], layer["s_up"], layer["s_down"]]
    for n, v in zip(names, values):
        assert tuple(scope.find_var(n).shape) == v.shape, n
        scope.set(n, jnp.asarray(v))
    out = exe.run(main, feed={"x": x},
                  fetch_list=[v for p in parts for v in p] + [shared],
                  scope=scope)
    whole, _, load, _, _ = ref.experts(cfg, layer, jnp.asarray(x[0]),
                                       ref._Precision("exact"), None)
    total = out[0][0] + out[2][0] + out[4][0]
    assert _err(total, whole) < TOLERANCE
    np.testing.assert_array_equal(np.concatenate([out[1], out[3]]),
                                  np.asarray(load))
    assert _err(total + out[4][0], whole) > 0.05     # counted twice
    assert _err(out[0][0] + out[4][0], whole) > 0.05  # one share alone


# ------------------------------------------------------------ name scopes
def test_name_scope_tags_ops_and_their_grad_ops_and_nothing_else():
    from paddle_tpu.core.executor import _analyze_program

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4, 8], append_batch_size=False)
        y = fluid.layers.fc(x, 8, bias_attr=False)
        with fluid.name_scope("mtp"):
            z = fluid.layers.fc(fluid.layers.tanh(y), 8, bias_attr=False)
            with fluid.name_scope("inner"):
                z = fluid.layers.scale(z, scale=2.0)
        loss = fluid.layers.mean(fluid.layers.elementwise_add(y, z))
    fluid.backward.append_backward(loss)
    block = main.global_block()
    scopes = dict(zip([id(op) for op in block.ops],
                      _analyze_program(main).op_scopes(block)))
    tagged = [(scopes[id(op)], op.attrs["op_namescope"]) for op in block.ops
              if "op_namescope" in op.attrs]
    assert tagged == [("fwd/tanh/mtp", "mtp"), ("fwd/mul/mtp", "mtp"),
                      ("fwd/scale/mtp/inner", "mtp/inner"),
                      ("bwd/scale/mtp/inner", "mtp/inner"),
                      ("bwd/mul_grad/mtp", "mtp"),
                      ("bwd/tanh_grad/mtp", "mtp"),
                      # y's two gradients summed where the tagged one lands
                      ("bwd/sum/mtp", "mtp")]
    for op in block.ops:                 # the untagged scopes as they were
        if "op_namescope" not in op.attrs:
            assert scopes[id(op)].count("/") == 1
    with pytest.raises(ValueError, match="one segment"):
        with fluid.name_scope("a/b"):
            pass


def test_the_model_tags_the_module_and_only_the_module(share):
    ops = share["main"].global_block().ops
    tags = {op.attrs.get("op_namescope") for op in ops}
    assert tags == {None, "mtp"}
    mine = [op.type for op in ops if op.attrs.get("op_namescope")]
    assert mine[:5] == ["lookup_table", "rms_norm", "rms_norm", "concat",
                        "mul"]
    assert mine.count("routed_experts") == 1 and "mtp_loss" in mine
    assert mine.count("causal_self_attention_grad") == 1
    untagged = [op.type for op in ops if not op.attrs.get("op_namescope")]
    assert untagged.count("causal_self_attention") == 3


@pytest.mark.parametrize("mtp_factor", [None, 8.0])
def test_the_modules_row_buffer_has_its_own_factor(mtp_factor):
    """``mtp_row_buffer_factor`` sizes the module's routed block alone (its
    routing crowded onto the held experts at one seed on the chip: 2183
    rows over the capacity of 2048 at step 68); the main blocks keep
    ``row_buffer_factor``, and without the key the module does too."""
    cfg = dict(SHARE) if mtp_factor is None \
        else dict(SHARE, mtp_row_buffer_factor=mtp_factor)
    main = build_joyai_llm_flash_lm(cfg, T)[0]
    factors = [(op.attrs.get("op_namescope"), op.attrs["row_buffer_factor"])
               for op in main.global_block().ops
               if op.type == "routed_experts"]
    assert factors == [(None, 4.0), (None, 4.0),
                       ("mtp", mtp_factor or 4.0)]


def test_the_mtp_gauge_is_set_when_the_step_is_traced():
    """``mtp_loss`` = loss + lambda * mean(MTP cross-entropy), and the gauge
    says the depth, the positions and lambda of the program last traced."""
    from paddle_tpu.obs.metrics import REGISTRY

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        loss = fluid.layers.data("loss", shape=[1], append_batch_size=False)
        logits = fluid.layers.data("logits", shape=[1, 6, 5],
                                   append_batch_size=False)
        labels = fluid.layers.data("labels", shape=[1, 6, 1], dtype="int64",
                                   append_batch_size=False)
        total = fluid.layers.mtp_loss(loss, logits, labels, 0.25)
    rng = np.random.RandomState(0)
    lg = rng.randn(1, 6, 5).astype(np.float32)
    lab = rng.randint(0, 5, (1, 6, 1)).astype(np.int64)
    (got,) = fluid.Executor(mode="jit").run(
        main, feed={"loss": np.array([2.0], np.float32), "logits": lg,
                    "labels": lab}, fetch_list=[total])
    ce = -np.take_along_axis(jax.nn.log_softmax(lg, -1), lab, -1).mean()
    assert float(np.asarray(got).reshape(())) == pytest.approx(
        2.0 + 0.25 * ce, rel=1e-6)
    gauge = REGISTRY.get("paddle_tpu_mtp")
    assert gauge.labels(kind="depth").value == 1
    assert gauge.labels(kind="positions").value == 6
    assert gauge.labels(kind="loss_weight").value == pytest.approx(0.25)


def test_a_deeper_module_is_refused_by_name():
    with pytest.raises(ValueError, match="depth above 1"):
        build_joyai_llm_flash_lm(dict(TINY, num_nextn_predict_layers=2), T)


# ------------------------------------------------- the module's device time
def _reader():
    sys.path.insert(0, ROOT)
    try:
        from benchmark import harness
        return harness.load_module(
            os.path.join(ROOT, "benchmark", "readers",
                         "module_device_time.py"), "module_device_time")
    finally:
        sys.path.remove(ROOT)


def test_the_module_reader_sums_self_time_of_the_tagged_operations():
    """Self time in the window of operations whose op_name carries the
    segment after its phase/op-type pair; nested and untagged operations and
    the part outside the window do not count; no tagged operation: None."""
    reader = _reader()
    assert reader.module_of("jit(step_ps1)/fwd/mul/mtp/dot_general:") \
        == "mtp"
    assert reader.module_of("jit(step_ps1)/fwd/mul") is None
    assert reader.module_of("jit(step_ps1)/mul") is None
    events = [
        ("a", 0.0, 1.0, "jit(step_ps1)/fwd/mul/mtp/dot_general"),
        ("b", 1.0, 3.0, "jit(step_ps1)/bwd/while/mtp/body"),
        ("c", 1.5, 2.0, "jit(step_ps1)/fwd/mul/dot_general"),  # nested
        ("d", 3.0, 4.0, "jit(step_ps1)/fwd/rms_norm/x"),
        ("e", 4.0, 9.0, "jit(step_ps1)/opt/adam/mtp/y"),       # cut at 5
    ]
    assert reader.module_seconds(events, 0.5, 5.0, "mtp") \
        == pytest.approx(0.5 + 1.5 + 1.0)
    assert reader.module_seconds(events, 0.5, 5.0, "other") is None


# ------------------------------------------------------------ serving
def _bundle(dirname, mixer):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data("tokens", shape=[1, 8, 1], dtype="int64",
                                   append_batch_size=False)
        x = fluid.layers.embedding(tokens, size=[32, 48])
        x = fluid.layers.elementwise_add(x, mixer(x, tokens))
        logits = fluid.layers.fc(x, size=32, num_flatten_dims=2)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    fluid.io.save_inference_model(str(dirname), ["tokens"], [logits], exe,
                                  main, scope=scope)
    return str(dirname)


def test_generation_engine_refuses_a_rotated_latent_slice(tmp_path):
    """A rotated slice of a latent head is refused by name instead of
    decoded wrongly; the NoPE form (the Kimi-Linear cell's) is taken as
    before."""
    from paddle_tpu.serving.generate import GenerationEngine

    def mla(rope_theta):
        return lambda x, _: fluid.layers.latent_attention(
            x, 2, 8, 16, 8, 16, q_lora_rank=12, rope_theta=rope_theta,
            rope_interleave=True)

    with pytest.raises(ValueError, match="rotary_dim=8.*latent"):
        GenerationEngine(_bundle(tmp_path / "rope", mla(3.2e7)))
    GenerationEngine(_bundle(tmp_path / "nope", mla(None)))


def test_generation_engine_refuses_the_mtp_module(tmp_path):
    """Any op the module's name scope tagged is refused by name."""
    from paddle_tpu.serving.generate import GenerationEngine

    def tagged(x, _):
        with fluid.name_scope("mtp"):
            return fluid.layers.fc(x, size=48, num_flatten_dims=2)

    with pytest.raises(ValueError, match="multi-token-prediction"):
        GenerationEngine(_bundle(tmp_path / "mtp", tagged))

"""Mellum2-12B-A2.5B at its published widths, one chip's share of an 8-way
expert-parallel training job (``config.json``: the cut, the deployment and
what was assumed). The program is the repo's own builder
(``paddle_tpu/testing/models.build_mellum2_lm``: ``fluid.layers`` ->
``optimizer.minimize`` -> ``Executor.run``); the plain reference is
``reference.py`` beside this file (a copy of
``paddle_tpu/testing/reference/mellum2.py``; a test holds the two equal);
FLOPs and bytes are counted from shapes, for the work done here."""

import importlib.util
import os

import jax
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_mellum2", os.path.join(_HERE, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward, global-norm
    clipping and Adam. Batch and length are the feed's. The probe is
    ``sign_projections`` of the logits, computed in the program: at a random
    initialisation the loss is ln(vocab) give or take the logits' variance
    whatever the network computes, and the logits are where its signal
    is."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.testing.models import build_mellum2_lm

    main, startup, loss, logits, _ = build_mellum2_lm(cfg, length=-1,
                                                      batch=-1)
    opt = cfg["optimizer"]
    with fluid.program_guard(main, startup):
        probe = _sign_projections_program(
            fluid.layers, logits, main.global_block().var("tokens"),
            cfg["vocab_size"], int(cfg["probe_projections"]))
        fluid.clip.set_gradient_clip(
            fluid.clip.GradientClipByGlobalNorm(opt["clip_global_norm"]))
        rate, warmup = opt["learning_rate"], int(opt.get("warmup_steps", 0))
        if warmup:          # linear to ``rate`` at ``warmup``, then t^-0.5
            rate = fluid.layers.noam_decay(
                d_model=(rate * warmup ** 0.5) ** -2, warmup_steps=warmup)
        fluid.optimizer.Adam(
            learning_rate=rate, epsilon=opt.get("epsilon", 1e-8)).minimize(
                loss, startup)
    return main, startup, loss, probe


def sign_projections(tokens, logits, n):
    """The probe, [n, vocab]: ``mean_t s_j(t) * logits[t]`` over ALL
    positions t, for j < n, where s_j(t) is +1 or -1 by bit j of the token id
    at t (ids are uniform over the slice, so the bits are fair coins). Each
    entry is a random-sign mean of thousands of logits: its error is the
    root-mean-square error of the logits, which a handful of rows whose
    router near-tie rounded the other way hardly moves, where the largest
    error over single positions is decided by exactly those rows (PERF.md,
    PR 28)."""
    ids = np.asarray(tokens, np.int64).reshape(-1)
    signs = 1.0 - 2.0 * ((ids[None, :] >> np.arange(n)[:, None]) & 1)
    rows = np.asarray(logits, np.float64).reshape(len(ids), -1)
    return signs @ rows / len(ids)


def _sign_projections_program(layers, logits, tokens, vocab, n):
    """``sign_projections`` as Fluid ops, float32 throughout (elementwise
    and reductions: AMP casts none of them): bit j of an id is
    floor(id / 2^j) - 2 floor(id / 2^(j+1))."""
    rows = layers.reshape(layers.cast(logits, "float32"), [-1, vocab])
    halved = [layers.reshape(layers.cast(tokens, "float32"), [-1])]
    for _ in range(n):
        halved.append(layers.floor(layers.scale(halved[-1], scale=0.5)))
    parts = []
    for j in range(n):
        bit = layers.elementwise_sub(halved[j],
                                     layers.scale(halved[j + 1], scale=2.0))
        sign = layers.scale(bit, scale=-2.0, bias=1.0)
        mean = layers.reduce_mean(
            layers.elementwise_mul(rows, sign, axis=0), dim=0)
        parts.append(layers.reshape(mean, [1, vocab]))
    return layers.concat(parts, axis=0)


# ------------------------------------------------------------------ data
def markov_tokens(vocab, seed, index, length, successors):
    """``length + 1`` ids of an order-1 Markov chain over ``vocab`` ids:
    each id has ``len(successors)`` successors taken with those
    probabilities. The j-th successors of all ids are a permutation of the
    ids (drawn from ``seed``, the same table for every ``index``), so every
    id is also the j-th successor of exactly one id: the chain's stationary
    distribution is uniform, and what a model can learn is which ids follow
    which, not how often an id occurs. The walk itself is drawn from
    (``seed``, ``index``)."""
    table_rng = np.random.RandomState([seed, 0])
    table = np.stack([table_rng.permutation(vocab) for _ in successors],
                     axis=1)
    rng = np.random.RandomState([seed, 1, index])
    picks = rng.choice(len(successors), size=length, p=successors)
    ids = np.empty(length + 1, np.int32)
    ids[0] = rng.randint(0, vocab)
    for t, pick in enumerate(picks):
        ids[t + 1] = table[ids[t], pick]
    return ids


def device_batch(cfg, seed, index, batch, params):
    """Batch ``index`` of ``seed``: ``batch`` sequences of
    ``params['length']`` tokens and their next tokens, drawn on the host
    and put on the device once."""
    length = int(params["length"])
    ids = np.stack([markov_tokens(cfg["vocab_size"], seed, index * batch + b,
                                  length, params["successors"])
                    for b in range(batch)])
    return jax.device_put({"tokens": ids[:, :-1, None],
                           "labels": ids[:, 1:, None]})


def batch_counts(feed):
    """(sequences, tokens) of one feed."""
    b, t = feed["tokens"].shape[:2]
    return int(b), int(b * t)


# ------------------------------------------------------- plain reference
def run_reference(cfg, weights, feed):
    """(loss, probes) of one feed from the start-up weights: the exact
    arithmetic's loss and ``logits`` probe (float32, ``highest``), and
    ``logits_as_stated``, the same plain code at the precision the
    configuration states (bfloat16 operands, float32 accumulation). Also
    prints the rows per held expert of the reference's own routing, and how
    many top-k sets differ between its two versions."""
    ref = _reference()
    tokens = np.asarray(feed["tokens"])[..., 0]
    labels = np.asarray(feed["labels"])[..., 0]
    losses, exact, stated = [], [], []
    for tok, lab in zip(tokens, labels):
        loss, logits, loads, tops, _ = ref.run(cfg, weights, tok, lab)
        _, logits_s, _, tops_s, _ = ref.run(
            cfg, weights, tok, lab,
            precision=cfg["reference"]["stated_precision"])
        differ = [int(np.sum(np.any(np.sort(np.asarray(a), -1)
                                    != np.sort(np.asarray(b), -1), axis=-1)))
                  for a, b in zip(tops, tops_s)]
        print(f"reference: rows per held expert, by layer: "
              f"{[np.asarray(l).tolist() for l in loads]}; tokens whose "
              f"top-{cfg['num_experts_per_tok']} set differs between exact "
              f"and stated precision, by layer: {differ} of {len(tok)}")
        losses.append(float(loss))
        exact.append(np.asarray(logits))
        stated.append(np.asarray(logits_s))
    n = int(cfg["probe_projections"])
    return float(np.mean(losses)), {
        "logits": sign_projections(tokens, np.concatenate(exact), n),
        "logits_as_stated": sign_projections(tokens, np.concatenate(stated),
                                             n)}


# ------------------------------------------------------- FLOPs and bytes
def band_pairs(length, window):
    """Visible (query, key) pairs of one head of one sequence."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _layer_pairs(cfg, length):
    return [band_pairs(length, cfg["sliding_window"]
                       if kind == "sliding_attention" else 0)
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def _held_rows(cfg, tokens):
    """Rows the held experts see at their expectation: each token's top k
    falls on a held expert with probability held / routed."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_routed"]


def op_work(cfg, traffic):
    """{"experts" | "attention": {"flops", "bytes"}} of ONE training step,
    forward and backward, summed over the layers held here, counted from
    shapes for the work done HERE and the same whatever implements the op:
    held rows at their expectation, the band's pairs and not T^2, nothing
    recomputed. Bytes are what has to cross HBM once: float32 master
    weights read forward and backward and their gradients written, the
    float32 residual-stream tensors, bfloat16 projections and kept rows."""
    b, t = int(traffic["batch"]), int(traffic["length"])
    tokens = b * t
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    rows = _held_rows(cfg, tokens)
    expert_w = cfg["num_experts"] * 3 * h * f
    router_w = h * cfg["num_experts_routed"]
    experts = {
        # three grouped products over the held rows and the router's, each
        # forward, input gradient and weight gradient: 6 FLOP a MAC
        "flops": layers * 6 * (rows * 3 * h * f + tokens * router_w),
        "bytes": layers * (
            3 * 4 * (expert_w + router_w)      # weights twice, gradients
            + 4 * 4 * tokens * h               # x, out; d out, d x
            + 2 * 2 * 2 * rows * f),           # Gate, Up written and read
    }
    pairs = sum(_layer_pairs(cfg, t)) * b
    qkv = tokens * (heads + 2 * kv) * d
    attention = {
        # QK^T and PV forward, their four gradient products backward (the
        # recomputed QK^T earns nothing): 3 x 4 x d FLOP a pair and head
        "flops": 3 * 4 * d * heads * pairs,
        "bytes": layers * 2 * (
            4 * (tokens * (heads + kv) * d)    # rotary: q, k in and out,
                                               # forward and backward
            + 2 * qkv + 3 * tokens * heads * d  # attention: q, k, v twice,
                                                # out, out again, d out
            + qkv),                            # dq, dk, dv
    }
    return {"experts": experts, "attention": attention}


def train_flops(cfg, feed):
    """Training FLOPs one feed needs here, from shapes: 6 FLOP a
    multiply-accumulate of every product a token meets (the attention
    projections, the router, the held experts' rows at their expectation,
    the head over the vocabulary slice), plus attention's pairs as
    ``op_work`` counts them. The embedding is a gather and its gradient a
    scatter; norms, rotations and the optimizer are elementwise. Nothing
    recomputed is counted."""
    b, t = feed["tokens"].shape[:2]
    tokens = int(b * t)
    h = cfg["hidden_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    work = op_work(cfg, {"batch": int(b), "length": int(t)})
    projections = cfg["num_hidden_layers"] * (2 * h * heads * d
                                              + 2 * h * kv * d)
    return (6 * tokens * (projections + h * cfg["vocab_size"])
            + work["experts"]["flops"] + work["attention"]["flops"])

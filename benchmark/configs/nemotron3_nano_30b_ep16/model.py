"""NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, one chip's share
of a 16-way expert-parallel training job (``config.json``: the cut, the
deployment and what was assumed). The program is the repo's own builder
(``paddle_tpu/testing/models.build_nemotron_h_lm``: ``fluid.layers`` ->
``optimizer.minimize`` -> ``Executor.run``); the plain reference is
``reference.py`` beside this file (a copy of
``paddle_tpu/testing/reference/nemotron_h.py``; a test holds the two equal);
FLOPs and bytes are counted from shapes, for the work done here. The token
streams, the probe and the feed's counts are the Mellum2 configuration's own
functions (``../mellum2_12b_ep8/model.py``): the cells draw the same chain
over their own vocabulary slices and are judged by the same
``sign_projections``."""

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load("benchmark_reference_nemotron_h", "reference.py")


_shared = _load("benchmark_config_mellum2_shared", os.pardir,
                "mellum2_12b_ep8", "model.py")
sign_projections = _shared.sign_projections
markov_tokens = _shared.markov_tokens
device_batch = _shared.device_batch
batch_counts = _shared.batch_counts


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward, global-norm
    clipping and Adam, and each expert layer's selection-bias update. Batch
    and length are the feed's. The probe is ``sign_projections`` of the
    logits, computed in the program (the Mellum2 configuration's, for its
    reasons)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.testing.models import build_nemotron_h_lm

    main, startup, loss, logits, _ = build_nemotron_h_lm(cfg, length=-1,
                                                         batch=-1)
    opt = cfg["optimizer"]
    with fluid.program_guard(main, startup):
        probe = _shared._sign_projections_program(
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


# ------------------------------------------------------- plain reference
def run_reference(cfg, weights, feed):
    """(loss, probes) of one feed from the start-up weights: the exact
    arithmetic's loss and ``logits`` probe (float32, ``highest``; the
    state-space layers as their recurrence), and ``logits_as_stated``, the
    same plain code at the precision the configuration states (bfloat16
    operands, float32 accumulation). Also prints the rows per held expert of
    the reference's own routing, and how many top-k sets differ between its
    two versions."""
    ref = _reference()
    tokens = np.asarray(feed["tokens"])[..., 0]
    labels = np.asarray(feed["labels"])[..., 0]
    losses, exact, stated = [], [], []
    for tok, lab in zip(tokens, labels):
        loss, logits, loads, tops = ref.run(cfg, weights, tok, lab)[:4]
        logits_s, _, tops_s = ref.run(
            cfg, weights, tok, lab,
            precision=cfg["reference"]["stated_precision"])[1:4]
        differ = [int(np.sum(np.any(np.sort(np.asarray(a), -1)
                                    != np.sort(np.asarray(b), -1), axis=-1)))
                  for a, b in zip(tops, tops_s)]
        print(f"reference: rows per held expert, by expert layer: "
              f"{[np.asarray(l).tolist() for l in loads]}; tokens whose "
              f"top-{cfg['num_experts_per_tok']} set differs between exact "
              f"and stated precision, by expert layer: {differ} of "
              f"{len(tok)}")
        losses.append(float(loss))
        exact.append(np.asarray(logits))
        stated.append(np.asarray(logits_s))
    n = int(cfg["probe_projections"])
    return float(np.mean(losses)), {
        "logits": sign_projections(tokens, np.concatenate(exact), n),
        "logits_as_stated": sign_projections(tokens, np.concatenate(stated),
                                             n)}


# ------------------------------------------------------- FLOPs and bytes
def _kinds(cfg):
    """(Mamba-2 layers, attention layers, expert layers) held here."""
    held = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return held.count("M"), held.count("*"), held.count("E")


def _held_rows(cfg, tokens):
    """Rows the held experts see at their expectation: each token's top k
    falls on a held expert with probability held / routed."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["num_experts_routed"]


def op_work(cfg, traffic):
    """{"ssm" | "attention" | "experts": {"flops", "bytes"}} of ONE training
    step, forward and backward, summed over the layers held here, counted
    from shapes for the work done HERE and the same whatever implements the
    op. Bytes are what has to cross HBM once: float32 master weights read
    forward and backward and their gradients written, float32
    residual-stream tensors, bfloat16 projections and kept rows. Nothing
    recomputed is counted.

    ``ssm`` is everything of a Mamba-2 mixer that is no ``mul`` and no plain
    ``rms_norm``. FLOPs: the chunked core at the chunk size C that runs, per
    token and head, as multiply-accumulates forward: ``C B^T`` over the
    lower triangle (C / 2 x N, once a GROUP of heads), its product with the
    chunk's inputs (C / 2 x P), the chunk's contribution to the state and
    the state's to the outputs (N x P each); twice that again backward.
    Bytes: the core reads x, B, C and the raw step (bfloat16) and writes y
    forward, reads them and d y and writes their gradients backward, and
    writes and reads the chunks' float32 states; the convolution over x, B
    and C and the gated norm read their inputs and write their outputs
    forward, and read inputs and output gradients and write input gradients
    backward.

    ``attention``: the core over ``T (T + 1) / 2`` pairs a query head,
    forward and its four gradient products backward; bytes of q, k, v read
    twice, out, out again, d out, and dq, dk, dv (no rotation runs).

    ``experts`` is the routed layer: TWO grouped products over the held
    rows at their expectation and the router (the shared expert is two
    ``mul``)."""
    b, t = int(traffic["batch"]), int(traffic["length"])
    tokens = b * t
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_ssm, n_att, n_exp = _kinds(cfg)

    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    chunk = int(cfg.get("chunk_size", 128))
    inner, bc = heads * p, groups * n
    conv = inner + 2 * bc
    chunks = -(-t // chunk) * b
    macs = chunk // 2 * (n * groups // heads + p) + 2 * n * p
    ssm = {
        "flops": n_ssm * 3 * 2 * tokens * heads * macs,
        "bytes": n_ssm * (
            2 * tokens * (
                # core forward: x, B, C, the step in; y out
                conv + heads + inner
                # core backward: those again, d y, and their gradients
                + conv + heads + inner + conv + heads
                # convolution: x, y forward; x, dy, dx backward
                + 5 * conv
                # gated norm: y, z, out; y, z, d out, dy, dz
                + 8 * inner)
            + 2 * 4 * chunks * heads * p * n),      # states out and in
    }

    q_heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    pairs = b * t * (t + 1) // 2
    qkv = tokens * (q_heads + 2 * kv) * d
    attention = {
        "flops": n_att * 3 * 4 * d * q_heads * pairs,
        "bytes": n_att * 2 * (2 * qkv + 3 * tokens * q_heads * d + qkv),
    }

    rows = _held_rows(cfg, tokens)
    expert_w = cfg["n_routed_experts"] * 2 * h * f
    router_w = h * cfg["num_experts_routed"]
    experts = {
        # two grouped products over the held rows and the router's, each
        # forward, input gradient and weight gradient: 6 FLOP a MAC
        "flops": n_exp * 6 * (rows * 2 * h * f + tokens * router_w),
        "bytes": n_exp * (
            3 * 4 * (expert_w + router_w)      # weights twice, gradients
            + 4 * 4 * tokens * h               # x, out; d out, d x
            + 2 * 2 * rows * f),               # Up written and read
    }
    return {"ssm": ssm, "attention": attention, "experts": experts}


def train_flops(cfg, feed):
    """Training FLOPs one feed needs here, from shapes: 6 FLOP a
    multiply-accumulate of every product a token meets (a Mamba-2 mixer's
    in- and out-projection; attention's four projections; each expert
    layer's shared expert; the head over the vocabulary slice), plus the
    routed experts (held rows at their expectation, the router), attention's
    pairs and the chunked state-space core as ``op_work`` counts them. The
    embedding is a gather and its gradient a scatter; norms, the
    convolution, gates and the optimizer are elementwise. Nothing recomputed
    is counted."""
    b, t = feed["tokens"].shape[:2]
    tokens = int(b * t)
    h = cfg["hidden_size"]
    n_ssm, n_att, n_exp = _kinds(cfg)
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    per_token = (
        n_ssm * (h * (2 * inner + 2 * bc + cfg["mamba_num_heads"])
                 + inner * h)
        + n_att * (h * (heads + 2 * kv) * d + heads * d * h)
        + n_exp * 2 * h * cfg.get("n_shared_experts", 1)
        * cfg["moe_shared_expert_intermediate_size"]
        + h * cfg["vocab_size"])
    work = op_work(cfg, {"batch": int(b), "length": int(t)})
    return (6 * tokens * per_token + work["experts"]["flops"]
            + work["attention"]["flops"] + work["ssm"]["flops"])

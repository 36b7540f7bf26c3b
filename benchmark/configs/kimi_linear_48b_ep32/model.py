"""Kimi-Linear-48B-A3B at its published widths, one chip's share of a 32-way
expert-parallel training job (``config.json``: the cut, the deployment and
what was assumed). The program is the repo's own builder
(``paddle_tpu/testing/models.build_kimi_linear_lm``: ``fluid.layers`` ->
``optimizer.minimize`` -> ``Executor.run``); the plain reference is
``reference.py`` beside this file (a copy of
``paddle_tpu/testing/reference/kimi_linear.py``; a test holds the two
equal); FLOPs and bytes are counted from shapes, for the work done here. The
token streams, the probe and the feed's counts are the Mellum2
configuration's own functions (``../mellum2_12b_ep8/model.py``): the two
cells draw the same chain over their own vocabulary slices and are judged by
the same ``sign_projections``."""

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
    return _load("benchmark_reference_kimi_linear", "reference.py")


_shared = _load("benchmark_config_mellum2_shared", os.pardir,
                "mellum2_12b_ep8", "model.py")
sign_projections = _shared.sign_projections
markov_tokens = _shared.markov_tokens
device_batch = _shared.device_batch
batch_counts = _shared.batch_counts


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward, global-norm
    clipping and Adam, and each sparse layer's selection-bias update. Batch
    and length are the feed's. The probe is ``sign_projections`` of the
    logits, computed in the program (the Mellum2 configuration's, for its
    reasons)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.testing.models import build_kimi_linear_lm

    main, startup, loss, logits, _ = build_kimi_linear_lm(cfg, length=-1,
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
        loss, logits, loads, tops = ref.run(cfg, weights, tok, lab)[:4]
        logits_s, _, tops_s = ref.run(
            cfg, weights, tok, lab,
            precision=cfg["reference"]["stated_precision"])[1:4]
        differ = [int(np.sum(np.any(np.sort(np.asarray(a), -1)
                                    != np.sort(np.asarray(b), -1), axis=-1)))
                  for a, b in zip(tops, tops_s)]
        print(f"reference: rows per held expert, by sparse layer: "
              f"{[np.asarray(l).tolist() for l in loads]}; tokens whose "
              f"top-{cfg['num_experts_per_token']} set differs between exact "
              f"and stated precision, by sparse layer: {differ} of "
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
    """(KDA blocks, latent-attention blocks, dense blocks, sparse blocks)
    held here."""
    lin, n = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    kda = sum(1 for i in lin["kda_layers"] if i <= n)
    dense = min(cfg["first_k_dense_replace"], n)
    return kda, n - kda, dense, n - dense


def _held_rows(cfg, tokens):
    """Rows the held experts see at their expectation: each token's top k
    falls on a held expert with probability held / routed."""
    return tokens * cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["num_experts_routed"]


def op_work(cfg, traffic):
    """{"kda" | "latent_attention" | "experts": {"flops", "bytes"}} of ONE
    training step, forward and backward, summed over the layers held here,
    counted from shapes for the work done HERE and the same whatever
    implements the op. Bytes are what has to cross HBM once: float32 master
    weights read forward and backward and their gradients written, float32
    residual-stream tensors, bfloat16 projections and kept rows. Nothing
    recomputed is counted.

    ``kda`` is everything of a KDA block's attention that is no ``mul`` and
    no plain ``rms_norm``. FLOPs: the chunked core at the chunk size C that
    runs, per token and head, as multiply-accumulates forward: the two
    pairwise-decay matrices' rows (2 C d), the triangular system applied to
    [V | K e^G] (2 C d) and the pseudo-values read by the queries (C d),
    each over the lower triangle (half), and the three products with the
    state (3 d^2); twice that again backward. Bytes: the core reads q, k, v
    (bfloat16), g (float32) and beta and writes o forward, reads them and
    d o and writes their gradients backward, and writes and reads the
    chunks' float32 states; the three convolutions, the decay gate and the
    gated norm read their inputs and write their outputs forward, and read
    inputs and output gradients and write input gradients backward.

    ``latent_attention``: the core over ``T (T + 1) / 2`` pairs at
    ``qk_nope + qk_rope`` (scores) and ``v_head_dim`` (values) a head,
    UNPADDED, forward and its four gradient products backward; bytes of the
    core (q, k, v read twice, out, out again, d out; dq, dk, dv) and of the
    assembly of per-head keys and values from the up-projected latent and
    the one shared key, and their gradients back.

    ``experts`` is the routed layer: the held rows at their expectation and
    the router (the shared expert is three ``mul``)."""
    b, t = int(traffic["batch"]), int(traffic["length"])
    tokens = b * t
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_kda, n_mla, _, sparse = _kinds(cfg)

    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    chunk = int(cfg.get("kda_chunk_size", 64))
    wide = heads * d
    chunks = -(-t // chunk) * b
    kda = {
        "flops": n_kda * 3 * 2 * tokens * heads * d * (5 * chunk // 2
                                                       + 3 * d),
        "bytes": n_kda * (
            tokens * (
                # core forward: q, k, v, o bfloat16; g float32; beta
                4 * 2 * wide + 4 * wide + 2 * heads
                # core backward: those again, d o, and their gradients
                + 4 * 2 * wide + 4 * wide + 2 * heads
                + 3 * 2 * wide + 4 * wide + 2 * heads
                # convolutions: x, y forward; x, dy, dx backward
                + 3 * 5 * 2 * wide
                # decay gate: x in bfloat16, g out float32; x, dg, dx
                + 2 * wide + 4 * wide + 2 * wide + 4 * wide + 2 * wide
                # gated norm: x, gate, y; x, gate, dy, dx, dgate
                + 8 * 2 * wide)
            + 2 * 4 * chunks * heads * d * d),      # states out and in
    }

    mla_heads = cfg["num_attention_heads"]
    qk = mla_heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    v = mla_heads * cfg["v_head_dim"]
    kv = mla_heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    rope = cfg["qk_rope_head_dim"]
    pairs = b * t * (t + 1) // 2
    latent = {
        "flops": n_mla * 3 * 2 * pairs * (qk + v),
        "bytes": n_mla * 2 * tokens * (
            2 * (kv + rope + qk + v)           # assembly: the latent and
                                               # the key in, k and v out;
                                               # their gradients back
            + 2 * (2 * qk + v) + 3 * v         # core: q, k, v twice, out,
                                               # out again, d out
            + 2 * qk + v),                     # dq, dk, dv
    }

    rows = _held_rows(cfg, tokens)
    expert_w = cfg["num_experts"] * 3 * h * f
    router_w = h * cfg["num_experts_routed"]
    experts = {
        # three grouped products over the held rows and the router's, each
        # forward, input gradient and weight gradient: 6 FLOP a MAC
        "flops": sparse * 6 * (rows * 3 * h * f + tokens * router_w),
        "bytes": sparse * (
            3 * 4 * (expert_w + router_w)      # weights twice, gradients
            + 4 * 4 * tokens * h               # x, out; d out, d x
            + 2 * 2 * 2 * rows * f),           # Gate, Up written and read
    }
    return {"kda": kda, "latent_attention": latent, "experts": experts}


def train_flops(cfg, feed):
    """Training FLOPs one feed needs here, from shapes: 6 FLOP a
    multiply-accumulate of every product a token meets (a KDA block's q, k,
    v and output projections, its two low-rank gates and its step size; a
    latent block's four projections; the dense block's MLP; each sparse
    block's shared expert; the head over the vocabulary slice), plus the
    routed experts (held rows at their expectation, the router), latent
    attention's pairs at its unpadded heads and the chunked delta rule as
    ``op_work`` counts them. The embedding is a gather and its gradient a
    scatter; norms, convolutions, gates and the optimizer are elementwise.
    Nothing recomputed is counted."""
    b, t = feed["tokens"].shape[:2]
    tokens = int(b * t)
    h = cfg["hidden_size"]
    n_kda, n_mla, dense, sparse = _kinds(cfg)
    lin = cfg["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    rank = int(cfg.get("kda_gate_rank") or lin["head_dim"])
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    per_token = (
        n_kda * (4 * h * wide + 2 * (h * rank + rank * wide)
                 + h * lin["num_heads"])
        + n_mla * (h * heads * (nope + rope) + h * (cfg["kv_lora_rank"]
                                                    + rope)
                   + cfg["kv_lora_rank"] * heads * (nope + dv)
                   + heads * dv * h)
        + dense * 3 * h * cfg["intermediate_size"]
        + sparse * 3 * h * cfg["num_shared_experts"]
        * cfg["moe_intermediate_size"]
        + h * cfg["vocab_size"])
    work = op_work(cfg, {"batch": int(b), "length": int(t)})
    return (6 * tokens * per_token + work["experts"]["flops"]
            + work["latent_attention"]["flops"] + work["kda"]["flops"])

"""LFM2-24B-A2B at its published widths, one chip's share of an 8-way
expert-parallel training job (``config.json``: the cut, the deployment and
what was assumed). The program is built by
``paddle_tpu/testing/models.build_lfm2_moe_lm`` ( ``fluid.layers`` ->
``optimizer.minimize`` -> ``Executor.run``); the plain reference is
``reference.py`` beside this file (a copy of
``paddle_tpu/testing/reference/lfm2_moe.py``; a test holds the two equal);
FLOPs and bytes are counted from shapes, for the work done here. The token
chain, the feed and the probe are the Mellum2 configuration's own functions
(``../mellum2_12b_ep8/model.py``)."""

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
    return _load("benchmark_reference_lfm2_moe", "reference.py")


_shared = _load("benchmark_config_mellum2_shared", os.pardir,
                "mellum2_12b_ep8", "model.py")
sign_projections = _shared.sign_projections
device_batch = _shared.device_batch
batch_counts = _shared.batch_counts


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward, global-norm
    clipping and Adam, and each routed layer's selection-bias update. Batch
    and length are the feed's. The probe is ``sign_projections`` of the
    logits, computed in the program (the Mellum2 configuration's, for its
    reasons)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.testing.models import build_lfm2_moe_lm

    main, startup, loss, logits, _ = build_lfm2_moe_lm(cfg, length=-1,
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
        _, logits_s, _, tops_s = ref.run(
            cfg, weights, tok, lab,
            precision=cfg["reference"]["stated_precision"])[:4]
        differ = [int(np.sum(np.any(np.sort(np.asarray(a), -1)
                                    != np.sort(np.asarray(b), -1), axis=-1)))
                  for a, b in zip(tops, tops_s)]
        print(f"reference: rows per held expert, by routed layer: "
              f"{[np.asarray(l).tolist() for l in loads]}; tokens whose "
              f"top-{cfg['num_experts_per_tok']} set differs between exact "
              f"and stated precision, by routed layer: {differ} of "
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
    """(mixer, feed-forward part) of each layer held here."""
    return _reference().layer_kinds(cfg)


def _held_rows(cfg, tokens):
    """Rows the held experts see at their expectation: each token's top k
    falls on a held expert with probability held / routed."""
    return tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_routed"]


def op_work(cfg, traffic):
    """{"short_conv" | "attention" | "experts": {"flops", "bytes"}} of ONE
    training step, forward and backward, summed over the layers held here,
    counted from shapes for the work done HERE and the same whatever
    implements the op. Nothing recomputed is counted.

    ``short_conv``: the gated short convolutions (``gated_short_conv`` and
    its grad op), each ONE crossing of HBM each way in bfloat16: forward
    X [T, 3H] read and ``out`` [T, H] written; backward X and ``Out@GRAD``
    read and ``X@GRAD`` [T, 3H] written; the float32 filter read twice and
    its gradient written. FLOPs per channel and token: forward ``B * x``,
    the taps' products and sums, ``C * z`` (2 taps + 1); backward ``dC``,
    ``dz``, the transposed convolution, ``dB``, ``dx`` and the filter's
    products and sums (4 taps + 3). Bytes bound.

    ``attention``: the core over T (T + 1) / 2 pairs at the UNPADDED head
    of 64, QK^T and PV forward and their four gradient products backward,
    and its bytes (q, k, v read twice, out, out again, d out; dq, dk, dv).

    ``experts``: the held rows at their expectation and the router (three
    grouped products and the router's, each forward, input gradient and
    weight gradient), and their bytes as the Mellum2 configuration counts
    them."""
    b, t = int(traffic["batch"]), int(traffic["length"])
    tokens = b * t
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    taps = cfg["conv_L_cache"]
    kinds = _kinds(cfg)
    convs = sum(1 for mixer, _ in kinds if mixer == "conv")
    attentions = len(kinds) - convs
    sparse = sum(1 for _, mlp in kinds if mlp == "sparse")
    short_conv = {
        "flops": convs * tokens * h * (6 * taps + 4),
        "bytes": convs * (2 * tokens * h * (3 + 1 + 3 + 1 + 3)
                          + 3 * 4 * taps * h),
    }
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    pairs = b * t * (t + 1) // 2
    qkv = tokens * (heads + 2 * kv) * d
    attention = {
        "flops": attentions * 3 * 4 * d * heads * pairs,
        "bytes": attentions * 2 * (2 * qkv + 3 * tokens * heads * d + qkv),
    }
    rows = _held_rows(cfg, tokens)
    expert_w = cfg["num_experts"] * 3 * h * f
    router_w = h * cfg["num_experts_routed"]
    experts = {
        "flops": sparse * 6 * (rows * 3 * h * f + tokens * router_w),
        "bytes": sparse * (
            3 * 4 * (expert_w + router_w)      # weights twice, gradients
            + 4 * 4 * tokens * h               # x, out; d out, d x
            + 2 * 2 * 2 * rows * f),           # Gate, Up written and read
    }
    return {"short_conv": short_conv, "attention": attention,
            "experts": experts}


def train_flops(cfg, feed):
    """Training FLOPs one feed needs here, from shapes: 6 FLOP a
    multiply-accumulate of every product a token meets (each conv mixer's
    W_in and W_out, the attention layer's four projections, the dense
    layer's MLP, the head over the vocabulary slice), plus the routed
    experts and attention's pairs as ``op_work`` counts them. The embedding
    is a gather and its gradient a scatter; the gated convolutions, norms,
    rotations and the optimizer are elementwise. Nothing recomputed is
    counted."""
    b, t = feed["tokens"].shape[:2]
    tokens = int(b * t)
    h = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    per_token = h * cfg["vocab_size"]
    for mixer, mlp in _kinds(cfg):
        per_token += 4 * h * h if mixer == "conv" \
            else 2 * h * heads * d + 2 * h * kv * d
        if mlp == "dense":
            per_token += 3 * h * cfg["intermediate_size"]
    work = op_work(cfg, {"batch": int(b), "length": int(t)})
    return (6 * tokens * per_token + work["experts"]["flops"]
            + work["attention"]["flops"])

"""JoyAI-LLM-Flash at its published widths, one chip's share of a 32-way
expert-parallel training job (``config.json``: the cut, the deployment and
what was assumed). The program is the repo's own builder
(``paddle_tpu/testing/models.build_joyai_llm_flash_lm``: ``fluid.layers`` ->
``optimizer.minimize`` -> ``Executor.run``); the plain reference is
``reference.py`` beside this file (a copy of
``paddle_tpu/testing/reference/joyai_llm_flash.py``; a test holds the two
equal); FLOPs and bytes are counted from shapes, for the work done here. The
token chain and the probe are the Mellum2 configuration's own functions
(``../mellum2_12b_ep8/model.py``); the feed adds the tokens two ahead, the
multi-token-prediction module's targets, and the probe is the main logits'
``sign_projections`` followed by the module's."""

import importlib.util
import os

import jax
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference():
    return _load("benchmark_reference_joyai_llm_flash", "reference.py")


_shared = _load("benchmark_config_mellum2_shared", os.pardir,
                "mellum2_12b_ep8", "model.py")
sign_projections = _shared.sign_projections
markov_tokens = _shared.markov_tokens
batch_counts = _shared.batch_counts


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, both losses, backward,
    global-norm clipping and Adam, and each sparse block's selection-bias
    update. Batch and length are the feed's. The probe is
    ``sign_projections`` of the main logits followed by those of the MTP
    module's, computed in the program: ONE vector, as the harness compares
    one."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.testing.models import build_joyai_llm_flash_lm

    main, startup, loss, logits, mtp_logits, _ = build_joyai_llm_flash_lm(
        cfg, length=-1, batch=-1)
    opt = cfg["optimizer"]
    with fluid.program_guard(main, startup):
        tokens = main.global_block().var("tokens")
        n = int(cfg["probe_projections"])
        probe = fluid.layers.concat([
            _shared._sign_projections_program(
                fluid.layers, lg, tokens, cfg["vocab_size"], n)
            for lg in (logits, mtp_logits)], axis=0)
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


# ------------------------------------------------------------------ data
def device_batch(cfg, seed, index, batch, params):
    """Batch ``index`` of ``seed``: ``batch`` sequences of
    ``params['length']`` tokens, their next tokens (``labels``) and the
    tokens two ahead (``mtp_labels``, the MTP module's targets: each chain
    is drawn one token further than the other cells' for them), drawn on
    the host and put on the device once."""
    length = int(params["length"])
    ids = np.stack([markov_tokens(cfg["vocab_size"], seed, index * batch + b,
                                  length + 1, params["successors"])
                    for b in range(batch)])
    return jax.device_put({"tokens": ids[:, :-2, None],
                           "labels": ids[:, 1:-1, None],
                           "mtp_labels": ids[:, 2:, None]})


# ------------------------------------------------------- plain reference
def run_reference(cfg, weights, feed):
    """(loss, probes) of one feed from the start-up weights: the exact
    arithmetic's loss and ``logits`` probe (float32, ``highest``), and
    ``logits_as_stated``, the same plain code at the precision the
    configuration states (bfloat16 operands, float32 accumulation); each
    probe is the main logits' ``sign_projections`` followed by the MTP
    module's. Also prints the rows per held expert of the reference's own
    routing, and how many top-k sets differ between its two versions."""
    ref = _reference()
    tokens, labels, ahead = (np.asarray(feed[k])[..., 0] for k in
                             ("tokens", "labels", "mtp_labels"))
    n = int(cfg["probe_projections"])
    losses, exact, stated = [], [], []
    for tok, lab, two in zip(tokens, labels, ahead):
        loss, logits, mtp, loads, tops = ref.run(cfg, weights, tok, lab,
                                                 two)[:5]
        _, logits_s, mtp_s, _, tops_s = ref.run(
            cfg, weights, tok, lab, two,
            precision=cfg["reference"]["stated_precision"])[:5]
        differ = [int(np.sum(np.any(np.sort(np.asarray(a), -1)
                                    != np.sort(np.asarray(b), -1), axis=-1)))
                  for a, b in zip(tops, tops_s)]
        print(f"reference: rows per held expert, by sparse block (the MTP's "
              f"last): {[np.asarray(l).tolist() for l in loads]}; tokens "
              f"whose top-{cfg['num_experts_per_tok']} set differs between "
              f"exact and stated precision, by sparse block: {differ} of "
              f"{len(tok)}")
        losses.append(float(loss))
        exact.append((np.asarray(logits), np.asarray(mtp)))
        stated.append((np.asarray(logits_s), np.asarray(mtp_s)))

    def probe(pairs):
        return np.concatenate([
            sign_projections(tokens, np.concatenate([p[i] for p in pairs]),
                             n) for i in (0, 1)])

    return float(np.mean(losses)), {"logits": probe(exact),
                                    "logits_as_stated": probe(stated)}


# ------------------------------------------------------- FLOPs and bytes
def _blocks(cfg):
    """(latent-attention blocks, dense blocks, sparse blocks) held here,
    the MTP module's block among the first and the last."""
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    return n + mtp, dense, n - dense + mtp


def _held_rows(cfg, tokens):
    """Rows the held experts see at their expectation: each token's top k
    falls on a held expert with probability held / routed."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["num_experts_routed"]


def _mla_params(cfg):
    """Product parameters of one latent-attention block: W_qa, W_qb, W_kva,
    W_kvb, W_o."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * qr + qr * heads * (nope + rope) + h * (kr + rope)
            + kr * heads * (nope + dv) + heads * dv * h)


def _attention(cfg, b, t, blocks):
    """``mla_rope`` of ``blocks`` latent-attention blocks (see
    ``op_work``)."""
    tokens = b * t
    heads = cfg["num_attention_heads"]
    qk = heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    v = heads * cfg["v_head_dim"]
    kv = heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    rope = cfg["qk_rope_head_dim"]
    turned = (heads + 1) * rope          # every query's slice, the one key
    pairs = b * t * (t + 1) // 2
    return {
        "flops": blocks * (3 * 2 * pairs * (qk + v)
                           # the rotation: 3 FLOP a coordinate each way
                           + 2 * 3 * tokens * turned),
        "bytes": blocks * 2 * tokens * (
            2 * (kv + rope + qk + v)           # assembly: the latent and
                                               # the key in, k and v out;
                                               # their gradients back
            + 2 * (2 * qk + v) + 3 * v         # core: q, k, v twice, out,
                                               # out again, d out
            + 2 * qk + v                       # dq, dk, dv
            + 2 * 2 * turned),                 # the rotated slices in and
                                               # out, each way
    }


def _experts(cfg, tokens, sparse):
    """``experts`` of ``sparse`` routed layers (see ``op_work``)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = _held_rows(cfg, tokens)
    expert_w = cfg["n_routed_experts"] * 3 * h * f
    router_w = h * cfg["num_experts_routed"]
    return {
        # three grouped products over the held rows and the router's, each
        # forward, input gradient and weight gradient: 6 FLOP a MAC
        "flops": sparse * 6 * (rows * 3 * h * f + tokens * router_w),
        "bytes": sparse * (
            3 * 4 * (expert_w + router_w)      # weights twice, gradients
            + 4 * 4 * tokens * h               # x, out; d out, d x
            + 2 * 2 * 2 * rows * f),           # Gate, Up written and read
    }


def op_work(cfg, traffic):
    """{"mla_rope" | "experts" | "mtp": {"flops", "bytes"}} of ONE training
    step, forward and backward, counted from shapes for the work done HERE
    and the same whatever implements the op. Bytes are what has to cross
    HBM once: float32 master weights read forward and backward and their
    gradients written, float32 residual-stream tensors, bfloat16
    projections and kept rows. Nothing recomputed is counted.

    ``mla_rope``: the latent-attention blocks' work that is no ``mul`` and
    no ``rms_norm``, the MTP module's block among them: the core over ``T
    (T + 1) / 2`` pairs at ``qk_nope + qk_rope`` (scores) and ``v_head_dim``
    (values) a head, UNPADDED, forward and its four gradient products
    backward; the rotation of every query head's rope slice and of the ONE
    shared key (3 FLOP a coordinate each way); bytes of the core (q, k, v
    read twice, out, out again, d out; dq, dk, dv), of the assembly of
    per-head keys and values from the up-projected latent and the shared
    key and their gradients back, and of the rotated slices in and out each
    way.

    ``experts`` is the routed layers, the MTP's among them: the held rows
    at their expectation and the router (the shared expert is three
    ``mul``).

    ``mtp`` is the whole module: its products (``W_eh``, its block's five
    latent projections, its shared expert, the SHARED head over the slice:
    6 FLOP a multiply-accumulate), its block's attention and experts as
    above; bytes of those weights (read twice, gradients written), of the
    module's float32 stream in and out each way, and of its logits
    (bfloat16 out, their float32 softmax written and read back)."""
    b, t = int(traffic["batch"]), int(traffic["length"])
    tokens = b * t
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    blocks, _, sparse = _blocks(cfg)
    products = (2 * h * h + _mla_params(cfg)
                + 3 * h * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
                + h * vocab)
    attention, experts = _attention(cfg, b, t, 1), _experts(cfg, tokens, 1)
    mtp = {
        "flops": 6 * tokens * products + attention["flops"]
        + experts["flops"],
        "bytes": 3 * 4 * products + attention["bytes"] + experts["bytes"]
        + 4 * 4 * tokens * h + tokens * vocab * (2 + 2 * 4),
    }
    if not cfg.get("num_nextn_predict_layers", 0):
        mtp = {"flops": 0, "bytes": 0}
    return {"mla_rope": _attention(cfg, b, t, blocks),
            "experts": _experts(cfg, tokens, sparse), "mtp": mtp}


def train_flops(cfg, feed):
    """Training FLOPs one feed needs here, from shapes: 6 FLOP a
    multiply-accumulate of every product a token meets (each latent block's
    five projections, the MTP module's among them; the dense block's MLP;
    each sparse block's shared expert; the MTP's ``W_eh``; the head over the
    vocabulary slice twice, once for each prediction), plus the routed
    experts (held rows at their expectation, the router), latent
    attention's pairs at its unpadded heads and the rotations as ``op_work``
    counts them. The embedding is a gather and its gradient a scatter;
    norms and the optimizer are elementwise. Nothing recomputed is
    counted."""
    b, t = feed["tokens"].shape[:2]
    tokens = int(b * t)
    h = cfg["hidden_size"]
    blocks, dense, sparse = _blocks(cfg)
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    per_token = (
        blocks * _mla_params(cfg)
        + dense * 3 * h * cfg["intermediate_size"]
        + sparse * 3 * h * cfg["n_shared_experts"]
        * cfg["moe_intermediate_size"]
        + mtp * 2 * h * h
        + (1 + mtp) * h * cfg["vocab_size"])
    work = op_work(cfg, {"batch": int(b), "length": int(t)})
    return (6 * tokens * per_token + work["experts"]["flops"]
            + work["mla_rope"]["flops"])

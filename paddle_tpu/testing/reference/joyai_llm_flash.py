"""Plain reference of the JoyAI-LLM-Flash block stack (jdopensource/
JoyAI-LLM-Flash, ``model_type: joyai_llm_flash``, 48B-A2.7B), written from
its published ``config.json``: forward, both losses and gradients in
straightforward ``jax.numpy``. No kernels, no sorting, no rotation matrix:
the rotation is the pair formula, the experts are dense, and attention holds
a block of query rows' scores at a time.

    every block, d = qk_nope_head_dim + qk_rope_head_dim (128 + 64):
      a = RMSNorm(x)
      q = RMSNorm(a W_qa) W_qb                   heads x [q_nope | q_rope]
      [c_kv | k_r] = a W_kva                     kv_lora_rank | ONE key slice
      RMSNorm(c_kv) W_kvb                        heads x [k_nope | v]
      q_rope, k_r = R(q_rope), R(k_r)            k_r rotated once, shared
      k_i = [k_nope_i | k_r]
      x = x + concat_i softmax_causal(q_i k_i^T / sqrt(d)) v_i W_o
      b = RMSNorm(x)
      x = x + W_down(silu(W_gate b) * W_up b)    blocks 0..first_k_dense - 1
      x = x + y_routed + E_shared(b)             the others
    logits = RMSNorm(x) W_head                   untied head

``R`` turns adjacent pairs (``rope_interleave``): for a 64-vector at
position p and j = 0..31, ``(y_2j, y_2j+1) = (x_2j cos p w_j - x_2j+1 sin
p w_j, x_2j sin p w_j + x_2j+1 cos p w_j)``, ``w_j = theta^(-2j / 64)``,
positions 0..T-1 of the one packed stream, no YaRN (``rope_scaling``
null). The family's code de-interleaves a slice to ``[evens | odds]`` and
then applies ``rotate_half``: the same scores, under a permutation of the
slice's coordinates that queries and keys share.

Multi-token prediction, depth 1 (``num_nextn_predict_layers``; DeepSeek-V3's
technical report, section 2.2), over ``h`` = the last main block's output
BEFORE the final norm:

    h' = W_eh [enorm(Emb(t_{i+1})) ; hnorm(h_i)]       the embedding first
    h' = block(h')                 one full block, its own router and bias
    mtp_logits = RMSNorm_mtp(h') W_head                the SHARED head
    loss = CE(logits, t_{i+1}) + lambda CE(mtp_logits, t_{i+2}) + balance

Routing: ``s = sigmoid(b W_r)`` over all ``num_experts_routed`` in float32;
the ``num_experts_per_tok`` largest of ``s + bias`` (the bias selects, it
does not weigh); ``w_k = routed_scaling_factor * s_k / (sum of the chosen s
+ 1e-20)``; expert e is ``W_down[e](silu(W_gate[e] b) * W_up[e] b)``.

The chip's share (``model-configs`` guide, section 4): ``weights`` hold the
routed experts ``expert_offset .. expert_offset + n_routed_experts - 1``
only and a ``vocab_size``-row slice of embedding and head; ``y_routed`` is
the part those experts give, and what the absent ones would add is left out
here as in the program; the shared expert is whole on every chip. With
``n_routed_experts == num_experts_routed`` it is the whole model.

Departures and what the config leaves open (it has no key for these): the
MTP module reads ``h`` before the main final norm (vLLM feeds the normed
state; at initialisation both norms' scales are 1, so the first step cannot
tell the two apart); its residual stream is float32 like the main one's;
lambda is ``mtp_loss_weight`` (0.3, the report's for the first 10T
tokens); the balance term is ``num_experts_routed * sum_e f_e P_e`` per
sparse block, the MTP's block among them (f_e the assignments to e over the
tokens, P_e the mean of ``s_e / sum(s)``), averaged over those blocks,
times ``balance_loss_coef``. The bias's update (``bias_e += rate *
sign(mean(c) - c_e)``) is ``bias_update``, apart from the loss: it is no
gradient's. One packed stream: positions run 0..T-1 across documents.

``precision``: ``"exact"`` is float32 with every product at ``highest``;
``"stated"`` is the same code at the precision the program states under AMP
(bfloat16 operands, float32 accumulation, bfloat16 where the program keeps
an activation in it: projections, latents and their norms' outputs, the
rotated slices, attention's output, the MLPs' and experts'
pre-activations, logits; router, scores, bias, selection, softmaxes, the
residual streams and the blocks' norms float32); ``"bfloat16"`` keeps
everything in bfloat16: the nearest precision below. ``mutate`` breaks one
piece of the mathematics on purpose, for the tests that show a tolerance
catches it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("rotate_half_pairs", "shared_key_unrotated", "queries_unrotated",
             "rotation_on_nope", "key_positions_off_by_one", "theta_10000",
             "scale_by_nope_dim", "q_norm_left_out", "kv_norm_left_out",
             "mtp_input_current_token", "mtp_target_next_token",
             "mtp_concat_swapped", "hnorm_left_out", "mtp_norm_left_out",
             "mtp_weight_zero", "scaling_left_out", "bias_in_weights",
             "softmax_scores", "shared_expert_left_out",
             "expert_offset_off_by_one")
QUERY_BLOCK = 512
MLA = ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb", "w_o")
DENSE = ("w_gate", "w_up", "w_down")
SPARSE = ("router", "bias", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down")
MTP = ("enorm", "hnorm", "w_eh")


def layer_kinds(cfg):
    """``dense`` for the first ``first_k_dense_replace`` blocks held here,
    ``sparse`` after."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "sparse"
            for i in range(cfg["num_hidden_layers"])]


def unpack(cfg, weights):
    """The flat list of parameters in the program's creation order ->
    (embedding, [block dicts], final norm, head, MTP dict or None)."""
    weights = list(weights)
    at = [1]

    def take(names):
        got = dict(zip(names, weights[at[0]:at[0] + len(names)]))
        at[0] += len(names)
        return got

    def block(mlp):
        return dict(take(("norm1",) + MLA + ("norm2",)
                         + (DENSE if mlp == "dense" else SPARSE)), mlp=mlp)

    layers = [block(kind) for kind in layer_kinds(cfg)]
    final, head = take(("final", "head")).values()
    mtp = None
    if cfg.get("num_nextn_predict_layers", 0):
        mtp = take(MTP)
        mtp["block"] = block("sparse")
        mtp["norm"] = take(("norm",))["norm"]
    assert at[0] == len(weights), (at[0], len(weights))
    return weights[0], layers, final, head, mtp


class _Precision:
    def __init__(self, name):
        assert name in ("exact", "stated", "bfloat16"), name
        self.name = name
        self.low = jnp.bfloat16 if name != "exact" else jnp.float32
        # the type of the residual streams, the norms, the softmax and the
        # router
        self.island = jnp.bfloat16 if name == "bfloat16" else jnp.float32

    def operand(self, x):
        """An operand as the MXU takes it: rounded to the compute type. The
        product itself is then float32 at ``highest`` everywhere, which for
        rounded operands IS low-precision operands with float32
        accumulation, and runs on any backend."""
        return x.astype(self.low).astype(jnp.float32)

    def mm(self, a, b):
        """A product the program hands to the MXU and keeps in the compute
        type."""
        out = jnp.dot(self.operand(a), self.operand(b),
                      precision=jax.lax.Precision.HIGHEST)
        return self.kept(out)

    def kept(self, x):
        """An activation the program keeps in the compute type."""
        return x.astype(self.low).astype(self.island)


def rms_norm(x, scale, eps, pr, keep=False):
    """``keep``: the input was a kept activation, and so is the result."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    y = (y * scale.astype(jnp.float32)).astype(pr.island)
    return pr.kept(y) if keep else y


def rotate(cfg, x, pr, mutate, shift=0):
    """x [T, heads, 64] (kept) turned by the pair formula at positions
    ``shift``..T-1+``shift``; ``rotate_half_pairs`` turns (j, j + 32)
    instead of (2j, 2j + 1)."""
    t, _, rd = x.shape
    theta = 10000.0 if mutate == "theta_10000" else float(cfg["rope_theta"])
    omega = 1.0 / theta ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    start = shift
    angles = jnp.arange(start, start + t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(omega, jnp.float32)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    if mutate == "rotate_half_pairs" or not cfg.get("rope_interleave", True):
        a, b = xf[..., :rd // 2], xf[..., rd // 2:]
        return pr.kept(jnp.concatenate([a * cos - b * sin,
                                        a * sin + b * cos], -1))
    a, b = xf[..., 0::2], xf[..., 1::2]
    return pr.kept(jnp.stack([a * cos - b * sin, a * sin + b * cos],
                             -1).reshape(x.shape))


def mla(cfg, layer, x, pr, mutate):
    """x [T, hidden], already normed -> [T, hidden], by the definition, in
    blocks of query rows."""
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    d, eps = nope + rope, cfg["rms_norm_eps"]
    qa = pr.mm(x, layer["w_qa"])
    if mutate != "q_norm_left_out":
        qa = rms_norm(qa, layer["q_norm"], eps, pr, keep=True)
    q = pr.mm(qa, layer["w_qb"]).reshape(t, heads, d)
    kva = pr.mm(x, layer["w_kva"])
    c_kv, k_r = kva[:, :cfg["kv_lora_rank"]], kva[:, cfg["kv_lora_rank"]:]
    if mutate != "kv_norm_left_out":
        c_kv = rms_norm(c_kv, layer["kv_norm"], eps, pr, keep=True)
    kv = pr.mm(c_kv, layer["w_kvb"]).reshape(t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    k_r = k_r[:, None, :]
    if mutate == "rotation_on_nope":
        q_nope = jnp.concatenate([rotate(cfg, q_nope[..., :rope], pr, None),
                                  q_nope[..., rope:]], -1)
        k_nope = jnp.concatenate([rotate(cfg, k_nope[..., :rope], pr, None),
                                  k_nope[..., rope:]], -1)
    else:
        if mutate != "queries_unrotated":
            q_rope = rotate(cfg, q_rope, pr, mutate)
        if mutate != "shared_key_unrotated":
            # a shift of every position is invisible (the scores see
            # p' - p alone); the mutation shifts the keys' alone
            k_r = rotate(cfg, k_r, pr, mutate,
                         shift=int(mutate == "key_positions_off_by_one"))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (t, heads, rope))],
                        -1)
    scale = (nope if mutate == "scale_by_nope_dim" else d) ** -0.5
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kpos = jnp.arange(t)[None, :]

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", pr.operand(qb), pr.operand(k),
                       precision=jax.lax.Precision.HIGHEST) * scale
        seen = kpos <= lo + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf)
                           .astype(pr.island), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr.operand(p), pr.operand(v),
                       precision=jax.lax.Precision.HIGHEST)
        return pr.kept(o.reshape(block, heads * dv))

    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * dv)
    return pr.mm(out, layer["w_o"])


def gated_mlp(x, w_gate, w_up, w_down, pr):
    gate, up = pr.mm(x, w_gate), pr.mm(x, w_up)
    act = pr.kept(pr.kept(jax.nn.silu(gate.astype(jnp.float32)))
                  * up.astype(jnp.float32))
    return pr.mm(act, w_down)


def route(cfg, layer, x, pr, mutate):
    """(weights over all routed experts [T, routed], zero off the top k;
    the balance term; assignments per routed expert [routed]; top-k ids)."""
    routed, k = cfg["num_experts_routed"], cfg["num_experts_per_tok"]
    logits = jnp.dot(x.astype(pr.island), layer["router"].astype(pr.island),
                     precision=jax.lax.Precision.HIGHEST).astype(pr.island)
    sigmoid = cfg.get("scoring_func", "sigmoid") == "sigmoid" \
        and mutate != "softmax_scores"
    s = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, -1)
    bias = layer["bias"].astype(pr.island)
    _, top = jax.lax.top_k(s + bias, k)
    chosen = jnp.sum(jax.nn.one_hot(top, routed, dtype=s.dtype), axis=1)
    weight = (s + bias if mutate == "bias_in_weights" else s) * chosen
    if cfg.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, -1, keepdims=True)
                           + (1e-20 if sigmoid else 0.0))
    if mutate != "scaling_left_out":
        weight = weight * cfg.get("routed_scaling_factor", 1.0)
    counts = jnp.sum(chosen, 0)
    share = jax.lax.stop_gradient(counts) / x.shape[0]
    dist = s / jnp.sum(s, -1, keepdims=True) if sigmoid else s
    aux = routed * jnp.sum(share * jnp.mean(dist, axis=0))
    return weight, aux.astype(jnp.float32), counts.astype(jnp.int32), top


def experts(cfg, layer, x, pr, mutate):
    """x [T, hidden], already normed -> (the held routed experts' part plus
    the shared expert [T, hidden], the balance term, rows per held expert
    [held], top-k ids [T, k], assignments per routed expert [routed])."""
    routed = cfg["num_experts_routed"]
    offset = cfg.get("expert_offset", 0)
    if mutate == "expert_offset_off_by_one":
        offset += 1
    held = layer["e_gate"].shape[0]
    weight, aux, counts, top = route(cfg, layer, x, pr, mutate)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):                         # dense: every token
        w = weight[:, (offset + e) % routed, None].astype(jnp.float32)
        y = y + w * gated_mlp(x, layer["e_gate"][e], layer["e_up"][e],
                              layer["e_down"][e], pr).astype(jnp.float32)
    y = y.astype(pr.island)
    if mutate != "shared_expert_left_out":
        y = y + gated_mlp(x, layer["s_gate"], layer["s_up"],
                          layer["s_down"], pr)
    return y, aux, counts[offset:offset + held], top, counts


def bias_update(bias, counts, rate):
    """The selection bias after a step whose assignments per routed expert
    were ``counts``."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def block(cfg, layer, x, pr, mutate):
    eps = cfg["rms_norm_eps"]
    x = x + mla(cfg, layer, rms_norm(x, layer["norm1"], eps, pr), pr, mutate)
    b = rms_norm(x, layer["norm2"], eps, pr)
    if layer["mlp"] == "dense":
        return x + gated_mlp(b, layer["w_gate"], layer["w_up"],
                             layer["w_down"], pr), None
    y, aux, load, top, counts = experts(cfg, layer, b, pr, mutate)
    return x + y, (aux, load, top, counts)


def mtp_module(cfg, mtp, emb, head, h, tokens, labels, pr, mutate):
    """The MTP module's logits [T, vocab] (predicting t_{i+2} at i) and its
    block's routing, from the main stream ``h`` before the final norm."""
    eps = cfg["rms_norm_eps"]
    ahead = tokens if mutate == "mtp_input_current_token" else labels
    e = rms_norm(emb[ahead].astype(pr.island), mtp["enorm"], eps, pr)
    hn = h if mutate == "hnorm_left_out" else rms_norm(h, mtp["hnorm"], eps,
                                                       pr)
    both = [hn, e] if mutate == "mtp_concat_swapped" else [e, hn]
    x, routed = block(cfg, mtp["block"],
                      pr.mm(jnp.concatenate(both, -1), mtp["w_eh"]), pr,
                      mutate)
    if mutate != "mtp_norm_left_out":
        x = rms_norm(x, mtp["norm"], eps, pr)
    return pr.mm(x, head).astype(jnp.float32), routed


def forward(cfg, weights, tokens, labels, precision="exact", mutate=None):
    """tokens, labels (t_{i+1}) [T] int -> (logits [T, vocab] float32, the
    MTP's logits or None, mean balance term, and per sparse block (the
    MTP's last): rows per held expert, top-k ids, assignments per routed
    expert)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    pr = _Precision(precision)
    emb, layers, final, head, mtp = unpack(cfg, weights)
    x = emb[tokens].astype(pr.island)
    routed = []
    for layer in layers:
        x, r = block(cfg, layer, x, pr, mutate)
        if r is not None:
            routed.append(r)
    logits = pr.mm(rms_norm(x, final, cfg["rms_norm_eps"], pr),
                   head).astype(jnp.float32)
    mtp_logits = None
    if mtp is not None:
        mtp_logits, r = mtp_module(cfg, mtp, emb, head, x, tokens, labels,
                                   pr, mutate)
        routed.append(r)
    aux = jnp.mean(jnp.stack([r[0] for r in routed])) if routed else 0.0
    return (logits, mtp_logits, aux, [r[1] for r in routed],
            [r[2] for r in routed], [r[3] for r in routed])


def _nll(logits, labels):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                labels[:, None], axis=1)[:, 0]


def loss_fn(cfg, weights, tokens, labels, mtp_labels, precision="exact",
            mutate=None):
    """Mean next-token cross-entropy over the vocabulary slice, plus
    ``mtp_loss_weight`` times the MTP's mean cross-entropy against the
    tokens two ahead, plus ``balance_loss_coef`` times the mean balance
    term; also the two logits and the routing."""
    logits, mtp_logits, aux, loads, tops, counts = forward(
        cfg, weights, tokens, labels, precision, mutate)
    loss = jnp.mean(_nll(logits, labels)) \
        + cfg.get("balance_loss_coef", 0.0) * aux
    if mtp_logits is not None:
        target = labels if mutate == "mtp_target_next_token" else mtp_labels
        weight = 0.0 if mutate == "mtp_weight_zero" \
            else cfg["mtp_loss_weight"]
        loss = loss + weight * jnp.mean(_nll(mtp_logits, target))
    return loss, (logits, mtp_logits, loads, tops, counts)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "mutate",
                                             "with_grads"))
def _run(cfg_key, weights, tokens, labels, mtp_labels, precision, mutate,
         with_grads):
    cfg = _CFGS[cfg_key]

    def f(w):
        return loss_fn(cfg, w, tokens, labels, mtp_labels, precision, mutate)

    if with_grads:
        (loss, extra), grads = jax.value_and_grad(f, has_aux=True)(weights)
        return loss, extra, grads
    loss, extra = f(weights)
    return loss, extra, None


_CFGS = {}


def run(cfg, weights, tokens, labels, mtp_labels, precision="exact",
        mutate=None, with_grads=False):
    """(loss, logits [T, vocab], MTP logits [T, vocab] or None, [rows per
    held expert], [top-k ids], gradients in the weights' order or None,
    [assignments per routed expert]) of one sequence, jitted."""
    import json
    key = json.dumps(cfg, sort_keys=True, default=str)
    _CFGS[key] = cfg
    weights = [jnp.asarray(w, jnp.float32) for w in weights]
    with jax.default_matmul_precision("highest"):
        loss, (logits, mtp_logits, loads, tops, counts), grads = _run(
            key, weights, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(labels, jnp.int32),
            jnp.asarray(mtp_labels, jnp.int32), precision, mutate,
            with_grads)
    return loss, logits, mtp_logits, loads, tops, grads, counts

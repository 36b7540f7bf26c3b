"""Plain reference of the Kimi-Linear block stack (moonshotai/
Kimi-Linear-48B-A3B-Instruct, ``model_type: kimi_linear``, 48B-A3B), written
from its published ``config.json``: forward, loss and gradients in
straightforward ``jax.numpy``. No kernels, no chunks, no sorting: the linear
attention is its recurrence, token by token; the experts are dense; latent
attention holds a block of query rows' scores at a time.

    a = RMSNorm(x)
    KDA blocks (``linear_attn_config.kda_layers``, counted from 1), per head
    of ``linear_attn_config.num_heads``, d = ``linear_attn_config.head_dim``:
      q, k, v = silu(conv(a W_q)), silu(conv(a W_k)), silu(conv(a W_v))
          conv: causal, depthwise, over the current and the three earlier
          tokens (``short_conv_kernel_size`` 4), one filter a channel
      q = l2norm(q) / sqrt(d)      k = l2norm(k)
      g_t = -exp(A_log) * softplus(a W_fa W_fb + dt_bias)   per key channel
      beta_t = sigmoid(a W_b)                               per head
      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                      S_0 = 0, S in R^{d x d}, float32
      o = RMSNorm_d(o) * sigmoid(a W_ga W_gb)       one scale [d], all heads
      x = x + concat_h(o) W_o
    MLA blocks (``full_attn_layers``), NoPE (``mla_use_nope``):
      q = a W_q                                  heads x (nope + rope)
      [c_kv | k_r] = a W_kva                     kv_lora_rank | ONE key slice
      RMSNorm(c_kv) W_kvb                        heads x [k_nope | v]
      k_i = [k_nope_i | k_r]; nothing is rotated
      x = x + concat_i softmax_causal(q_i k_i^T / sqrt(nope + rope)) v_i W_o
    b = RMSNorm(x)
    x = x + W_down(silu(W_gate b) * W_up b)        blocks 1..first_k_dense
    x = x + y_routed + E_shared(b)                 the others
    logits = RMSNorm(x) W_head                     untied head

Routing: ``s = sigmoid(b W_r)`` over all ``num_experts_routed`` in float32;
the ``num_experts_per_token`` largest of ``s + bias`` (the bias selects, it
does not weigh); ``w_k = routed_scaling_factor * s_k / (sum of the chosen s +
1e-20)``; expert e is ``W_down[e](silu(W_gate[e] b) * W_up[e] b)``.

The chip's share (``model-configs`` guide, section 4): ``weights`` hold the
routed experts ``expert_offset .. expert_offset + num_experts - 1`` only and
a ``vocab_size``-row slice of embedding and head; ``y_routed`` is the part
those experts give, and what the absent ones would add is left out here as
in the program; the shared expert is whole on every chip. With ``num_experts
== num_experts_routed`` it is the whole model.

Departures from the published description, and what it leaves open (the
config has no key for any of these; the family's public code decides the
order inside KDA): SiLU follows the convolution and the L2 norm follows
SiLU; the scale d^-0.5 is on q; the state decays BEFORE the delta correction
reads it; the gate is ``-exp(A_log) * softplus(. + dt_bias)`` with the
low-rank projection unbiased; L2 norms add 1e-6 under the root. One packed
stream: neither the state nor the convolution is reset at a document
boundary. The balance term is ``num_experts_routed * sum_e f_e P_e`` per
sparse layer (f_e the assignments to e over the tokens, P_e the mean of ``s_e
/ sum(s)``), averaged over the sparse layers, times ``balance_loss_coef``.
The bias's update (``bias_e += rate * sign(mean(c) - c_e)``) is
``bias_update``, apart from the loss: it is no gradient's.

``precision``: ``"exact"`` is float32 with every product at ``highest``;
``"stated"`` is the same code at the precision the program states under AMP
(bfloat16 operands, float32 accumulation, bfloat16 where the program keeps
an activation in it; router, scores, bias, selection, the decay's gate, the
state ``S``, softmaxes and residual stream float32); ``"bfloat16"`` keeps
everything in bfloat16, the state too: the nearest precision below.
``mutate`` breaks one piece of the mathematics on purpose, for the tests
that show a tolerance catches it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("decay_after_update", "decay_per_head", "beta_left_out",
             "l2_norm_left_out", "conv_looks_ahead", "output_gate_left_out",
             "scale_by_nope_dim", "shared_key_rotated", "shared_key_per_head",
             "softmax_scores", "bias_in_weights", "scaling_left_out",
             "shared_expert_left_out", "expert_offset_off_by_one")
QUERY_BLOCK = 512
L2_EPS = 1e-6
KDA = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
       "a_log", "dt_bias", "w_b", "w_ga", "w_gb", "o_norm", "w_o")
MLA = ("w_q", "w_kva", "kv_norm", "w_kvb", "w_o")
DENSE = ("w_gate", "w_up", "w_down")
SPARSE = ("router", "bias", "e_gate", "e_up", "e_down",
          "s_gate", "s_up", "s_down")


def layer_kinds(cfg):
    """[(attention kind, mlp kind)] of the blocks held here: ``kda`` or
    ``mla`` by ``linear_attn_config`` (layers counted from 1), ``dense`` for
    the first ``first_k_dense_replace`` blocks and ``sparse`` after."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in lin["kda_layers"]) == (i in lin["full_attn_layers"]):
            raise ValueError(f"layer {i} is not exactly one of kda_layers "
                             "and full_attn_layers")
        kinds.append(("kda" if i in lin["kda_layers"] else "mla",
                      "dense" if i <= cfg["first_k_dense_replace"]
                      else "sparse"))
    return kinds


def unpack(cfg, weights):
    """The flat list of parameters in the program's creation order ->
    (embedding, [layer dicts], final norm, head)."""
    weights = list(weights)
    at = [1]

    def take(names):
        got = dict(zip(names, weights[at[0]:at[0] + len(names)]))
        at[0] += len(names)
        return got

    layers = []
    for attention, mlp in layer_kinds(cfg):
        layer = take(("norm1",) + (KDA if attention == "kda" else MLA)
                     + ("norm2",) + (DENSE if mlp == "dense" else SPARSE))
        layers.append(dict(layer, attention=attention, mlp=mlp))
    final, head = weights[at[0]], weights[at[0] + 1]
    assert at[0] + 2 == len(weights), (at[0] + 2, len(weights))
    return weights[0], layers, final, head


class _Precision:
    def __init__(self, name):
        assert name in ("exact", "stated", "bfloat16"), name
        self.name = name
        self.low = jnp.bfloat16 if name != "exact" else jnp.float32
        # the type of the residual stream, the norms, the softmax, the
        # router, the decay's gate and the state
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

    def held(self, x):
        """A float32 island's value as this precision holds it."""
        return x.astype(self.island).astype(jnp.float32)


def rms_norm(x, scale, eps, pr, keep=False):
    """``keep``: the input was a kept activation, and so is the result."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    y = (y * scale.astype(jnp.float32)).astype(pr.island)
    return pr.kept(y) if keep else y


def short_conv(x, w, pr, mutate):
    """x [T, channels] (kept), w [taps, channels]: silu of each channel's
    own filter over the current token (the last tap) and the ``taps - 1``
    before it (after it under ``conv_looks_ahead``)."""
    taps, t = w.shape[0], x.shape[0]
    xf = x.astype(jnp.float32)
    if mutate == "conv_looks_ahead":
        wide = jnp.pad(xf, ((0, taps - 1), (0, 0)))
        y = sum(wide[j:j + t] * w[taps - 1 - j] for j in range(taps))
    else:
        wide = jnp.pad(xf, ((taps - 1, 0), (0, 0)))
        y = sum(wide[j:j + t] * w[j] for j in range(taps))
    return pr.kept(jax.nn.silu(y))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta, pr, mutate):
    """The recurrence, token by token. q, k, g [T, H, d], v [T, H, dv], beta
    [T, H] -> o [T, H, dv]. The state is float32 (``pr.island``)."""
    hi = jax.lax.Precision.HIGHEST

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        decay = jnp.exp(g_t)[..., None]
        if mutate != "decay_after_update":
            s = pr.held(decay * s)
        seen = jnp.einsum("hde,hd->he", s, k_t, precision=hi)
        s = s + b_t[:, None, None] * k_t[..., None] * (v_t - seen)[:, None, :]
        if mutate == "decay_after_update":
            s = decay * s
        s = pr.held(s)
        return s, jnp.einsum("hde,hd->he", s, q_t, precision=hi)

    heads, d = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((heads, d, v.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))
    return o


def kda(cfg, layer, x, pr, mutate):
    """x [T, hidden], already normed -> [T, hidden]."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]
    q, k, v = (short_conv(pr.mm(x, layer["w_" + n]), layer["conv_" + n], pr,
                          mutate).astype(jnp.float32).reshape(t, heads, d)
               for n in "qkv")
    if mutate != "l2_norm_left_out":
        q, k = _l2(q), _l2(k)
    q = q * d ** -0.5
    step = jax.nn.softplus(
        pr.mm(pr.mm(x, layer["w_fa"]), layer["w_fb"]).astype(jnp.float32)
        + layer["dt_bias"]).reshape(t, heads, d)
    g = pr.held(-jnp.exp(layer["a_log"])[None, :, None] * step)
    if mutate == "decay_per_head":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = pr.kept(jax.nn.sigmoid(
        pr.mm(x, layer["w_b"]).astype(jnp.float32))).astype(jnp.float32)
    if mutate == "beta_left_out":
        beta = jnp.ones_like(beta)
    o = pr.kept(delta_rule(q, k, v, g, beta, pr, mutate)) \
        .astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * layer["o_norm"]
    if mutate != "output_gate_left_out":
        gate = pr.mm(pr.mm(x, layer["w_ga"]), layer["w_gb"])
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)) \
            .reshape(t, heads, d)
    return pr.mm(pr.kept(o.reshape(t, heads * d)), layer["w_o"])


def _rotated(x, theta, pr):
    """x [T, heads, d] turned whole by its positions (``rotate_half``): the
    ``shared_key_rotated`` mutation's alone, the model rotates nothing."""
    t, _, d = x.shape
    freq = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], -1)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return pr.kept(xf * jnp.cos(angles)
                   + jnp.concatenate([-b, a], -1) * jnp.sin(angles))


def mla(cfg, layer, x, pr, mutate):
    """x [T, hidden], already normed -> [T, hidden], by the definition, in
    blocks of query rows."""
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    d, eps = nope + rope, cfg["rms_norm_eps"]
    q = pr.mm(x, layer["w_q"]).reshape(t, heads, d)
    kva = pr.mm(x, layer["w_kva"])
    c_kv, k_r = kva[:, :cfg["kv_lora_rank"]], kva[:, cfg["kv_lora_rank"]:]
    kv = pr.mm(rms_norm(c_kv, layer["kv_norm"], eps, pr, keep=True),
               layer["w_kvb"]).reshape(t, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_r = jnp.broadcast_to(k_r[:, None, :], (t, heads, rope))
    if mutate == "shared_key_per_head":     # head i's own: the shared one,
        k_r = jnp.stack([jnp.roll(k_r[:, i], i + 1, axis=-1)   # rolled i+1
                         for i in range(heads)], axis=1)
    if mutate == "shared_key_rotated":
        theta = cfg.get("rope_theta", 10000)
        q = jnp.concatenate([q[..., :nope],
                             _rotated(q[..., nope:], theta, pr)], -1)
        k_r = _rotated(k_r, theta, pr)
    k = jnp.concatenate([k_nope, k_r], -1)
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
    routed, k = cfg["num_experts_routed"], cfg["num_experts_per_token"]
    logits = jnp.dot(x.astype(pr.island), layer["router"].astype(pr.island),
                     precision=jax.lax.Precision.HIGHEST).astype(pr.island)
    sigmoid = cfg.get("moe_router_activation_func", "sigmoid") == "sigmoid" \
        and mutate != "softmax_scores"
    s = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, -1)
    bias = layer["bias"].astype(pr.island)
    _, top = jax.lax.top_k(s + bias, k)
    chosen = jnp.sum(jax.nn.one_hot(top, routed, dtype=s.dtype), axis=1)
    weight = (s + bias if mutate == "bias_in_weights" else s) * chosen
    if cfg.get("moe_renormalize", True):
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
    attention = kda if layer["attention"] == "kda" else mla
    x = x + attention(cfg, layer, rms_norm(x, layer["norm1"], eps, pr), pr,
                      mutate)
    b = rms_norm(x, layer["norm2"], eps, pr)
    if layer["mlp"] == "dense":
        return x + gated_mlp(b, layer["w_gate"], layer["w_up"],
                             layer["w_down"], pr), None
    y, aux, load, top, counts = experts(cfg, layer, b, pr, mutate)
    return x + y, (aux, load, top, counts)


def forward(cfg, weights, tokens, precision="exact", mutate=None):
    """tokens [T] int -> (logits [T, vocab] float32, mean balance term, and
    per sparse layer: rows per held expert, top-k ids, assignments per
    routed expert)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    pr = _Precision(precision)
    emb, layers, final, head = unpack(cfg, weights)
    x = emb[tokens].astype(pr.island)
    routed = []
    for layer in layers:
        x, r = block(cfg, layer, x, pr, mutate)
        if r is not None:
            routed.append(r)
    logits = pr.mm(rms_norm(x, final, cfg["rms_norm_eps"], pr),
                   head).astype(jnp.float32)
    aux = jnp.mean(jnp.stack([r[0] for r in routed])) if routed else 0.0
    return (logits, aux, [r[1] for r in routed], [r[2] for r in routed],
            [r[3] for r in routed])


def loss_fn(cfg, weights, tokens, labels, precision="exact", mutate=None):
    """Mean next-token cross-entropy over the vocabulary slice plus
    ``balance_loss_coef`` times the mean balance term; also the logits."""
    logits, aux, loads, tops, counts = forward(cfg, weights, tokens,
                                               precision, mutate)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               labels[:, None], axis=1)[:, 0]
    loss = jnp.mean(nll) + cfg.get("balance_loss_coef", 0.0) * aux
    return loss, (logits, loads, tops, counts)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "mutate",
                                             "with_grads"))
def _run(cfg_key, weights, tokens, labels, precision, mutate, with_grads):
    cfg = _CFGS[cfg_key]
    if with_grads:
        (loss, extra), grads = jax.value_and_grad(
            lambda w: loss_fn(cfg, w, tokens, labels, precision, mutate),
            has_aux=True)(weights)
        return loss, extra, grads
    loss, extra = loss_fn(cfg, weights, tokens, labels, precision, mutate)
    return loss, extra, None


_CFGS = {}


def run(cfg, weights, tokens, labels, precision="exact", mutate=None,
        with_grads=False):
    """(loss, logits [T, vocab], [rows per held expert], [top-k ids],
    gradients in the weights' order or None, [assignments per routed
    expert]) of one sequence, jitted."""
    import json
    key = json.dumps(cfg, sort_keys=True, default=str)
    _CFGS[key] = cfg
    weights = [jnp.asarray(w, jnp.float32) for w in weights]
    with jax.default_matmul_precision("highest"):
        loss, (logits, loads, tops, counts), grads = _run(
            key, weights, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(labels, jnp.int32), precision, mutate, with_grads)
    return loss, logits, loads, tops, grads, counts

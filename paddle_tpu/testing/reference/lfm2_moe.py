"""Plain reference of the LFM2-MoE layer stack (LiquidAI/LFM2-24B-A2B,
``model_type: lfm2_moe``), written from its published ``config.json``:
forward, loss and gradients in straightforward ``jax.numpy``. No kernels, no
sorting, no blocking beyond query-row blocks that keep attention's scores
small enough to hold.

    every layer, pre-norm (RMSNorm, eps ``norm_eps``), no bias anywhere:
      h   = x + mixer(RMSNorm(x))
      out = h + ffn(RMSNorm(h))
    logits = RMSNorm(out) W_head                untied head

The mixer is the layer's ``layer_types`` entry:

    conv (the gated short convolution, ``conv_L_cache`` taps, no bias):
      [B | C | x] = a W_in                      hidden -> 3 x hidden
      u = B * x
      z_t = sum_{s=0..taps-1} w[taps - 1 - s] * u_{t-s}   (zeros before t=0)
      out = (C * z) W_out                       no activation anywhere
    full_attention (grouped-query, ``num_attention_heads`` query heads over
    ``num_key_value_heads`` key/value heads of hidden / heads = 64, query
    head j reading key/value head j // group):
      q, k, v = a W_q, a W_k, a W_v
      q, k = RMSNorm_q(q), RMSNorm_k(k)         per head, a learned [64] each
      q, k = R(q), R(k)                         rotate_half at theta
                                                rope_parameters.rope_theta
      out = concat_j softmax_causal(q_j k_g^T / 8) v_g W_o

The feed-forward part is a dense gated-SiLU MLP of ``intermediate_size`` in
the first ``num_dense_layers`` layers and ``num_experts_routed`` routed
experts after, no shared expert: ``s = sigmoid(b W_r)`` in float32, the
``num_experts_per_tok`` largest of ``s + bias`` (``use_expert_bias``: the
bias selects, it does not weigh), ``w_k = routed_scaling_factor * s_k /
(sum of the chosen s + 1e-20)`` (``norm_topk_prob``), expert e
``W_down[e](silu(W_gate[e] b) * W_up[e] b)``.

The chip's share (``model-configs`` guide, section 4): this chip holds the
published layers ``first_layer .. first_layer + num_hidden_layers - 1``
(counted from 0; ``layer_kinds``); ``weights`` hold the routed experts
``expert_offset .. expert_offset + num_experts - 1`` only and a
``vocab_size``-row slice of embedding and head; the routed result is the
part those experts give, and what the absent ones would add is left out here
as in the program. With ``num_experts == num_experts_routed`` it is the whole
layer.

Departures and what the config leaves open (it has no key for these): the
renormalisation adds 1e-20 to the sum of the chosen scores (the family's
code adds 1e-6; ``ops/moe_ops`` has one constant for every sigmoid router);
the balance term is ``num_experts_routed * sum_e f_e P_e`` per routed layer
(f_e the assignments to e over the tokens, P_e the mean of ``s_e /
sum(s)``), averaged over those layers, times ``balance_loss_coef``. The
bias's update (``bias_e += rate * sign(mean(c) - c_e)``) is
``bias_update``, apart from the loss: it is no gradient's. One packed
stream: positions run 0..T-1 across documents.

``precision``: ``"exact"`` is float32 with every product at ``highest``;
``"stated"`` is the same code at the precision the program states under AMP
(bfloat16 operands, float32 accumulation, bfloat16 where the program keeps
an activation in it: the projections, the convolution's output, the normed
and rotated queries and keys, attention's output, the MLPs' and experts'
pre-activations, the logits; inside the convolution, the router with its
scores, bias and selection, the norms, softmaxes and the residual stream
float32); ``"bfloat16"`` keeps everything in bfloat16: the nearest precision
below. ``mutate`` breaks one piece of the mathematics on purpose, for the
tests that show a tolerance catches it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("b_c_swapped", "taps_reversed", "silu_added",
             "qk_norm_left_out", "bias_in_weights", "not_renormalised",
             "kv_interleaved", "expert_offset_off_by_one")
QUERY_BLOCK = 512
CONV = ("w_in", "filter", "w_out")
ATTENTION = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
DENSE = ("w_gate", "w_up", "w_down")
SPARSE = ("router", "bias", "e_gate", "e_up", "e_down")


def layer_kinds(cfg):
    """(mixer, feed-forward part) of each layer held here: the published
    layers ``first_layer`` .. ``first_layer + num_hidden_layers - 1``, the
    mixer ``conv`` or ``full_attention`` by ``layer_types``, the
    feed-forward part ``dense`` below ``num_dense_layers`` and ``sparse``
    from there."""
    first = int(cfg.get("first_layer", 0))
    return [(cfg["layer_types"][i],
             "dense" if i < cfg["num_dense_layers"] else "sparse")
            for i in range(first, first + cfg["num_hidden_layers"])]


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
    for mixer, mlp in layer_kinds(cfg):
        layer = take(("norm1",) + (CONV if mixer == "conv" else ATTENTION)
                     + ("norm2",) + (DENSE if mlp == "dense" else SPARSE))
        layers.append(dict(layer, mixer=mixer, mlp=mlp))
    final, head = take(("final", "head")).values()
    assert at[0] == len(weights), (at[0], len(weights))
    return weights[0], layers, final, head


class _Precision:
    def __init__(self, name):
        assert name in ("exact", "stated", "bfloat16"), name
        self.name = name
        self.low = jnp.bfloat16 if name != "exact" else jnp.float32
        # the type of the residual stream, the norms, the convolution's
        # inside, the softmax and the router
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
    """Over the last axis; ``keep``: the input was a kept activation, and so
    is the result."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    y = (y * scale.astype(jnp.float32)).astype(pr.island)
    return pr.kept(y) if keep else y


def conv_mixer(cfg, layer, a, pr, mutate):
    """a [T, hidden], already normed -> [T, hidden]: the gated short
    convolution."""
    h = a.shape[-1]
    bcx = pr.mm(a, layer["w_in"])
    b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
    if mutate == "b_c_swapped":
        b, c = c, b
    f = pr.island
    w = layer["filter"].astype(f)
    if mutate == "taps_reversed":
        w = w[::-1]
    taps, t = w.shape[0], a.shape[0]
    u = b.astype(f) * x.astype(f)
    back = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    z = sum(back[j:j + t] * w[j] for j in range(taps))
    if mutate == "silu_added":
        z = jax.nn.silu(z)
    return pr.mm(pr.kept(c.astype(f) * z), layer["w_out"])


def rotate(x, theta, pr):
    """x [T, heads, d] (kept): x * cos + rotate_half(x) * sin at positions
    0..T-1."""
    t, _, d = x.shape
    freq = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], -1)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return pr.kept(xf * cos + jnp.concatenate([-b, a], -1) * sin)


def attention(cfg, layer, a, pr, mutate):
    """a [T, hidden], already normed -> [T, hidden], by the definition, in
    blocks of query rows."""
    t, hidden = a.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = hidden // heads, cfg["norm_eps"]
    q = pr.mm(a, layer["wq"]).reshape(t, heads, d)
    k = pr.mm(a, layer["wk"]).reshape(t, kv_heads, d)
    v = pr.mm(a, layer["wv"]).reshape(t, kv_heads, d)
    if mutate != "qk_norm_left_out":
        q = rms_norm(q, layer["q_norm"], eps, pr, keep=True)
        k = rms_norm(k, layer["k_norm"], eps, pr, keep=True)
    theta = cfg["rope_parameters"]["rope_theta"]
    q, k = rotate(q, theta, pr), rotate(k, theta, pr)
    if mutate == "kv_interleaved":
        which = jnp.arange(heads) % kv_heads
    else:
        which = jnp.arange(heads) // (heads // kv_heads)
    k, v = k[:, which], v[:, which]                      # [T, heads, d]
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kpos = jnp.arange(t)[None, :]

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", pr.operand(qb), pr.operand(k),
                       precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        seen = kpos <= lo + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf)
                           .astype(pr.island), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr.operand(p), pr.operand(v),
                       precision=jax.lax.Precision.HIGHEST)
        return pr.kept(o.reshape(block, heads * d))

    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * d)
    return pr.mm(out, layer["wo"])


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
    s = jax.nn.sigmoid(logits)
    bias = layer["bias"].astype(pr.island)
    _, top = jax.lax.top_k(s + bias, k)
    chosen = jnp.sum(jax.nn.one_hot(top, routed, dtype=s.dtype), axis=1)
    weight = (s + bias if mutate == "bias_in_weights" else s) * chosen
    if cfg.get("norm_topk_prob", True) and mutate != "not_renormalised":
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * cfg.get("routed_scaling_factor", 1.0)
    counts = jnp.sum(chosen, 0)
    share = jax.lax.stop_gradient(counts) / x.shape[0]
    aux = routed * jnp.sum(share * jnp.mean(s / jnp.sum(s, -1, keepdims=True),
                                            axis=0))
    return weight, aux.astype(jnp.float32), counts.astype(jnp.int32), top


def experts(cfg, layer, x, pr, mutate):
    """x [T, hidden], already normed -> (the held experts' part [T,
    hidden], the balance term, rows per held expert [held], top-k ids [T,
    k], assignments per routed expert [routed])."""
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
    return (y.astype(pr.island), aux, counts[offset:offset + held], top,
            counts)


def bias_update(bias, counts, rate):
    """The selection bias after a step whose assignments per routed expert
    were ``counts``."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def layer_forward(cfg, layer, x, pr, mutate):
    eps = cfg["norm_eps"]
    mixer = conv_mixer if layer["mixer"] == "conv" else attention
    x = x + mixer(cfg, layer, rms_norm(x, layer["norm1"], eps, pr), pr,
                  mutate)
    b = rms_norm(x, layer["norm2"], eps, pr)
    if layer["mlp"] == "dense":
        return x + gated_mlp(b, layer["w_gate"], layer["w_up"],
                             layer["w_down"], pr), None
    y, aux, load, top, counts = experts(cfg, layer, b, pr, mutate)
    return x + y, (aux, load, top, counts)


def forward(cfg, weights, tokens, precision="exact", mutate=None):
    """tokens [T] int -> (logits [T, vocab] float32, mean balance term, and
    per routed layer: rows per held expert, top-k ids, assignments per
    routed expert)."""
    assert mutate is None or mutate in MUTATIONS, mutate
    pr = _Precision(precision)
    emb, layers, final, head = unpack(cfg, weights)
    x = emb[tokens].astype(pr.island)
    routed = []
    for layer in layers:
        x, r = layer_forward(cfg, layer, x, pr, mutate)
        if r is not None:
            routed.append(r)
    logits = pr.mm(rms_norm(x, final, cfg["norm_eps"], pr),
                   head).astype(jnp.float32)
    aux = jnp.mean(jnp.stack([r[0] for r in routed])) if routed else 0.0
    return (logits, aux, [r[1] for r in routed], [r[2] for r in routed],
            [r[3] for r in routed])


def loss_fn(cfg, weights, tokens, labels, precision="exact", mutate=None):
    """Mean next-token cross-entropy over the vocabulary slice plus
    ``balance_loss_coef`` times the mean balance term; also the logits and
    the routing."""
    logits, aux, loads, tops, counts = forward(cfg, weights, tokens,
                                               precision, mutate)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               labels[:, None], axis=1)
    loss = jnp.mean(nll) + cfg.get("balance_loss_coef", 0.0) * aux
    return loss, (logits, loads, tops, counts)


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "mutate",
                                             "with_grads"))
def _run(cfg_key, weights, tokens, labels, precision, mutate, with_grads):
    cfg = _CFGS[cfg_key]

    def f(w):
        return loss_fn(cfg, w, tokens, labels, precision, mutate)

    if with_grads:
        (loss, extra), grads = jax.value_and_grad(f, has_aux=True)(weights)
        return loss, extra, grads
    loss, extra = f(weights)
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

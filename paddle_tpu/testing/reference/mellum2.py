"""Plain reference of the Mellum2 block stack (JetBrains/Mellum2-12B-A2.5B,
``model_type: mellum``), written from its published ``config.json``:
forward, loss and gradients in straightforward ``jax.numpy``. No kernels,
no sorting, no blocking beyond query-row blocks that keep attention's scores
small enough to hold.

    h   = x + Attn(RMSNorm(x))          pre-norm, no bias anywhere
    out = h + MoE(RMSNorm(h))           every block's MLP is sparse
    logits = RMSNorm(out) @ W_head      untied head

Attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim`` (query head j reads key/value head
j // group), causal; a ``sliding_attention`` layer lets position i see j
with 0 <= i - j < ``sliding_window`` and rotates by plain rotary angles, a
``full_attention`` layer sees every j <= i and rotates by YaRN's
(``rope_parameters``). MoE: router logits over ``num_experts_routed``
experts in float32, softmax over all of them, the ``num_experts_per_tok``
largest, renormalised to sum 1 (``norm_topk_prob``); expert e is
``W_down[e](silu(W_gate[e] x) * W_up[e] x)``.

The chip's share (``model-configs`` guide, section 4): ``weights`` hold the
experts ``expert_offset .. expert_offset + num_experts - 1`` only and a
``vocab_size``-row slice of embedding and head; the result is the part those
experts give, and what the absent ones would add is left out here as in the
program. With ``num_experts == num_experts_routed`` it is the whole model.

Not in the published config, so not built: QK-norm, an MTP head. Assumed
(the family's convention): the load-balancing term ``num_experts_routed *
sum_e f_e P_e`` per layer, averaged over the layers, times
``balance_loss_coef``.

``precision``: ``"exact"`` is float32 with every product at ``highest``;
``"stated"`` is the same code at the precision the program states under AMP
(bfloat16 operands, float32 accumulation, bfloat16 where the program keeps
an activation in it; router, norms, softmax and residual stream float32);
``"bfloat16"`` keeps everything in bfloat16, the nearest precision below.
``mutate`` breaks one piece of the mathematics on purpose, for the tests
that show a tolerance catches it.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("window_ignored", "yarn_ignored", "kv_interleaved",
             "topk_not_renormalised", "softmax_over_topk",
             "expert_offset_off_by_one")
QUERY_BLOCK = 512
PER_LAYER = 10          # weights a block holds, in creation order


def unpack(cfg, weights):
    """The flat list of parameters in the program's creation order ->
    (embedding, [layer dicts], final norm, head)."""
    weights = list(weights)
    names = ("norm1", "wq", "wk", "wv", "wo", "norm2", "router", "w_gate",
             "w_up", "w_down")
    layers = [dict(zip(names, weights[1 + PER_LAYER * i:
                                      1 + PER_LAYER * (i + 1)]))
              for i in range(cfg["num_hidden_layers"])]
    assert len(weights) == 3 + PER_LAYER * len(layers), len(weights)
    return weights[0], layers, weights[-2], weights[-1]


class _Precision:
    def __init__(self, name):
        assert name in ("exact", "stated", "bfloat16"), name
        self.name = name
        self.low = jnp.bfloat16 if name != "exact" else jnp.float32
        # the type of the residual stream, the norms, the softmax, the router
        self.island = jnp.bfloat16 if name == "bfloat16" else jnp.float32

    def operand(self, x):
        """An operand as the MXU takes it: rounded to the compute type. The
        product itself is then float32 at ``highest`` everywhere, which for
        rounded operands IS low-precision operands with float32
        accumulation, and runs on any backend."""
        return x.astype(self.low).astype(jnp.float32)

    def mm(self, a, b):
        """A product the program hands to the MXU."""
        out = jnp.dot(self.operand(a), self.operand(b),
                      precision=jax.lax.Precision.HIGHEST)
        return out.astype(self.island)

    def kept(self, x):
        """An activation the program keeps in the compute type."""
        return x.astype(self.low).astype(self.island)


def inv_freq(head_dim, rope):
    """[head_dim / 2] inverse wavelengths of one ``rope_parameters``
    entry."""
    theta = float(rope["rope_theta"])
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos, 1.0
    assert rope["rope_type"] == "yarn", rope

    def correction_dim(rotations):
        return head_dim * math.log(
            rope["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    blended = ramp / (rope["factor"] * pos) + (1 - ramp) / pos
    return blended, float(rope.get(
        "attention_factor", 0.1 * math.log(rope["factor"]) + 1.0))


def rotate(x, rope, pr):
    """x [T, heads, d]: x * cos + rotate_half(x) * sin."""
    t, _, d = x.shape
    freq, factor = inv_freq(d, rope)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], -1)
    cos = (jnp.cos(angles) * factor)[:, None, :]
    sin = (jnp.sin(angles) * factor)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return pr.kept(xf * cos + jnp.concatenate([-b, a], -1) * sin)


def rms_norm(x, scale, eps, pr):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(pr.island)


def attention(cfg, layer, kind, x, pr, mutate):
    """x [T, hidden] -> [T, hidden], by the definition, in blocks of query
    rows."""
    t = x.shape[0]
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    group = heads // kv_heads
    rope = cfg["rope_parameters"][kind]
    if mutate == "yarn_ignored":
        rope = cfg["rope_parameters"]["sliding_attention"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    if mutate == "window_ignored":
        window = 0
    q = rotate(pr.kept(pr.mm(x, layer["wq"])).reshape(t, heads, d), rope, pr)
    k = rotate(pr.kept(pr.mm(x, layer["wk"])).reshape(t, kv_heads, d), rope,
               pr)
    v = pr.kept(pr.mm(x, layer["wv"])).reshape(t, kv_heads, d)
    if mutate == "kv_interleaved":
        which = jnp.arange(heads) % kv_heads
    else:
        which = jnp.arange(heads) // group
    k, v = k[:, which], v[:, which]                      # [T, heads, d]
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    kpos = jnp.arange(t)[None, :]

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", pr.operand(qb), pr.operand(k),
                       precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        qpos = lo + jnp.arange(block)[:, None]
        seen = kpos <= qpos
        if window:
            seen = seen & (qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf)
                           .astype(pr.island), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr.operand(p), pr.operand(v),
                       precision=jax.lax.Precision.HIGHEST)
        return pr.kept(o.reshape(block, heads * d))

    out = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * d)
    return pr.kept(pr.mm(out, layer["wo"]))


def experts(cfg, layer, x, pr, mutate):
    """x [T, hidden] -> (the held experts' part of the layer [T, hidden],
    the balance term, rows per held expert [held], top-k ids [T, k])."""
    routed, k = cfg["num_experts_routed"], cfg["num_experts_per_tok"]
    offset = cfg.get("expert_offset", 0)
    if mutate == "expert_offset_off_by_one":
        offset += 1
    held = layer["w_gate"].shape[0]
    logits = jnp.dot(x.astype(pr.island), layer["router"].astype(pr.island),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits.astype(pr.island), axis=-1)
    _, top = jax.lax.top_k(probs, k)
    chosen = jnp.sum(jax.nn.one_hot(top, routed, dtype=probs.dtype), axis=1)
    weight = probs * chosen
    if mutate == "softmax_over_topk":
        weight = jax.nn.softmax(jnp.where(chosen > 0, logits, -jnp.inf), -1)
    elif cfg.get("norm_topk_prob", True) \
            and mutate != "topk_not_renormalised":
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):                         # dense: every token
        gate = pr.kept(pr.mm(x, layer["w_gate"][e]))
        up = pr.kept(pr.mm(x, layer["w_up"][e]))
        act = pr.kept(jax.nn.silu(gate.astype(jnp.float32))
                      * up.astype(jnp.float32))
        w = weight[:, (offset + e) % routed, None].astype(jnp.float32)
        y = y + w * pr.mm(act, layer["w_down"][e]).astype(jnp.float32)
    share = jax.lax.stop_gradient(jnp.sum(chosen, 0)) / x.shape[0]
    aux = routed * jnp.sum(share * jnp.mean(probs, axis=0))
    load = jnp.sum(chosen, 0).astype(jnp.int32)[offset:offset + held]
    return y.astype(pr.island), aux.astype(jnp.float32), load, top


def forward(cfg, weights, tokens, precision="exact", mutate=None):
    """tokens [T] int -> (logits [T, vocab] float32, mean balance term,
    [rows per held expert of each layer], [top-k ids of each layer])."""
    assert mutate is None or mutate in MUTATIONS, mutate
    pr = _Precision(precision)
    emb, layers, final, head = unpack(cfg, weights)
    eps = cfg["rms_norm_eps"]
    x = emb[tokens].astype(pr.island)
    aux, loads, tops = [], [], []
    for i, layer in enumerate(layers):
        kind = cfg["layer_types"][i]
        x = x + attention(cfg, layer, kind,
                          rms_norm(x, layer["norm1"], eps, pr), pr, mutate)
        y, a, load, top = experts(cfg, layer,
                                  rms_norm(x, layer["norm2"], eps, pr), pr,
                                  mutate)
        x = x + y
        aux.append(a)
        loads.append(load)
        tops.append(top)
    logits = pr.kept(pr.mm(rms_norm(x, final, eps, pr), head))
    return (logits.astype(jnp.float32), jnp.mean(jnp.stack(aux)), loads,
            tops)


def loss_fn(cfg, weights, tokens, labels, precision="exact", mutate=None):
    """Mean next-token cross-entropy over the vocabulary slice plus
    ``balance_loss_coef`` times the mean balance term; also the logits."""
    logits, aux, loads, tops = forward(cfg, weights, tokens, precision,
                                       mutate)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               labels[:, None], axis=1)
    loss = jnp.mean(nll) + cfg.get("balance_loss_coef", 0.0) * aux
    return loss, (logits, loads, tops)


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
    gradients in the weights' order or None) of one sequence, jitted."""
    import json
    key = json.dumps(cfg, sort_keys=True, default=str)
    _CFGS[key] = cfg
    weights = [jnp.asarray(w, jnp.float32) for w in weights]
    loss, (logits, loads, tops), grads = _run(
        key, weights, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(labels, jnp.int32), precision, mutate, with_grads)
    return loss, logits, loads, tops, grads

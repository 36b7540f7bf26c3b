"""Plain reference of the NemotronH layer stack (nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``,
31.6B-A3.2B), written from its published ``config.json``: forward, loss and
gradients in straightforward ``jax.numpy``. No kernels, no chunks, no
sorting: the state-space layer is its recurrence, token by token; the
experts are a loop over the held ones, each over every token; attention
holds a block of query rows' scores at a time.

Every layer is ONE mixer with a pre-norm and a residual,

    x = x + mixer_l(RMSNorm(x))

and the mixer is, by the l-th letter of ``hybrid_override_pattern``:

    M, Mamba-2 (``mamba_num_heads`` H heads of ``mamba_head_dim`` P,
    ``n_groups`` G groups of ``ssm_state_size`` N, ``conv_kernel`` taps):
      [z | xBC | dt] = u W_in          widths H P | H P + 2 G N | H
      xBC = silu(conv(xBC) + b)        causal, depthwise, the last tap on
                                       the current token
      x [H, P], B [G, N], C [G, N] = split(xBC); head h reads group
                                       h // (H / G)
      dt_t = softplus(dt_t + dt_bias)  per head, no upper clamp
      a_t = exp(dt_t * A), A = -exp(A_log)          one scalar a head
      H_t = a_t H_{t-1} + dt_t x_t B_t^T            H in R^{P x N}, H_0 = 0
      y_t = H_t C_t + D x_t
      y = GroupRMSNorm(y * silu(z)) * w             groups of H P / G
      out = y W_out
    *, attention (``num_attention_heads`` query and ``num_key_value_heads``
    key/value heads of ``head_dim``; query head q reads key/value head
    q // (heads / kv_heads)):
      out = concat_q softmax_causal(q k^T / sqrt(head_dim)) v  W_o
      nothing is rotated and no position term is added
    E, experts:
      s = sigmoid(u W_r) over all ``num_experts_routed`` in float32; the
      ``num_experts_per_tok`` largest of ``s + bias`` (the bias selects, it
      does not weigh); w_k = routed_scaling_factor * s_k / (sum of the
      chosen s + 1e-20); expert e is W_down[e] relu(W_up[e] u)^2 (no gate);
      out = y_routed + W_down_s relu(W_up_s u)^2   (ONE shared expert)

then ``logits = RMSNorm(x) W_head`` (untied head). No bias anywhere but the
convolution's.

The chip's share (``model-configs`` guide, section 4): ``weights`` hold the
routed experts ``expert_offset .. expert_offset + n_routed_experts - 1``
only and a ``vocab_size``-row slice of embedding and head; ``y_routed`` is
the part those experts give, and what the absent ones would add is left out
here as in the program; the shared expert is whole on every chip. With
``n_routed_experts == num_experts_routed`` it is the whole model. The layers
are the first ``num_hidden_layers`` letters of the pattern.

Departures from the published description, and what it leaves open (the
config has no key for any of these; the family's public code decides them):
the order inside the Mamba mixer (SiLU after the convolution's bias; the
gate multiplies BEFORE the grouped norm; ``dt`` has no upper clamp); the
state decays BEFORE the input term is added and the output reads the new
state; attention applies no rotation (``rope_theta`` and
``partial_rotary_factor`` are carried and unused: the state-space layers
carry position); ``expand`` is carried and unused (the inner width is
``mamba_num_heads * mamba_head_dim``). One packed stream: neither the state
nor the convolution is reset at a document boundary. The balance term is
``num_experts_routed * sum_e f_e P_e`` per expert layer (f_e the assignments
to e over the tokens, P_e the mean of ``s_e / sum(s)``), averaged over the
expert layers, times ``balance_loss_coef``. The bias's update (``bias_e +=
rate * sign(mean(c) - c_e)``) is ``bias_update``, apart from the loss: it is
no gradient's.

``precision``: ``"exact"`` is float32 with every product at ``highest``;
``"stated"`` is the same code at the precision the program states under AMP
(bfloat16 operands, float32 accumulation, bfloat16 where the program keeps
an activation in it; residual stream, norms, router, scores, bias,
selection, ``dt``, the decay, the state ``H`` and softmaxes float32);
``"bfloat16"`` keeps everything in bfloat16, the state too: the nearest
precision below. ``mutate`` breaks one piece of the mathematics on purpose,
for the tests that show a tolerance catches it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

MUTATIONS = ("decay_after_update", "dt_left_out_of_input", "skip_left_out",
             "decay_one_for_all_heads", "group_by_modulo", "gate_after_norm",
             "norm_over_all_channels", "sigmoid_gate", "conv_bias_left_out",
             "conv_looks_ahead", "relu_for_relu2", "gated_expert",
             "scaling_left_out", "bias_in_weights", "softmax_scores",
             "shared_expert_left_out", "expert_offset_off_by_one",
             "kv_head_by_modulo", "rotary_applied")
QUERY_BLOCK = 512
MAMBA = ("w_in", "conv_w", "conv_b", "a_log", "dt_bias", "d", "gate_norm",
         "w_out")
ATTENTION = ("w_q", "w_k", "w_v", "w_o")
EXPERTS = ("router", "bias", "e_up", "e_down", "s_up", "s_down")
KINDS = {"M": ("mamba", MAMBA), "*": ("attention", ATTENTION),
         "E": ("experts", EXPERTS)}


def layer_kinds(cfg):
    """The mixers held here, by the first ``num_hidden_layers`` letters of
    ``hybrid_override_pattern``: ``mamba``, ``attention`` or ``experts``."""
    pattern, n = cfg["hybrid_override_pattern"], cfg["num_hidden_layers"]
    if n > len(pattern):
        raise ValueError(f"{n} layers of a pattern of {len(pattern)}")
    for letter in pattern[:n]:
        if letter not in KINDS:
            raise ValueError(f"hybrid_override_pattern holds {letter!r}: "
                             "not one of M (Mamba-2), * (attention), E "
                             "(experts)")
    return [KINDS[letter][0] for letter in pattern[:n]]


def eps_of(cfg):
    return cfg.get("layer_norm_epsilon", cfg.get("norm_eps", 1e-5))


def unpack(cfg, weights):
    """The flat list of parameters in the program's creation order ->
    (embedding, [layer dicts], final norm, head)."""
    weights = list(weights)
    at = 1
    layers = []
    names = {kind: slots for kind, slots in KINDS.values()}
    for kind in layer_kinds(cfg):
        slots = ("norm",) + names[kind]
        layers.append(dict(zip(slots, weights[at:at + len(slots)]),
                           kind=kind))
        at += len(slots)
    assert at + 2 == len(weights), (at + 2, len(weights))
    return weights[0], layers, weights[at], weights[at + 1]


class _Precision:
    def __init__(self, name):
        assert name in ("exact", "stated", "bfloat16"), name
        self.name = name
        self.low = jnp.bfloat16 if name != "exact" else jnp.float32
        # the type of the residual stream, the norms, the softmax, the
        # router, dt, the decay and the state
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


def rms_norm(x, scale, eps, pr):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(pr.island)


def short_conv(x, w, bias, pr, mutate):
    """x [T, channels] (kept), w [taps, channels], bias [channels]: silu of
    each channel's own filter over the current token (the last tap) and the
    ``taps - 1`` before it (after it under ``conv_looks_ahead``), plus the
    bias."""
    taps, t = w.shape[0], x.shape[0]
    xf = x.astype(jnp.float32)
    if mutate == "conv_looks_ahead":
        wide = jnp.pad(xf, ((0, taps - 1), (0, 0)))
        y = sum(wide[j:j + t] * w[taps - 1 - j] for j in range(taps))
    else:
        wide = jnp.pad(xf, ((taps - 1, 0), (0, 0)))
        y = sum(wide[j:j + t] * w[j] for j in range(taps))
    if mutate != "conv_bias_left_out":
        y = y + bias
    return pr.kept(jax.nn.silu(y))


def head_groups(heads, groups, mutate=None):
    """The group each head reads: blocked, ``h // (heads / groups)``."""
    h = np.arange(heads)
    return h % groups if mutate == "group_by_modulo" \
        else h // (heads // groups)


def ssd_recurrence(x, dt, a, b, c, d, pr, mutate=None):
    """The state-space recurrence, token by token. x [T, H, P], dt [T, H]
    (after softplus), a [H] (negative), b, c [T, G, N], d [H] -> y [T, H,
    P] float32. The state [H, P, N] is float32 (``pr.island``)."""
    hi = jax.lax.Precision.HIGHEST
    heads, groups = x.shape[1], b.shape[1]
    group = head_groups(heads, groups, mutate)
    if mutate == "decay_one_for_all_heads":
        a = jnp.broadcast_to(jnp.mean(a), a.shape)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = pr.held(jnp.exp(dt_t * a))[:, None, None]
        step_in = x_t if mutate == "dt_left_out_of_input" \
            else x_t * dt_t[:, None]
        update = step_in[:, :, None] * b_t[group][:, None, :]
        if mutate == "decay_after_update":
            state = decay * (state + update)
        else:
            state = decay * state + update
        state = pr.held(state)
        y = jnp.einsum("hpn,hn->hp", state, c_t[group], precision=hi)
        if mutate != "skip_left_out":
            y = y + d[:, None] * x_t
        return state, y

    n = b.shape[-1]
    _, y = jax.lax.scan(
        step, jnp.zeros((heads, x.shape[2], n), jnp.float32),
        (x, dt, b, c))
    return y


def gated_group_norm(y, z, scale, groups, eps, pr, mutate=None):
    """y, z [T, width] -> RMSNorm over each of ``groups`` slices of (y *
    silu(z)), times the per-channel scale."""
    yf, zf = y.astype(jnp.float32), z.astype(jnp.float32)
    gate = jax.nn.sigmoid(zf) if mutate == "sigmoid_gate" \
        else jax.nn.silu(zf)
    if mutate != "gate_after_norm":
        yf = yf * gate
    if mutate == "norm_over_all_channels":
        groups = 1
    g = yf.reshape(yf.shape[0], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    out = g.reshape(yf.shape) * scale.astype(jnp.float32)
    if mutate == "gate_after_norm":
        out = out * gate
    return pr.kept(out)


def mamba(cfg, layer, u, pr, mutate):
    """u [T, hidden], already normed -> [T, hidden]."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, t = heads * p, u.shape[0]
    proj = pr.mm(u, layer["w_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * groups * n],
                  proj[:, 2 * inner + 2 * groups * n:])
    xbc = short_conv(xbc, layer["conv_w"], layer["conv_b"], pr,
                     mutate).astype(jnp.float32)
    x = xbc[:, :inner].reshape(t, heads, p)
    b = xbc[:, inner:inner + groups * n].reshape(t, groups, n)
    c = xbc[:, inner + groups * n:].reshape(t, groups, n)
    dt = pr.held(jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"]))
    a = -jnp.exp(layer["a_log"].astype(jnp.float32))
    y = pr.kept(ssd_recurrence(x, dt, a, b, c,
                               layer["d"].astype(jnp.float32), pr, mutate))
    y = gated_group_norm(y.reshape(t, inner), z, layer["gate_norm"], groups,
                         eps_of(cfg), pr, mutate)
    return pr.mm(y, layer["w_out"])


def _rotated(x, theta, pr):
    """x [T, heads, d] turned whole by its positions (``rotate_half``): the
    ``rotary_applied`` mutation's alone, the model rotates nothing."""
    t, _, d = x.shape
    freq = 1.0 / float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]
    angles = jnp.concatenate([angles, angles], -1)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    return pr.kept(xf * jnp.cos(angles)
                   + jnp.concatenate([-b, a], -1) * jnp.sin(angles))


def attention(cfg, layer, u, pr, mutate):
    """u [T, hidden], already normed -> [T, hidden], by the definition, in
    blocks of query rows."""
    t = u.shape[0]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    q = pr.mm(u, layer["w_q"]).reshape(t, heads, d)
    k = pr.mm(u, layer["w_k"]).reshape(t, kv, d)
    v = pr.mm(u, layer["w_v"]).reshape(t, kv, d)
    if mutate == "rotary_applied":
        theta = cfg.get("rope_theta", 10000)
        q, k = _rotated(q, theta, pr), _rotated(k, theta, pr)
    of = np.arange(heads) % kv if mutate == "kv_head_by_modulo" \
        else np.arange(heads) // (heads // kv)
    k, v = k[:, of], v[:, of]
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
    return pr.mm(out, layer["w_o"])


def relu2_mlp(x, w_up, w_down, pr, mutate=None):
    """``W_down relu(W_up x)^2``, no gate."""
    up = pr.mm(x, w_up).astype(jnp.float32)
    if mutate == "relu_for_relu2":
        act = jax.nn.relu(up)
    elif mutate == "gated_expert":          # the one matrix as its own gate
        act = jax.nn.silu(up) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    return pr.mm(pr.kept(act), w_down)


def route(cfg, layer, x, pr, mutate):
    """(weights over all routed experts [T, routed], zero off the top k;
    the balance term; assignments per routed expert [routed]; top-k ids)."""
    routed, k = cfg["num_experts_routed"], cfg["num_experts_per_tok"]
    logits = jnp.dot(x.astype(pr.island), layer["router"].astype(pr.island),
                     precision=jax.lax.Precision.HIGHEST).astype(pr.island)
    sigmoid = mutate != "softmax_scores"
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
    held = layer["e_up"].shape[0]
    weight, aux, counts, top = route(cfg, layer, x, pr, mutate)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):                         # dense: every token
        w = weight[:, (offset + e) % routed, None].astype(jnp.float32)
        y = y + w * relu2_mlp(x, layer["e_up"][e], layer["e_down"][e], pr,
                              mutate).astype(jnp.float32)
    y = y.astype(pr.island)
    if mutate != "shared_expert_left_out":
        y = y + relu2_mlp(x, layer["s_up"], layer["s_down"], pr, mutate)
    return y, aux, counts[offset:offset + held], top, counts


def bias_update(bias, counts, rate):
    """The selection bias after a step whose assignments per routed expert
    were ``counts``."""
    counts = jnp.asarray(counts, jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def layer_forward(cfg, layer, x, pr, mutate):
    u = rms_norm(x, layer["norm"], eps_of(cfg), pr)
    if layer["kind"] == "mamba":
        return x + mamba(cfg, layer, u, pr, mutate), None
    if layer["kind"] == "attention":
        return x + attention(cfg, layer, u, pr, mutate), None
    y, aux, load, top, counts = experts(cfg, layer, u, pr, mutate)
    return x + y, (aux, load, top, counts)


def forward(cfg, weights, tokens, precision="exact", mutate=None):
    """tokens [T] int -> (logits [T, vocab] float32, mean balance term, and
    per expert layer: rows per held expert, top-k ids, assignments per
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
    logits = pr.mm(rms_norm(x, final, eps_of(cfg), pr),
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

"""Tiny parameterized training programs (model + optimizer) + matching feeds.

Each builder returns (main_program, startup_program, avg_loss_var). They are
the op-mix slices of the flagship benchmark / book models at toy shapes:

* build_mlp           — fc stack + softmax CE (recognize_digits MLP path)
* build_convnet_slice — conv+BN (NHWC) bottleneck with residual add, pooling,
                        fc head, momentum (bench.py resnet50 cut down)
* build_seq_slice     — ragged LoD tokens -> embedding -> fc -> dynamic GRU ->
                        per-token CE, Adam (machine_translation encoder mix)
"""

from __future__ import annotations

import numpy as np


def build_mlp(dim=16, classes=4, hidden=32, opt="momentum", lr=0.1, seed=7,
              depth=1, return_logits=False):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[dim])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        h = img
        for _ in range(depth):
            h = fluid.layers.fc(h, size=hidden, act="relu")
        logits = fluid.layers.fc(h, size=classes, act=None)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        if opt == "momentum":
            fluid.optimizer.Momentum(learning_rate=lr, momentum=0.9).minimize(
                loss, startup)
        else:
            fluid.optimizer.Adam(learning_rate=min(lr, 1e-2)).minimize(
                loss, startup)
    if return_logits:
        return main, startup, loss, logits
    return main, startup, loss


def mlp_feed(batch, dim=16, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "img": rng.normal(0, 1, (batch, dim)).astype("float32"),
        "label": rng.randint(0, classes, (batch, 1)).astype("int64"),
    }


def build_convnet_slice(size=8, classes=4, nf=8, lr=0.05, seed=7,
                        bottleneck=False):
    """conv+BN NHWC + residual + pools + fc + momentum. With ``bottleneck``,
    adds the stem/1x1-3x3-1x1/projection structure of bench.py's ResNet."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed

    def conv_bn(x, filters, k, stride=1, act="relu"):
        c = fluid.layers.conv2d(x, num_filters=filters, filter_size=k,
                                stride=stride, padding=(k - 1) // 2,
                                bias_attr=False, data_format="NHWC")
        return fluid.layers.batch_norm(c, act=act, data_layout="NHWC")

    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", shape=[size, size, 3])
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        if bottleneck:
            stem = conv_bn(img, nf, 3, stride=2)
            pool = fluid.layers.pool2d(stem, pool_size=3, pool_stride=2,
                                       pool_padding=1, pool_type="max",
                                       data_format="NHWC")
            b = conv_bn(pool, nf // 2, 1)
            b = conv_bn(b, nf // 2, 3)
            b = conv_bn(b, nf * 2, 1, act=None)
            short = conv_bn(pool, nf * 2, 1, act=None)
            x = fluid.layers.elementwise_add(x=b, y=short, act="relu")
        else:
            c = conv_bn(img, nf, 3)
            c2 = conv_bn(c, nf, 3, act=None)
            x = fluid.layers.elementwise_add(x=c2, y=c, act="relu")
            x = fluid.layers.pool2d(x, pool_size=2, pool_stride=2,
                                    pool_type="avg", data_format="NHWC")
        x = fluid.layers.pool2d(x, pool_size=2, global_pooling=True,
                                pool_type="avg", data_format="NHWC")
        logits = fluid.layers.fc(x, size=classes, act=None)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Momentum(learning_rate=lr, momentum=0.9).minimize(
            loss, startup)
    return main, startup, loss


def convnet_feed(batch, size=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "img": rng.normal(0, 1, (batch, size, size, 3)).astype("float32"),
        "label": rng.randint(0, classes, (batch, 1)).astype("int64"),
    }


def build_seq_slice(vocab=12, emb=8, hid=8, lr=1e-2, seed=7):
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        src = fluid.layers.data("src", shape=[1], dtype="int64", lod_level=1)
        tgt = fluid.layers.data("tgt", shape=[1], dtype="int64", lod_level=1)
        e = fluid.layers.embedding(src, size=[vocab, emb])
        h = fluid.layers.fc(e, size=hid * 3)
        h = fluid.layers.dynamic_gru(h, size=hid)
        logits = fluid.layers.fc(h, size=vocab, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=logits, label=tgt))
        fluid.optimizer.Adam(learning_rate=lr).minimize(loss, startup)
    return main, startup, loss


def seq_feed(batch, vocab=12, min_len=2, max_len=7, seed=0):
    rng = np.random.RandomState(seed)
    lens = [int(rng.randint(min_len, max_len)) for _ in range(batch)]
    seqs = [rng.randint(0, vocab, (ln, 1)).astype("int64") for ln in lens]
    return {"src": list(seqs), "tgt": list(seqs)}


def build_tiny_lm(vocab=32, emb=16, heads=2, n_layers=2, max_pos=256,
                  seed=7):
    """Decoder-only LM at toy scale — the generative-serving test/bench
    model: token + learned position embeddings, ``n_layers`` pre-LN-free
    transformer blocks (fc q/k/v -> causal_self_attention -> residual +
    layer_norm -> 2x fc MLP -> residual + layer_norm), vocab logits head.
    Feeds ``tokens``/``positions`` [b, seq, 1] int64, fetches logits
    [b, seq, vocab] — exactly the generative-bundle convention
    serving/generate documents. Returns (main, startup, logits_var)."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data("tokens", shape=[-1, 1], dtype="int64")
        positions = fluid.layers.data("positions", shape=[-1, 1],
                                      dtype="int64")
        x = fluid.layers.elementwise_add(
            fluid.layers.embedding(tokens, size=[vocab, emb]),
            fluid.layers.embedding(positions, size=[max_pos, emb]))
        for _ in range(n_layers):
            q = fluid.layers.fc(x, size=emb, num_flatten_dims=2)
            k = fluid.layers.fc(x, size=emb, num_flatten_dims=2)
            v = fluid.layers.fc(x, size=emb, num_flatten_dims=2)
            a = fluid.layers.causal_self_attention(q, k, v, num_heads=heads)
            x = fluid.layers.layer_norm(
                fluid.layers.elementwise_add(x, a), begin_norm_axis=2)
            h = fluid.layers.fc(x, size=emb * 2, num_flatten_dims=2,
                                act="relu")
            h = fluid.layers.fc(h, size=emb, num_flatten_dims=2)
            x = fluid.layers.layer_norm(
                fluid.layers.elementwise_add(x, h), begin_norm_axis=2)
        logits = fluid.layers.fc(x, size=vocab, num_flatten_dims=2)
    return main, startup, logits


def export_tiny_lm(dirname, scope=None, **kw):
    """Build + init + save_inference_model a tiny LM bundle at
    ``dirname``; returns the scope holding its parameters (for reference
    full-window runs in parity tests)."""
    import paddle_tpu.fluid as fluid

    main, startup, logits = build_tiny_lm(**kw)
    exe = fluid.Executor()
    scope = scope or fluid.Scope()
    exe.run(startup, scope=scope)
    fluid.io.save_inference_model(dirname, ["tokens", "positions"],
                                  [logits], exe, main, scope=scope)
    return main, scope, logits


def build_mellum2_lm(cfg, length, batch=1):
    """The Mellum2 block stack (JetBrains/Mellum2-12B-A2.5B's published
    ``config.json`` keys) as a Fluid program, and the chip's share of it:
    ``num_hidden_layers`` pre-norm blocks — RMSNorm -> q/k/v ``fc`` (no
    bias) -> rotary (plain on a ``sliding_attention`` layer, YaRN on a
    ``full_attention`` one) -> grouped-query causal attention (window
    ``sliding_window`` on a sliding layer) -> o ``fc`` -> residual; RMSNorm
    -> ``routed_experts`` holding ``num_experts`` of the router's
    ``num_experts_routed`` from ``expert_offset`` -> residual — then the
    final RMSNorm and an untied head over ``vocab_size`` rows. The loss is
    the mean next-token cross-entropy plus ``balance_loss_coef`` times the
    layers' mean load-balancing term. The plain reference is
    ``testing/reference/mellum2.py``; parameters are created in the order
    its ``unpack`` reads. Feeds ``tokens`` and ``labels`` [batch, length,
    1] int64. Returns (main, startup, loss, logits [batch, length, vocab],
    [expert_load of each layer])."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.initializer import Normal
    from paddle_tpu.fluid.param_attr import ParamAttr

    layers = fluid.layers
    hidden, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def init(std=cfg.get("init_std", 0.02)):
        return ParamAttr(initializer=Normal(0.0, std))

    def proj(x, size):
        return layers.fc(x, size, num_flatten_dims=2, param_attr=init(),
                         bias_attr=False)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        labels = layers.data("labels", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        x = layers.embedding(
            tokens, size=(cfg["vocab_size"], hidden),
            param_attr=init(cfg.get("embedding_init_std",
                                    cfg.get("init_std", 0.02))))
        aux, loads = [], []
        for i in range(cfg["num_hidden_layers"]):
            kind = cfg["layer_types"][i]
            rope = cfg["rope_parameters"][kind]
            xn = layers.rms_norm(x, epsilon=cfg["rms_norm_eps"])
            q, k, v = (proj(xn, heads * d), proj(xn, kv_heads * d),
                       proj(xn, kv_heads * d))
            q, k = layers.rotary_embedding(
                q, k, head_dim=d, theta=rope["rope_theta"],
                rope_type=rope["rope_type"], factor=rope.get("factor", 1.0),
                original_max_position=rope.get(
                    "original_max_position_embeddings", 0),
                beta_fast=rope.get("beta_fast", 32.0),
                beta_slow=rope.get("beta_slow", 1.0),
                attention_factor=rope.get("attention_factor", 1.0))
            a = layers.causal_self_attention(
                q, k, v, num_heads=heads, num_kv_heads=kv_heads,
                window=cfg["sliding_window"]
                if kind == "sliding_attention" else 0)
            x = layers.elementwise_add(x, proj(a, hidden))
            y, load, balance = layers.routed_experts(
                layers.rms_norm(x, epsilon=cfg["rms_norm_eps"]),
                num_experts=cfg["num_experts_routed"],
                top_k=cfg["num_experts_per_tok"],
                expert_width=cfg["moe_intermediate_size"],
                held_experts=cfg["num_experts"],
                expert_offset=cfg.get("expert_offset", 0),
                norm_topk_prob=cfg.get("norm_topk_prob", True),
                row_buffer_factor=cfg.get("row_buffer_factor", 2.0),
                router_task_gradient=cfg.get("router_task_gradient", True),
                param_attr=init())
            x = layers.elementwise_add(x, y)
            aux.append(balance)
            loads.append(load)
        logits = layers.fc(
            layers.rms_norm(x, epsilon=cfg["rms_norm_eps"]),
            cfg["vocab_size"], num_flatten_dims=2, bias_attr=False,
            param_attr=init(cfg.get("head_init_std",
                                    cfg.get("init_std", 0.02))))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        coef = cfg.get("balance_loss_coef", 0.0)
        if coef:
            loss = layers.elementwise_add(loss, layers.scale(
                layers.mean(layers.sums(aux)), scale=coef / len(aux)))
    return main, startup, loss, logits, loads


def build_kimi_linear_lm(cfg, length, batch=1):
    """The Kimi-Linear block stack (``model_type`` ``kimi_linear``, its
    published ``config.json`` keys) as a Fluid program, and the chip's share
    of it: ``num_hidden_layers`` pre-norm blocks whose attention is, by
    ``linear_attn_config`` (``kda_layers`` / ``full_attn_layers``, counted
    from 1), Kimi Delta Attention (``layers.kda_attention``: the gated delta
    rule in chunks, ``num_heads`` heads of ``head_dim``, convolutions of
    ``short_conv_kernel_size`` taps) or latent attention without positions
    (``layers.latent_attention``: ``q_lora_rank`` null, ``kv_lora_rank``,
    heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for queries and
    keys and ``v_head_dim`` for values, one shared key slice), and whose MLP
    is a dense gated-SiLU one of ``intermediate_size`` in the first
    ``first_k_dense_replace`` blocks and ``routed_experts`` after (scores by
    ``moe_router_activation_func``, a selection bias with its own update,
    top ``num_experts_per_token`` renormalised times
    ``routed_scaling_factor``) holding ``num_experts`` of the router's
    ``num_experts_routed`` from ``expert_offset``, beside
    ``num_shared_experts`` shared experts as one gated MLP. Then the final
    RMSNorm and an untied head over ``vocab_size`` rows. The loss is the
    mean next-token cross-entropy plus ``balance_loss_coef`` times the
    sparse layers' mean balance term. The plain reference is
    ``testing/reference/kimi_linear.py``; parameters are created in the
    order its ``unpack`` reads. Feeds ``tokens`` and ``labels`` [batch,
    length, 1] int64. Returns (main, startup, loss, logits [batch, length,
    vocab], [expert_load of each sparse layer])."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.initializer import Normal
    from paddle_tpu.fluid.param_attr import ParamAttr
    from paddle_tpu.testing.reference.kimi_linear import layer_kinds

    layers = fluid.layers
    hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    lin = cfg["linear_attn_config"]
    std = cfg.get("init_std", 0.02)
    if not cfg.get("mla_use_nope", True):
        raise ValueError("build_kimi_linear_lm: latent attention with "
                         "rotated positions is not built (mla_use_nope)")

    def init(scale=std):
        return ParamAttr(initializer=Normal(0.0, scale))

    aux, loads = [], []

    def block(x, attention, mlp):
        a = layers.rms_norm(x, epsilon=eps)
        if attention == "kda":
            a = layers.kda_attention(
                a, num_heads=lin["num_heads"], head_dim=lin["head_dim"],
                conv_size=lin["short_conv_kernel_size"],
                gate_rank=cfg.get("kda_gate_rank"),
                chunk_size=cfg.get("kda_chunk_size", 64), epsilon=eps,
                param_attr=init(cfg.get("kda_init_std", std)),
                conv_attr=init(cfg.get("conv_init_std", std)))
        else:
            a = layers.latent_attention(
                a, num_heads=cfg["num_attention_heads"],
                q_lora_rank=cfg.get("q_lora_rank"),
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"], epsilon=eps, param_attr=init(),
                down_attr=init(cfg.get("latent_down_init_std", std)),
                up_attr=init(cfg.get("latent_up_init_std", std)))
        x = layers.elementwise_add(x, a)
        b = layers.rms_norm(x, epsilon=eps)
        if mlp == "dense":
            return layers.elementwise_add(
                x, layers.gated_mlp(b, cfg["intermediate_size"], init()))
        y, load, balance = layers.routed_experts(
            b, num_experts=cfg["num_experts_routed"],
            top_k=cfg["num_experts_per_token"],
            expert_width=cfg["moe_intermediate_size"],
            held_experts=cfg["num_experts"],
            expert_offset=cfg.get("expert_offset", 0),
            norm_topk_prob=cfg.get("moe_renormalize", True),
            row_buffer_factor=cfg.get("row_buffer_factor", 2.0),
            router_task_gradient=cfg.get("router_task_gradient", True),
            scoring_func=cfg.get("moe_router_activation_func", "sigmoid"),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            selection_bias=True,
            bias_update_rate=cfg.get("bias_update_rate", 0.0),
            bias_attr=ParamAttr(initializer=Normal(
                cfg.get("selection_bias_init_mean", 0.0),
                cfg.get("selection_bias_init_std", 0.0))),
            param_attr=init(cfg.get("expert_init_std", std)))
        aux.append(balance)
        loads.append(load)
        x = layers.elementwise_add(x, y)
        return layers.elementwise_add(x, layers.gated_mlp(
            b, cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
            init(cfg.get("expert_init_std", std))))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        labels = layers.data("labels", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        x = layers.embedding(
            tokens, size=(cfg["vocab_size"], hidden),
            param_attr=init(cfg.get("embedding_init_std", std)))
        for attention, mlp in layer_kinds(cfg):
            x = block(x, attention, mlp)
        logits = layers.fc(
            layers.rms_norm(x, epsilon=eps), cfg["vocab_size"],
            num_flatten_dims=2, bias_attr=False,
            param_attr=init(cfg.get("head_init_std", std)))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        coef = cfg.get("balance_loss_coef", 0.0)
        if coef and aux:
            loss = layers.elementwise_add(loss, layers.scale(
                layers.mean(layers.sums(aux)), scale=coef / len(aux)))
    return main, startup, loss, logits, loads


def build_nemotron_h_lm(cfg, length, batch=1):
    """The NemotronH layer stack (``model_type`` ``nemotron_h``, its
    published ``config.json`` keys) as a Fluid program, and the chip's share
    of it: ``num_hidden_layers`` layers ``x + mixer(rms_norm(x))``, ONE
    mixer each, its kind the layer's letter in ``hybrid_override_pattern``:
    ``M`` a Mamba-2 mixer (``layers.mamba2_mixer``: ``mamba_num_heads`` heads
    of ``mamba_head_dim``, ``n_groups`` groups of ``ssm_state_size``, a
    convolution of ``conv_kernel`` taps with a bias, the state-space core in
    chunks of ``chunk_size``), ``*`` grouped-query attention without
    positions (``num_attention_heads`` / ``num_key_value_heads`` heads of
    ``head_dim``, nothing rotated), ``E`` ``routed_experts`` (sigmoid scores,
    a selection bias with its own update, top ``num_experts_per_tok``
    renormalised times ``routed_scaling_factor``, un-gated squared-ReLU
    experts of ``moe_intermediate_size``) holding ``n_routed_experts`` of
    the router's ``num_experts_routed`` from ``expert_offset``, beside one
    shared squared-ReLU expert of ``moe_shared_expert_intermediate_size``.
    Then the final RMSNorm and an untied head over ``vocab_size`` rows. The
    out-projections of the Mamba and attention mixers are drawn at
    ``init_std / sqrt(published num_hidden_layers)``
    (``rescale_prenorm_residual``). The loss is the mean next-token
    cross-entropy plus ``balance_loss_coef`` times the expert layers' mean
    balance term. The plain reference is ``testing/reference/nemotron_h.py``;
    parameters are created in the order its ``unpack`` reads. Feeds
    ``tokens`` and ``labels`` [batch, length, 1] int64. Returns (main,
    startup, loss, logits [batch, length, vocab], [expert_load of each
    expert layer])."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.initializer import Normal, Uniform
    from paddle_tpu.fluid.param_attr import ParamAttr
    from paddle_tpu.testing.reference.nemotron_h import eps_of, layer_kinds

    layers = fluid.layers
    hidden, eps = cfg["hidden_size"], eps_of(cfg)
    std = cfg.get("init_std", 0.02)
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    out_scale = depth ** -0.5 if cfg.get("rescale_prenorm_residual", True) \
        else 1.0

    def init(scale=std):
        return ParamAttr(initializer=Normal(0.0, scale))

    def project(x, size, scale=std):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=init(scale))

    # the filter and its bias as the family's framework draws a depthwise
    # convolution: uniform in +-1 / sqrt(taps)
    bound = cfg["conv_kernel"] ** -0.5
    conv = ParamAttr(initializer=Uniform(-bound, bound))
    aux, loads = [], []

    def mixer(kind, u):
        if kind == "mamba":
            return layers.mamba2_mixer(
                u, num_heads=cfg["mamba_num_heads"],
                head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
                state_size=cfg["ssm_state_size"],
                conv_size=cfg["conv_kernel"],
                chunk_size=cfg.get("chunk_size", 128), epsilon=eps,
                conv_bias=cfg.get("use_conv_bias", True),
                time_step_min=cfg.get("time_step_min", 0.001),
                time_step_max=cfg.get("time_step_max", 0.1),
                time_step_floor=cfg.get("time_step_floor", 1e-4),
                param_attr=init(cfg.get("mamba_init_std", std)),
                conv_attr=conv, conv_bias_attr=conv,
                out_attr=init(cfg.get("mamba_out_init_std", std) * out_scale))
        if kind == "attention":
            heads, kv, d = (cfg["num_attention_heads"],
                            cfg["num_key_value_heads"], cfg["head_dim"])
            qk = cfg.get("attention_init_std", std)
            q, k = project(u, heads * d, qk), project(u, kv * d, qk)
            v = project(u, kv * d)
            a = layers.causal_self_attention(q, k, v, num_heads=heads,
                                             num_kv_heads=kv)
            return project(
                a, hidden, cfg.get("attention_out_init_std", std) * out_scale)
        expert = init(cfg.get("expert_init_std", std))
        y, load, balance = layers.routed_experts(
            u, num_experts=cfg["num_experts_routed"],
            top_k=cfg["num_experts_per_tok"],
            expert_width=cfg["moe_intermediate_size"],
            held_experts=cfg["n_routed_experts"],
            expert_offset=cfg.get("expert_offset", 0),
            norm_topk_prob=cfg.get("norm_topk_prob", True),
            row_buffer_factor=cfg.get("row_buffer_factor", 2.0),
            router_task_gradient=cfg.get("router_task_gradient", True),
            scoring_func="sigmoid",
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            selection_bias=True,
            bias_update_rate=cfg.get("bias_update_rate", 0.0),
            bias_attr=ParamAttr(initializer=Normal(
                cfg.get("selection_bias_init_mean", 0.0),
                cfg.get("selection_bias_init_std", 0.0))),
            param_attr=expert, expert_form="relu2")
        aux.append(balance)
        loads.append(load)
        return layers.elementwise_add(y, layers.relu2_mlp(
            u, cfg.get("n_shared_experts", 1)
            * cfg["moe_shared_expert_intermediate_size"], expert))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        labels = layers.data("labels", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        x = layers.embedding(
            tokens, size=(cfg["vocab_size"], hidden),
            param_attr=init(cfg.get("embedding_init_std", std)))
        for kind in layer_kinds(cfg):
            x = layers.elementwise_add(
                x, mixer(kind, layers.rms_norm(x, epsilon=eps)))
        logits = layers.fc(
            layers.rms_norm(x, epsilon=eps), cfg["vocab_size"],
            num_flatten_dims=2, bias_attr=False,
            param_attr=init(cfg.get("head_init_std", std)))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        coef = cfg.get("balance_loss_coef", 0.0)
        if coef and aux:
            loss = layers.elementwise_add(loss, layers.scale(
                layers.mean(layers.sums(aux)), scale=coef / len(aux)))
    return main, startup, loss, logits, loads

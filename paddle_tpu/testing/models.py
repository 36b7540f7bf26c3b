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


def _biased_experts_and_shared(layers, cfg, x, b, expert_attr, routed,
                               top_k, held, renormalize, scoring, shared,
                               aux, loads):
    """``x + routed_experts(b) + gated_mlp(b)``: the sparse feed-forward
    part of the latent-attention families and LFM2 (sigmoid or softmax
    scores, a selection bias with its own update,
    ``routed_scaling_factor``, the ``held`` experts from ``expert_offset``,
    and ``shared`` shared experts as one gated MLP, none where ``shared`` is
    0); the balance term and the held experts' load are appended to ``aux``
    and ``loads``."""
    from paddle_tpu.fluid.initializer import Normal
    from paddle_tpu.fluid.param_attr import ParamAttr

    y, load, balance = layers.routed_experts(
        b, num_experts=routed, top_k=top_k,
        expert_width=cfg["moe_intermediate_size"], held_experts=held,
        expert_offset=cfg.get("expert_offset", 0),
        norm_topk_prob=renormalize,
        row_buffer_factor=cfg.get("row_buffer_factor", 2.0),
        router_task_gradient=cfg.get("router_task_gradient", True),
        scoring_func=scoring,
        routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
        selection_bias=True,
        bias_update_rate=cfg.get("bias_update_rate", 0.0),
        bias_attr=ParamAttr(initializer=Normal(
            cfg.get("selection_bias_init_mean", 0.0),
            cfg.get("selection_bias_init_std", 0.0))),
        param_attr=expert_attr)
    aux.append(balance)
    loads.append(load)
    x = layers.elementwise_add(x, y)
    if not shared:
        return x
    return layers.elementwise_add(x, layers.gated_mlp(
        b, shared * cfg["moe_intermediate_size"], expert_attr))


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
        return _biased_experts_and_shared(
            layers, cfg, x, b, init(cfg.get("expert_init_std", std)),
            routed=cfg["num_experts_routed"],
            top_k=cfg["num_experts_per_token"], held=cfg["num_experts"],
            renormalize=cfg.get("moe_renormalize", True),
            scoring=cfg.get("moe_router_activation_func", "sigmoid"),
            shared=cfg["num_shared_experts"], aux=aux, loads=loads)

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


def build_joyai_llm_flash_lm(cfg, length, batch=1):
    """The JoyAI-LLM-Flash block stack (``model_type`` ``joyai_llm_flash``,
    its published ``config.json`` keys) as a Fluid program, and the chip's
    share of it: ``num_hidden_layers`` pre-norm blocks, each latent
    attention (``layers.latent_attention``: queries through a rank of
    ``q_lora_rank`` with its RMSNorm, ``kv_lora_rank``, heads of
    ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for queries and keys and
    ``v_head_dim`` for values, the rope slices of the queries and the ONE
    shared rope key rotated at ``rope_theta``, adjacent pairs where
    ``rope_interleave``) and a dense gated-SiLU MLP of ``intermediate_size``
    in the first ``first_k_dense_replace`` blocks, ``routed_experts``
    after (sigmoid scores by ``scoring_func``, a selection bias with its own
    update, top ``num_experts_per_tok`` renormalised where
    ``norm_topk_prob`` times ``routed_scaling_factor``) holding
    ``n_routed_experts`` of the router's ``num_experts_routed`` from
    ``expert_offset``, beside ``n_shared_experts`` shared experts as one
    gated MLP. Then the final RMSNorm and an untied head over ``vocab_size``
    rows. With ``num_nextn_predict_layers`` (1) the multi-token-prediction
    module (``layers.multi_token_prediction``: the embedding of the next
    token and the last block's output before the final norm, each normed,
    through ``W_eh``, one more sparse block, its own norm, the SHARED head)
    predicts the token two ahead, and its cross-entropy enters the loss at
    ``mtp_loss_weight`` (``layers.mtp_loss``); its sparse block's row buffer
    is ``mtp_row_buffer_factor`` times the expectation where given (its
    routing can crowd onto the held experts early in training, and a step
    over the capacity is a NaN loss), ``row_buffer_factor`` otherwise. The
    loss is the mean next-token cross-entropy plus ``balance_loss_coef``
    times the sparse blocks' (the module's among them) mean balance term,
    plus the MTP's. The plain reference is
    ``testing/reference/joyai_llm_flash.py``; parameters are created in the
    order its ``unpack`` reads. Feeds
    ``tokens``, ``labels`` and, with the module, ``mtp_labels`` [batch,
    length, 1] int64. Returns (main, startup, loss, logits [batch, length,
    vocab], the MTP's logits or None, [expert_load of each sparse
    block])."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.initializer import Normal
    from paddle_tpu.fluid.param_attr import ParamAttr
    from paddle_tpu.testing.reference.joyai_llm_flash import layer_kinds

    layers = fluid.layers
    hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    std = cfg.get("init_std", 0.02)
    depth = int(cfg.get("num_nextn_predict_layers", 0))
    if depth > 1:
        raise ValueError("build_joyai_llm_flash_lm: MTP depth above 1 is "
                         "not built")

    def init(scale=std):
        return ParamAttr(initializer=Normal(0.0, scale))

    aux, loads = [], []

    def block(x, mlp, c=cfg):
        a = layers.latent_attention(
            layers.rms_norm(x, epsilon=eps),
            num_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
            rope_interleave=cfg.get("rope_interleave", False), epsilon=eps,
            param_attr=init(),
            down_attr=init(cfg.get("latent_down_init_std", std)),
            up_attr=init(cfg.get("latent_up_init_std", std)))
        x = layers.elementwise_add(x, a)
        b = layers.rms_norm(x, epsilon=eps)
        if mlp == "dense":
            return layers.elementwise_add(
                x, layers.gated_mlp(b, cfg["intermediate_size"], init()))
        return _biased_experts_and_shared(
            layers, c, x, b, init(cfg.get("expert_init_std", std)),
            routed=cfg["num_experts_routed"],
            top_k=cfg["num_experts_per_tok"], held=cfg["n_routed_experts"],
            renormalize=cfg.get("norm_topk_prob", True),
            scoring=cfg.get("scoring_func", "sigmoid"),
            shared=cfg["n_shared_experts"], aux=aux, loads=loads)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        def feed(name):
            return layers.data(name, shape=[batch, length, 1], dtype="int64",
                               append_batch_size=False)

        tokens, labels = feed("tokens"), feed("labels")
        x = layers.embedding(
            tokens, size=(cfg["vocab_size"], hidden),
            param_attr=init(cfg.get("embedding_init_std", std)))
        for mlp in layer_kinds(cfg):
            x = block(x, mlp)
        logits = layers.fc(
            layers.rms_norm(x, epsilon=eps), cfg["vocab_size"],
            num_flatten_dims=2, bias_attr=False,
            param_attr=init(cfg.get("head_init_std", std)))
        params = main.global_block().all_parameters()
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, labels))
        mtp_logits = None
        if depth:
            # the module's block has a row buffer of its own (docstring)
            mtp_cfg = dict(cfg, row_buffer_factor=cfg.get(
                "mtp_row_buffer_factor", cfg.get("row_buffer_factor", 2.0)))
            mtp_logits = layers.multi_token_prediction(
                x, labels, params[0], params[-1],
                lambda h: block(h, "sparse", mtp_cfg), epsilon=eps,
                param_attr=init())
        coef = cfg.get("balance_loss_coef", 0.0)
        if coef and aux:
            loss = layers.elementwise_add(loss, layers.scale(
                layers.mean(layers.sums(aux)), scale=coef / len(aux)))
        if depth:
            loss = layers.mtp_loss(loss, mtp_logits, feed("mtp_labels"),
                                   cfg["mtp_loss_weight"], depth)
    return main, startup, loss, logits, mtp_logits, loads


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


def build_lfm2_moe_lm(cfg, length, batch=1):
    """The LFM2-MoE layer stack (``model_type`` ``lfm2_moe``, its published
    ``config.json`` keys) as a Fluid program, and the chip's share of it:
    the published layers ``first_layer`` .. ``first_layer +
    num_hidden_layers - 1`` (counted from 0), each ``x + mixer(rms_norm(x))``
    then ``x + ffn(rms_norm(x))``. The mixer is the layer's ``layer_types``
    entry: ``conv``, the gated short convolution (``layers.short_conv_mixer``:
    ``[B | C | x] = x W_in``, ``C * conv(B * x)`` over ``conv_L_cache`` taps,
    ``W_out``; no bias, no activation), or ``full_attention``
    (``layers.grouped_query_attention``: ``num_attention_heads`` /
    ``num_key_value_heads`` heads of hidden / heads, QK-norm, rotary at
    ``rope_parameters.rope_theta``). The feed-forward part is a dense
    gated-SiLU MLP of ``intermediate_size`` below ``num_dense_layers`` and
    ``routed_experts`` from there (sigmoid scores, a selection bias with its
    own update where ``use_expert_bias``, top ``num_experts_per_tok``
    renormalised where ``norm_topk_prob`` times ``routed_scaling_factor``,
    no shared expert) holding ``num_experts`` of the router's
    ``num_experts_routed`` from ``expert_offset``. Then the final RMSNorm
    (eps ``norm_eps`` everywhere) and an untied head over ``vocab_size``
    rows. The filter is drawn uniform in +-1 / sqrt(taps), as the family's
    framework draws a depthwise convolution; W_q and W_k normal
    (``attention_qk_init_std``), every other projection normal
    (``init_std``). The loss is the mean next-token
    cross-entropy plus ``balance_loss_coef`` times the routed layers' mean
    balance term. The plain reference is ``testing/reference/lfm2_moe.py``;
    parameters are created in the order its ``unpack`` reads. Feeds
    ``tokens`` and ``labels`` [batch, length, 1] int64. Returns (main,
    startup, loss, logits [batch, length, vocab], [expert_load of each
    routed layer])."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.initializer import Normal, Uniform
    from paddle_tpu.fluid.param_attr import ParamAttr
    from paddle_tpu.testing.reference.lfm2_moe import layer_kinds

    layers = fluid.layers
    hidden, eps = cfg["hidden_size"], cfg["norm_eps"]
    heads = cfg["num_attention_heads"]
    std = cfg.get("init_std", 0.02)
    if not cfg.get("use_expert_bias", True):
        raise ValueError("build_lfm2_moe_lm: routing without the expert "
                         "bias is not built (use_expert_bias)")

    def init(scale=std):
        return ParamAttr(initializer=Normal(0.0, scale))

    bound = cfg["conv_L_cache"] ** -0.5
    aux, loads = [], []

    def mixer(kind, a):
        if kind == "conv":
            return layers.short_conv_mixer(
                a, cfg["conv_L_cache"], param_attr=init(),
                conv_attr=ParamAttr(initializer=Uniform(-bound, bound)))
        return layers.grouped_query_attention(
            a, heads, cfg["num_key_value_heads"], hidden // heads,
            rope_theta=cfg["rope_parameters"]["rope_theta"], qk_norm=True,
            epsilon=eps, param_attr=init(),
            qk_attr=init(cfg.get("attention_qk_init_std", std)))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        labels = layers.data("labels", shape=[batch, length, 1],
                             dtype="int64", append_batch_size=False)
        x = layers.embedding(
            tokens, size=(cfg["vocab_size"], hidden),
            param_attr=init(cfg.get("embedding_init_std", std)))
        for kind, mlp in layer_kinds(cfg):
            x = layers.elementwise_add(
                x, mixer(kind, layers.rms_norm(x, epsilon=eps)))
            b = layers.rms_norm(x, epsilon=eps)
            if mlp == "dense":
                x = layers.elementwise_add(
                    x, layers.gated_mlp(b, cfg["intermediate_size"], init()))
                continue
            x = _biased_experts_and_shared(
                layers, cfg, x, b, init(cfg.get("expert_init_std", std)),
                routed=cfg["num_experts_routed"],
                top_k=cfg["num_experts_per_tok"], held=cfg["num_experts"],
                renormalize=cfg.get("norm_topk_prob", True),
                scoring="sigmoid", shared=0, aux=aux, loads=loads)
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

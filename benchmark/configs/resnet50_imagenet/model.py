"""ResNet-50 for ImageNet as the reference's benchmark builds it
(benchmark/paddle/image/resnet.py, layer_num=50): the program through
``paddle_tpu.fluid`` (a copy of the sound builder in ``bench.py``, which
ROADMAP C1 deletes), its seeded synthetic batches, its plain float32
reference, and its training FLOPs from shapes."""

import functools

import jax
import jax.numpy as jnp

STRIDES = (1, 2, 2, 2)


# --------------------------------------------------------------- program
def _conv_bn(fluid, x, filters, size, stride=1, act="relu", layout="NHWC"):
    conv = fluid.layers.conv2d(input=x, num_filters=filters, filter_size=size,
                               stride=stride, padding=(size - 1) // 2,
                               act=None, bias_attr=False, data_format=layout)
    return fluid.layers.batch_norm(input=conv, act=act, data_layout=layout)


def _bottleneck(fluid, x, filters, stride, expansion, layout):
    y = _conv_bn(fluid, x, filters, 1, layout=layout)
    y = _conv_bn(fluid, y, filters, 3, stride=stride, layout=layout)
    y = _conv_bn(fluid, y, filters * expansion, 1, act=None, layout=layout)
    ch_in = x.shape[-1] if layout == "NHWC" else x.shape[1]
    if ch_in != filters * expansion or stride != 1:
        x = _conv_bn(fluid, x, filters * expansion, 1, stride=stride,
                     act=None, layout=layout)
    return fluid.layers.elementwise_add(x=y, y=x, act="relu")


def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward and Momentum.
    No probe: the first loss, 7.6 against the ln 1000 = 6.9 of a network
    that says nothing, carries the network's signal itself."""
    import paddle_tpu.fluid as fluid

    layout, size = cfg["layout"], cfg["image_size"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        shape = [size, size, cfg["channels"]] if layout == "NHWC" \
            else [cfg["channels"], size, size]
        img = fluid.layers.data("img", shape=shape)
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        x = _conv_bn(fluid, img, cfg["widths"][0], 7, stride=2, layout=layout)
        x = fluid.layers.pool2d(input=x, pool_size=3, pool_stride=2,
                                pool_padding=1, pool_type="max",
                                data_format=layout)
        for filters, count, stride in zip(cfg["widths"], cfg["depths"],
                                          STRIDES):
            for i in range(count):
                x = _bottleneck(fluid, x, filters, stride if i == 0 else 1,
                                cfg["bottleneck_expansion"], layout)
        x = fluid.layers.pool2d(input=x, pool_size=7, pool_type="avg",
                                global_pooling=True, data_format=layout)
        logits = fluid.layers.fc(input=x, size=cfg["class_dim"], act=None)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        opt = cfg["optimizer"]
        fluid.optimizer.Momentum(learning_rate=opt["learning_rate"],
                                 momentum=opt["momentum"]).minimize(
                                     loss, startup)
    return main, startup, loss, None


# ------------------------------------------------------------------ data
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _batch(key, batch, size, channels, classes):
    k1, k2 = jax.random.split(key)
    img = jax.random.normal(k1, (batch, size, size, channels), jnp.bfloat16)
    label = jax.random.randint(k2, (batch, 1), 0, classes, jnp.int32)
    return {"img": img, "label": label}


def device_batch(cfg, seed, index, batch, params=None):
    """Batch ``index`` of ``seed``, drawn on the device in one jitted call
    (1024 images are 308 MB; numpy would spend seconds on them)."""
    if cfg["layout"] != "NHWC":
        raise ValueError("the benchmark feeds NHWC images")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), index)
    return _batch(key, int(batch), cfg["image_size"], cfg["channels"],
                  cfg["class_dim"])


def batch_counts(feed):
    """(real samples, real elements) of one feed: images, and images."""
    n = int(feed["label"].shape[0])
    return n, n


# ------------------------------------------------------- plain reference
def _ref_conv(x, w, stride):
    pad = (w.shape[2] - 1) // 2
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _ref_bn(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def reference_loss(cfg, weights, feed):
    """Forward pass and mean cross-entropy in float32 at highest matmul
    precision under batch statistics. ``weights`` is the program's
    parameters in creation order: per conv+bn a filter (OIHW), scale, bias,
    moving mean and variance (unused in training), then the fc pair."""
    it = iter(weights)

    def conv_bn(x, stride, relu):
        w, scale, bias, _mean, _var = (next(it) for _ in range(5))
        y = _ref_bn(_ref_conv(x, w, stride), scale, bias)
        return jax.nn.relu(y) if relu else y

    with jax.default_matmul_precision("highest"):
        x = feed["img"].astype(jnp.float32)
        x = conv_bn(x, 2, True)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
            [(0, 0), (1, 1), (1, 1), (0, 0)])
        exp = cfg["bottleneck_expansion"]
        for filters, count, stride in zip(cfg["widths"], cfg["depths"],
                                          STRIDES):
            for i in range(count):
                s = stride if i == 0 else 1
                y = conv_bn(x, 1, True)
                y = conv_bn(y, s, True)
                y = conv_bn(y, 1, False)
                if x.shape[-1] != filters * exp or s != 1:
                    x = conv_bn(x, s, False)
                x = jax.nn.relu(y + x)
        x = jnp.mean(x, axis=(1, 2))
        w, b = next(it), next(it)
        logp = jax.nn.log_softmax(x @ w + b, axis=-1)
        label = feed["label"].reshape(-1)
        return -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=1))


def run_reference(cfg, weights, feed):
    """(the reference's loss as a float, no probes). Jitted so that it fits:
    a feed that is spread over several chips stays spread (the batch
    statistics are then reduced over the whole global batch, as GSPMD does in
    the system)."""
    return float(jax.jit(functools.partial(reference_loss, cfg))(
        list(weights), feed)), None


# ----------------------------------------------------------------- FLOPs
def forward_flops_per_sample(cfg):
    """(all layers, the stem alone): multiply-adds x 2 of every convolution
    and the fc for one image; batch-norm, activations and pooling are not
    counted."""
    size = cfg["image_size"]
    flops = 0

    def conv(hw, cin, cout, k, stride):
        out = -(-hw // stride)
        return out, 2 * out * out * k * k * cin * cout

    hw, stem = conv(size, cfg["channels"], cfg["widths"][0], 7, 2)
    flops += stem
    hw = -(-hw // 2)                                   # max pool
    cin, exp = cfg["widths"][0], cfg["bottleneck_expansion"]
    for filters, count, stride in zip(cfg["widths"], cfg["depths"], STRIDES):
        for i in range(count):
            s = stride if i == 0 else 1
            _, f1 = conv(hw, cin, filters, 1, 1)
            out, f2 = conv(hw, filters, filters, 3, s)
            _, f3 = conv(out, filters, filters * exp, 1, 1)
            flops += f1 + f2 + f3
            if cin != filters * exp or s != 1:
                flops += conv(hw, cin, filters * exp, 1, s)[1]
            hw, cin = out, filters * exp
    return flops + 2 * cin * cfg["class_dim"], stem


def train_flops(cfg, feed):
    """Training FLOPs the forward and backward passes need for one feed:
    three times the forward (the backward computes two products of the same
    size per layer), less the stem's input gradient, which nobody needs;
    nothing recomputed is counted."""
    fwd, stem = forward_flops_per_sample(cfg)
    return (3 * fwd - stem) * batch_counts(feed)[0]

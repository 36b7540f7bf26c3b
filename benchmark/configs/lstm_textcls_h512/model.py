"""The reference's RNN benchmark model (benchmark/paddle/rnn/rnn.py):
embedding(128) -> 2 x [fc(4h) + lstm(h)] -> last step -> fc softmax over 2
classes, Adam. The program through ``paddle_tpu.fluid`` (a copy of the sound
builder in ``bench.py``), seeded synthetic sequences, a plain float32
reference that runs each sequence for exactly its own length, and training
FLOPs from the sequences' real lengths."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

LENGTH_SEED = 0         # the corpus's set of lengths is the same for every --seed


# --------------------------------------------------------------- program
def build(cfg):
    """(main, startup, loss, probe) — forward, loss, backward and Adam. The
    probe is the classifier's logits, one row per sequence: with random
    weights every loss is ln 2 give or take 2e-5 whatever the network
    computes, and the logits are where its signal is."""
    import paddle_tpu.fluid as fluid

    hidden = cfg["hidden"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        words = fluid.layers.data("words", shape=[1], dtype="int64",
                                  lod_level=1)
        label = fluid.layers.data("label", shape=[1], dtype="int64")
        net = fluid.layers.embedding(words,
                                     size=(cfg["vocab"], cfg["emb_dim"]))
        for _ in range(cfg["lstm_num"]):
            proj = fluid.layers.fc(net, hidden * 4)
            net, _ = fluid.layers.dynamic_lstm(proj, size=hidden * 4)
        last = fluid.layers.sequence_last_step(net)
        logits = fluid.layers.fc(last, cfg["class_dim"])
        probs = fluid.layers.softmax(logits)         # fc(act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(probs, label))
        fluid.optimizer.Adam(
            learning_rate=cfg["optimizer"]["learning_rate"]).minimize(
                loss, startup)
    return main, startup, loss, logits


# ------------------------------------------------------------------ data
def labels_for(last_tokens, cfg, params, rng):
    """The class is which of ``class_dim`` equal slices of the vocabulary
    the sequence's last real token falls in — a function of the tokens that
    an LSTM learns within some hundred steps, so the loss can fall — and a
    share ``label_noise`` (traffic file) of the labels is then redrawn at
    random. Every sequence is in the data ``copies`` times, each copy with
    its own redrawn labels: what differs between copies cannot be memorised,
    so the loss settles at the noise's entropy (0.2 at a tenth redrawn) and
    not at 0, where Adam drifts (measured on the chip on two memorised
    batches: 0.0000, then 9.1)."""
    n, classes = len(last_tokens), cfg["class_dim"]
    drawn = rng.randint(0, classes, n).astype(np.int32)
    labels = (np.asarray(last_tokens, np.int64) * classes
              // cfg["vocab"]).astype(np.int32)
    redraw = rng.random_sample(n) < float(params["label_noise"])
    return np.where(redraw, drawn, labels)


def _lod(tokens, lens):
    from paddle_tpu.core.lod import LoDArray
    return LoDArray(tokens, lens)


def device_batch(cfg, seed, index, batch, params):
    """Batch ``index`` of ``seed``: ``batch`` sequences, all of length
    ``params['length']`` (the reference's pad_seq=True). The ring's
    ``ring // copies`` distinct batches of tokens come round ``copies``
    times, each time with labels of their own. Token ids are drawn on the
    host (a batch is under a megabyte) and put on the device once."""
    length = int(params["length"])
    distinct = max(1, int(params["ring"]) // int(params["copies"]))
    tokens = np.random.RandomState([seed, index % distinct]).randint(
        0, cfg["vocab"], (batch, length)).astype(np.int32)
    lens = np.full((batch,), length, np.int32)
    labels = labels_for(tokens[:, -1], cfg, params,
                        np.random.RandomState([seed, index, 1]))
    return jax.device_put({"words": _lod(tokens[..., None], lens),
                           "label": labels[:, None]})


def corpus(cfg, rng, params):
    """A seeded corpus of ``n_sequences`` (tokens [len, 1] int32, label)
    samples: ``n_sequences // copies`` distinct sequences, each ``copies``
    times over, shuffled. The lengths are log-normal (``length_median``,
    ``length_sigma``) clipped to ``length_min``..``length_max`` and drawn
    from ``LENGTH_SEED``, so every --seed gets the SAME set of lengths, in
    another order and with other tokens: the seed moves no work."""
    copies = int(params["copies"])
    n = int(params["n_sequences"]) // copies
    lens = np.exp(np.random.RandomState(LENGTH_SEED).normal(
        np.log(params["length_median"]), params["length_sigma"], n))
    lens = np.clip(np.rint(lens), params["length_min"],
                   params["length_max"]).astype(np.int64)
    lens = rng.permutation(lens)
    flat = rng.randint(0, cfg["vocab"], int(lens.sum())).astype(np.int32)
    ends = np.cumsum(lens)
    seqs = np.split(flat[:, None], ends[:-1])
    order = rng.permutation(np.tile(np.arange(n), copies))
    labels = labels_for(flat[ends - 1][order], cfg, params, rng)
    return [(seqs[j], l) for j, l in zip(order, labels.tolist())]


def collate(cfg, samples, bound):
    """A list of (tokens, label) samples -> the program's host feed, padded
    to ``bound`` by the system's own ``pack_sequences``."""
    from paddle_tpu.core.lod import pack_sequences
    return {"words": pack_sequences([s for s, _ in samples], max_len=bound),
            "label": np.asarray([[l] for _, l in samples], np.int32)}


def sample_length(sample):
    return len(sample[0])


def batch_counts(feed):
    """(real samples, real tokens) of one feed."""
    lens = np.asarray(feed["words"].lens)
    return int(lens.shape[0]), int(lens.sum())


# ------------------------------------------------------- plain reference
def _group_forward(cfg, precision, weights, tokens, labels, length):
    """(cross-entropy, logits) of each of ``tokens``' sequences, every one
    exactly ``length`` steps long: the loop runs ``length`` steps and reads
    nothing beyond them. ``tokens`` is [n, max_len]. Computes in the
    weights' type (float32, but for the tests that stand a lower precision
    in for the system) with matrix products at ``precision``."""
    emb, *layers, w_out, b_out = weights
    hidden = cfg["hidden"]
    n = tokens.shape[0]
    with jax.default_matmul_precision(precision):
        x_all = emb[tokens]                               # [n, max_len, e]

        def step(t, carry):
            x = jax.lax.dynamic_index_in_dim(x_all, t, axis=1, keepdims=False)
            new = []
            for (w_in, b_in, w_rec, b_rec), (h, c) in zip(layers, carry):
                gates = x @ w_in + b_in + h @ w_rec + b_rec.reshape(-1)
                i = jax.nn.sigmoid(gates[:, :hidden])
                f = jax.nn.sigmoid(gates[:, hidden:2 * hidden])
                g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
                o = jax.nn.sigmoid(gates[:, 3 * hidden:])
                c = f * c + i * g
                h = o * jnp.tanh(c)
                new.append((h, c))
                x = h
            return tuple(new)

        zeros = jnp.zeros((n, hidden), emb.dtype)
        carry = jax.lax.fori_loop(
            0, length, step, tuple((zeros, zeros) for _ in layers))
        logits = (carry[-1][0] @ w_out + b_out).astype(jnp.float32)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   labels[:, None], axis=1)
        return nll[:, 0], logits


def _forward(cfg, precision, weights, feed):
    """(cross-entropy [n], logits [n, classes]) of one feed, sequence by
    sequence and unpadded: sequences of one length go through
    ``_group_forward`` together, in slabs of an eighth of the batch (one
    compiled program for every length and slab; a short slab repeats a
    sequence and the repeats are dropped)."""
    tokens = np.asarray(feed["words"].data)[..., 0]
    lens = np.asarray(feed["words"].lens)
    labels = np.asarray(feed["label"]).reshape(-1)
    slab = max(1, len(lens) // 8)
    fn = jax.jit(functools.partial(_group_forward, cfg, precision))
    nll = np.zeros(len(lens), np.float64)
    logits = np.zeros((len(lens), cfg["class_dim"]), np.float32)
    for length in np.unique(lens):
        idx = np.nonzero(lens == length)[0]
        for lo in range(0, len(idx), slab):
            part = idx[lo:lo + slab]
            pad = np.resize(part, slab)
            a, b = fn(weights, tokens[pad], labels[pad], np.int32(length))
            nll[part] = np.asarray(a)[:len(part)]
            logits[part] = np.asarray(b)[:len(part)]
    return nll, logits


def run_reference(cfg, weights, feed, dtype=jnp.float32):
    """(mean loss, probes) of one feed. The loss and ``logits`` are the
    exact arithmetic's (``highest`` matmul precision); ``logits_as_stated``
    is the same plain code at the matmul precision the configuration states
    (``reference.stated_matmul_precision``: the platform's default passes,
    which on a TPU round the products' inputs to bfloat16), so that the
    system is held to the precision it states and not only to being near."""
    emb, *rest = [jnp.asarray(w, dtype) for w in weights]
    layers = [tuple(rest[4 * k:4 * k + 4]) for k in range(cfg["lstm_num"])]
    weights = (emb, *layers, rest[-2], rest[-1])
    nll, logits = _forward(cfg, "highest", weights, feed)
    _, stated = _forward(
        cfg, cfg["reference"]["stated_matmul_precision"], weights, feed)
    return float(nll.mean()), {"logits": logits, "logits_as_stated": stated}


# ----------------------------------------------------------------- FLOPs
def train_flops(cfg, feed):
    """Training FLOPs the forward and backward passes need for one feed,
    from each sequence's REAL length (padding earns nothing): per real token
    and layer the input projection and the recurrent product, 2 x in x 4h
    and 2 x h x 4h, plus the classifier once per sequence; times three for
    forward and backward, less the first projection's input gradient (the
    embedding's gradient is a scatter, not a product). Gate arithmetic and
    the embedding gather are not counted."""
    n, tokens = batch_counts(feed)
    h, e = cfg["hidden"], cfg["emb_dim"]
    fwd_token, first_proj = 0, 2 * e * 4 * h
    for k in range(cfg["lstm_num"]):
        fwd_token += 2 * (e if k == 0 else h) * 4 * h + 2 * h * 4 * h
    fwd_seq = 2 * h * cfg["class_dim"]
    return (3 * fwd_token - first_proj) * tokens + 3 * fwd_seq * n

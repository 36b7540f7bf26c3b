"""Expert parallelism: a mixture-of-experts FFN sharded over an ``ep`` mesh
axis.

The reference predates MoE entirely (SURVEY.md §2.3: expert parallelism
listed as TPU-native new work, "megablocks-style EP if desired"); its
closest capability is the sparse distributed lookup table. This module is
the TPU-first construction: top-1 token routing with a fixed per-expert
capacity (static shapes — the GShard/mesh-tensorflow dispatch-einsum
formulation), experts' weights sharded over ``ep``, and the token
shuffle expressed as plain einsums under GSPMD sharding constraints so XLA
inserts the all-to-all collectives over ICI.

    mesh = make_mesh(8, axes=("ep",))
    out, aux_loss = moe_ffn(x, params, mesh)    # x [tokens, d]

Routing uses a softmax gate; ``aux_loss`` is the standard load-balancing
term (mean fraction * mean gate mass per expert, scaled by E) to train
against expert collapse. Dropped tokens (over capacity) pass through the
residual (output 0 for their expert contribution), the GShard policy.

This is a ``jax``-level function with no ``fluid.Program`` behind it. The
Program-level expert layer is the ``routed_experts`` op
(``ops/moe_ops.py``, ``fluid.layers.routed_experts``): top-k routing, no
dropped rows, told which experts it holds. It computes one chip's share;
the exchange between chips (``shard_program_step`` over ``ep``) is not
built yet, and until it is this module is the only expert-PARALLEL path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def init_moe_params(rng, d_model, d_hidden, n_experts, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = 1.0 / jnp.sqrt(d_model)
    return {
        "gate": jax.random.normal(k1, (d_model, n_experts), dtype) * scale,
        "w_in": jax.random.normal(k2, (n_experts, d_model, d_hidden),
                                  dtype) * scale,
        "w_out": jax.random.normal(k3, (n_experts, d_hidden, d_model),
                                   dtype) * (1.0 / jnp.sqrt(d_hidden)),
    }


def shard_moe_params(params, mesh, axis="ep"):
    """Place expert weights expert-sharded over the mesh (gate replicated)."""
    ep = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    return {
        "gate": jax.device_put(params["gate"], rep),
        "w_in": jax.device_put(params["w_in"], ep),
        "w_out": jax.device_put(params["w_out"], ep),
    }


def moe_ffn(x, params, mesh=None, axis="ep", capacity_factor=1.25,
            act=jax.nn.relu):
    """Top-1 routed expert FFN. x [n_tokens, d_model] -> (out, aux_loss).

    The dispatch/combine are one-hot einsums over a [tokens, E, C] mask —
    static shapes; with ``mesh`` given, sharding constraints pin the
    expert-major intermediates to the ep axis so GSPMD materializes the
    token shuffle as all-to-all over ICI."""
    n, d = x.shape
    e = params["w_in"].shape[0]
    cap = max(1, int(capacity_factor * n / e))

    logits = x @ params["gate"]                     # [n, E]
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)         # [n]
    gate_val = jnp.take_along_axis(gates, expert_idx[:, None], axis=1)[:, 0]

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert_idx, e, dtype=x.dtype)       # [n, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot           # [n, E]
    keep = pos < cap
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, -1).astype(jnp.int32),
                            cap, dtype=x.dtype)                 # [n, E, C]
    dispatch = onehot[:, :, None] * pos_oh                      # [n, E, C]

    # aux load-balancing loss (GShard eq. 4): E * mean(frac) . mean(gate)
    frac = jnp.mean(onehot, axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux_loss = e * jnp.sum(frac * mean_gate)

    expert_in = jnp.einsum("nd,nec->ecd", x, dispatch)          # [E, C, d]
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(axis)))
    h = act(jnp.einsum("ecd,edh->ech", expert_in, params["w_in"]))
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["w_out"])
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(axis)))

    combine = dispatch * gate_val[:, None, None]                # [n, E, C]
    out = jnp.einsum("ecd,nec->nd", expert_out, combine)
    return out, aux_loss

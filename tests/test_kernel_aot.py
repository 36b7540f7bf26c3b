"""The main path's Mosaic kernels compile for a v5e at the benchmark's shapes.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached: what Mosaic refuses (a block that does not tile,
more VMEM than a kernel may use) fails here and costs no chip time. Nothing
runs, so these say nothing about results or times. The topology is
described inside a fixture, never at import: only one process may load the
TPU's library, and every xdist worker imports every test file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BATCH, HIDDEN = 256, 512          # cells 2 and 3 of BENCHMARK.json


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native_lowering(monkeypatch):
    """The kernels ask on_cpu() whether to interpret; the process here sees
    the CPU, so steer that one predicate to compile them natively. The
    persistent cache cannot read such an executable back: keep it off."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu.ops.pallas import (attention, causal_conv1d, delta_rule,
                                       grouped_matmul, moe_combine, rnn,
                                       ssd_scan)
    monkeypatch.setattr(rnn, "_on_cpu", lambda: False)
    for module in (attention, causal_conv1d, delta_rule, grouped_matmul,
                   moe_combine, ssd_scan):
        monkeypatch.setattr(module, "on_cpu", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(L, b, H, sharding):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return {"x": f32(L, b, 4 * H), "alive": f32(L, b, 1), "w": f32(H, 4 * H),
            "h0": f32(b, H), "c0": f32(b, H), "seq": f32(L, b, H)}


@pytest.mark.parametrize("L", [64, 512])
def test_lstm_forward_kernel_compiles_for_v5e(one_chip, native_lowering, L):
    from paddle_tpu.ops.pallas.rnn import _lstm_seq_fwd_pallas
    s = _shapes(L, BATCH, HIDDEN, one_chip)
    compiled = jax.jit(_lstm_seq_fwd_pallas).lower(
        s["x"], s["alive"], s["w"], s["h0"], s["c0"]).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("L", [64, 512])
def test_lstm_backward_kernel_compiles_for_v5e(one_chip, native_lowering, L):
    from paddle_tpu.ops.pallas import rnn
    assert rnn.lstm_bwd_fits(BATCH, HIDDEN)
    s = _shapes(L, BATCH, HIDDEN, one_chip)
    wb = jax.ShapeDtypeStruct(s["w"].shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(rnn._lstm_seq_bwd_pallas).lower(
        s["x"], s["alive"], wb, s["h0"], s["c0"],
        s["seq"], s["seq"], s["seq"], s["seq"]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "lstm_bwd" in text


def test_lstm_backward_whole_compiles_for_v5e(one_chip, native_lowering):
    """The kernel, the weight-gradient product after it and the counters
    around them, as lstm_grad calls them."""
    from paddle_tpu.ops.pallas import rnn
    s = _shapes(128, BATCH, HIDDEN, one_chip)
    compiled = jax.jit(rnn.lstm_seq_bwd).lower(
        s["x"], s["alive"], s["w"], s["h0"], s["c0"],
        s["seq"], s["seq"], s["seq"], s["seq"]).compile()
    assert "lstm_bwd" in compiled.as_text()


# ---- the Mellum2 cell: 8192 tokens, 32 query / 4 key-value heads of 128
ATT_T, ATT_HEADS, ATT_KV, ATT_D = 8192, 32, 4, 128


def _attention_shapes(sharding, T=ATT_T):
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    return (bf16(1, T, ATT_HEADS * ATT_D), bf16(1, T, ATT_KV * ATT_D),
            jax.ShapeDtypeStruct((1, ATT_HEADS, T, 128), jnp.float32,
                                 sharding=sharding))


def _pallas_grids(fn, *args):
    """{kernel name: (grid, scalar-prefetch operands)} of a traced call."""
    return {e.params["name"]:
            (tuple(e.params["grid_mapping"].grid),
             e.params["grid_mapping"].num_index_operands)
            for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "pallas_call"}


# a head's schedule at 8192 in blocks of 512: the window layers' 45
# entries, the full layer's 136; attention_dkv walks each of a group's 8
# query heads through a key/value head's
@pytest.mark.parametrize("window,entries", [(1024, 45), (0, 136)])
def test_attention_kernels_compile_for_v5e(one_chip, native_lowering,
                                           window, entries):
    """Both of the cell's layers, on the scalar-prefetch grid: its last
    axis is the schedule's length, so no step's body is skipped."""
    from paddle_tpu.ops.pallas import attention as att
    q, kv, lse = _attention_shapes(one_chip)
    fwd = lambda q, k, v: att.attention_pallas(         # noqa: E731
        q, k, v, ATT_HEADS, ATT_KV, window)
    bwd = lambda q, k, v, o, l, d: att.attention_pallas_bwd(  # noqa: E731
        q, k, v, o, l, d, ATT_HEADS, ATT_KV, window)
    assert _pallas_grids(fwd, q, kv, kv) == {
        "attention_fwd": ((1, ATT_HEADS, entries), 3)}
    assert _pallas_grids(bwd, q, kv, kv, q, lse, q) == {
        "attention_dq": ((1, ATT_HEADS, entries), 3),
        "attention_dkv": ((1, ATT_KV, entries * ATT_HEADS // ATT_KV), 4)}
    assert "attention_fwd" in jax.jit(fwd).lower(
        q, kv, kv).compile().as_text()
    text = jax.jit(bwd).lower(q, kv, kv, q, lse, q).compile().as_text()
    assert "attention_dq" in text and "attention_dkv" in text


def test_attention_schedule_fits_scalar_memory_at_the_longest_length(
        one_chip, native_lowering):
    """The schedule's tables live in scalar memory (1 MiB on a v5e) and
    grow with the square of the length: the longest full layer that
    attention_supported admits at the cell's heads (88 blocks of 512,
    attention_dkv's 31328 entries) still compiles, and the next power of
    two is the twin's."""
    from paddle_tpu.ops.pallas import attention as att
    T = 88 * 512
    q, kv, lse = _attention_shapes(one_chip, T)
    assert att.attention_supported(q, ATT_HEADS, ATT_KV, 0)
    assert not att.attention_supported(
        _attention_shapes(one_chip, 65536)[0], ATT_HEADS, ATT_KV, 0)
    assert att.attention_supported(
        _attention_shapes(one_chip, 65536)[0], ATT_HEADS, ATT_KV, 1024)
    bwd = lambda q, k, v, o, l, d: att.attention_pallas_bwd(  # noqa: E731
        q, k, v, o, l, d, ATT_HEADS, ATT_KV, 0)
    entries = len(att.band_schedule(T, 512, 0, by="key",
                                    group=ATT_HEADS // ATT_KV).q)
    assert att.MAX_ENTRIES - 2048 < entries <= att.MAX_ENTRIES
    text = jax.jit(bwd).lower(q, kv, kv, q, lse, q).compile().as_text()
    assert "attention_dq" in text and "attention_dkv" in text


# ---- the Kimi-Linear cell: latent attention trains expanded, 32 query AND
# 32 key/value heads, queries and keys of 192 (padded by the op to 256
# lanes), values of 128, full causal attention over 4096 tokens
MLA_HEADS, MLA_QK, MLA_V, MLA_T = 32, 256, 128, 4096


def _kernel_vmem(text):
    """{kernel name: bytes of VMEM (memory space 1) Mosaic's allocation
    asks for it}, read off a compiled module's text."""
    import re
    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        (name,) = set(re.findall(r"attention_(?:fwd|dq|dkv)", line))
        out[name] = sum(int(n) for n in re.findall(
            r'"memory_space":"1","offset":"0","size":"(\d+)"', line))
    return out


def test_attention_kernels_compile_for_v5e_at_heads_of_192_and_128(
        one_chip, native_lowering):
    """The op pads 192 to 256 lanes and asks the kernels about that: two
    head sizes in one call, the block from the length as before (512), a
    head's blocks, scratch and scores under Mosaic's default scoped limit
    (16 MiB on a v5e) by the compile's own count, and with a group of 1
    ``attention_dkv``'s schedule is a head's own."""
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas import attention as att

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    narrow = s((1, MLA_T, MLA_HEADS * 192))
    v = s((1, MLA_T, MLA_HEADS * MLA_V))
    assert not att.attention_supported(narrow, MLA_HEADS, MLA_HEADS, 0, v)
    assert jax.eval_shape(
        lambda x: attention_ops._lane_padded(x, MLA_HEADS),
        narrow).shape == (1, MLA_T, MLA_HEADS * MLA_QK)
    q = s((1, MLA_T, MLA_HEADS * MLA_QK))
    lse = s((1, MLA_HEADS, MLA_T, 128), jnp.float32)
    assert att.attention_supported(q, MLA_HEADS, MLA_HEADS, 0, v)
    assert att.kernel_block(MLA_T) == 512
    entries = (MLA_T // 512) * (MLA_T // 512 + 1) // 2
    fwd = lambda q, k, v: att.attention_pallas(         # noqa: E731
        q, k, v, MLA_HEADS, MLA_HEADS, 0, scale=192 ** -0.5)
    bwd = lambda q, k, v, o, l, d: att.attention_pallas_bwd(  # noqa: E731
        q, k, v, o, l, d, MLA_HEADS, MLA_HEADS, 0, scale=192 ** -0.5)
    assert _pallas_grids(fwd, q, q, v) == {
        "attention_fwd": ((1, MLA_HEADS, entries), 3)}
    assert _pallas_grids(bwd, q, q, v, v, lse, v) == {
        "attention_dq": ((1, MLA_HEADS, entries), 3),
        "attention_dkv": ((1, MLA_HEADS, entries), 4)}
    assert jax.eval_shape(fwd, q, q, v)[0].shape == v.shape
    assert [x.shape for x in jax.eval_shape(bwd, q, q, v, v, lse, v)] == [
        q.shape, q.shape, v.shape]
    vmem = _kernel_vmem(jax.jit(fwd).lower(q, q, v).compile().as_text())
    vmem.update(_kernel_vmem(
        jax.jit(bwd).lower(q, q, v, v, lse, v).compile().as_text()))
    assert sorted(vmem) == ["attention_dkv", "attention_dq", "attention_fwd"]
    # the blocks alone (double-buffered bfloat16 q, k of 512 x 256 and v,
    # out or d out of 512 x 128) are 1.5 MiB
    assert all(3 << 19 < n < 16 << 20 for n in vmem.values()), vmem


# (tokens, top k, held, experts, buffer factor, hidden, expert width): the
# Mellum2 cell's experts, and the Nemotron-3-Nano cell's, whose width is 14.5
# lane tiles (every block of the kernels spans a whole width, so
# ``supported`` admits whole HALVES of a tile)
MELLUM2_EXPERTS = (8192, 8, 8, 64, 2.0, 2304, 896)
NEMOTRON_EXPERTS = (4096, 6, 8, 128, 3.0, 2688, 1856)


def _expert_shapes(sharding, case=MELLUM2_EXPERTS):
    from paddle_tpu.ops import moe_ops
    tokens, top_k, held, experts, factor, hidden, width = case

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    _, _, buffer = moe_ops.row_buffer(tokens, top_k, held, experts, factor)
    return (s((buffer, hidden)), s((buffer, width)),
            s((held, hidden, width)), s((held, width, hidden)),
            s((buffer // moe_ops.TILE,), jnp.int32),
            s((1,), jnp.int32), s((held,), jnp.int32))


@pytest.mark.parametrize("case", [MELLUM2_EXPERTS, NEMOTRON_EXPERTS],
                         ids=["2304x896", "2688x1856"])
def test_grouped_matmul_kernels_compile_for_v5e(one_chip, native_lowering,
                                                case):
    """The three kernels at a cell's shapes: 18432 buffered rows over 8
    experts of 2304 x 896 (up) and 896 x 2304 (down); 6656 rows over 8 of
    2688 x 1856, the last lane tile half full; a width that is no whole
    half of a tile is not theirs."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    wide, narrow, w_up, w_down, tile_expert, tiles, _ = _expert_shapes(
        one_chip, case)
    assert gm.supported(wide, w_up) and gm.supported(narrow, w_down)
    assert not gm.supported(wide, jax.ShapeDtypeStruct(
        (8, case[5], case[6] + 32), jnp.bfloat16))
    for fn, args, name in (
            (gm.gmm, (wide, w_up), "grouped_matmul"),
            (gm.gmm, (narrow, w_down), "grouped_matmul"),
            (gm.gmm_t, (narrow, w_up), "grouped_matmul_t"),
            (gm.gmm_t, (wide, w_down), "grouped_matmul_t"),
            (lambda a, b, e, n: gm.tgmm(a, b, 8, e, n), (wide, narrow),
             "grouped_matmul_w"),
            (lambda a, b, e, n: gm.tgmm(a, b, 8, e, n), (narrow, wide),
             "grouped_matmul_w")):
        compiled = jax.jit(fn).lower(*args, tile_expert, tiles).compile()
        assert name in compiled.as_text()


def test_moe_combine_kernel_compiles_for_v5e(one_chip, native_lowering):
    """``combine`` at the cell's shape: 18432 buffered float32 rows of 2304
    (72 tiles, 8 held experts) to 8192 tokens; the whole walk and both row
    vectors fit scalar memory."""
    from paddle_tpu.ops.pallas import moe_combine as mc
    wide, _, _, _, tile_expert, _, held = _expert_shapes(one_chip)

    def s(dtype):
        return jax.ShapeDtypeStruct(wide.shape[:1], dtype,
                                    sharding=one_chip)
    rows = jax.ShapeDtypeStruct(wide.shape, jnp.float32, sharding=one_chip)
    assert wide.shape == (18432, 2304) and tile_expert.shape == (72,)
    assert mc.supported(rows, 8192, 8)
    compiled = mc.combine.lower(rows, s(jnp.float32), s(jnp.int32), held,
                                tile_expert, n=8192).compile()
    assert "moe_combine" in compiled.as_text()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_delta_rule_kernels_compile_for_v5e(one_chip, native_lowering,
                                            direction):
    """``delta_rule_fwd`` / ``delta_rule_bwd`` at the Kimi-Linear cell's
    shape: one stream of 4096 tokens, 32 heads of 128, chunks of 64,
    bfloat16 q / k / v / beta, float32 log-decays and kept states."""
    from paddle_tpu.ops.pallas import delta_rule as dr
    T, heads, d, chunk = 4096, 32, 128, 64

    def s(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = s(jnp.bfloat16, 1, T, heads * d)
    args = [x, x, x, s(jnp.float32, 1, T, heads * d),
            s(jnp.bfloat16, 1, T, heads)]
    assert dr.supported(x, x, args[3], heads, chunk)
    if direction == "fwd":
        lowered = dr.delta_rule_fwd.lower(*args, heads=heads, chunk=chunk,
                                          scale=d ** -0.5)
    else:
        lowered = dr.delta_rule_bwd.lower(
            *args, s(jnp.float32, 1, T // chunk, heads, d, d), x,
            heads=heads, chunk=chunk, scale=d ** -0.5)
    assert f"delta_rule_{direction}" in lowered.compile().as_text()


# the short convolutions of the Kimi-Linear cell (three a KDA layer over 32
# heads of 128, no bias) and of the Nemotron cell (one a mixer over x, B and
# C: 4096 + 2 x 1024 channels, with its bias), 4096 tokens, 4 taps
@pytest.mark.parametrize("channels,bias", [(4096, False), (6144, True)],
                         ids=["4096", "6144_bias"])
def test_causal_conv1d_kernels_compile_for_v5e(one_chip, native_lowering,
                                               channels, bias):
    """``causal_conv1d_fwd`` / ``causal_conv1d_bwd`` at both cells' shapes,
    bfloat16 x and ``Out@GRAD``, float32 filter and bias: blocks of 256
    channels of the whole time axis (three of them twice and two float32
    scratch columns are 16 MiB of the kernels' 48), a grid of (channel
    blocks, 1), and the longest float32 sequence ``supported`` admits
    still compiles at its block of 128."""
    from paddle_tpu.ops.pallas import causal_conv1d as cc

    def s(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = s(jnp.bfloat16, 1, 4096, channels)
    w = s(jnp.float32, 4, channels)
    b = s(jnp.float32, channels) if bias else None
    assert cc.supported(x, w)
    assert cc.block_width(x, backward=True) == 256
    assert cc.vmem_bytes(4096, 256, x.dtype, backward=True) == (
        12 << 20) + 2 * (4096 + 16) * 512
    # (the entry points are jitted: their own functions hold the call)
    assert _pallas_grids(cc.causal_conv1d_fwd.__wrapped__, x, w, b) == {
        "causal_conv1d_fwd": ((channels // 256, 1), 0)}
    assert _pallas_grids(cc.causal_conv1d_bwd.__wrapped__, x, w, b, x) == {
        "causal_conv1d_bwd": ((channels // 256, 1), 0)}
    assert "causal_conv1d_fwd" in cc.causal_conv1d_fwd.lower(
        x, w, b).compile().as_text()
    assert "causal_conv1d_bwd" in cc.causal_conv1d_bwd.lower(
        x, w, b, x).compile().as_text()
    if bias:
        long = s(jnp.float32, 2, 12280, 128)
        w, b = s(jnp.float32, 4, 128), s(jnp.float32, 128)
        assert cc.supported(long, w)
        assert cc.vmem_bytes(12280, 128, long.dtype, True) \
            <= cc.VMEM_BUDGET < cc.vmem_bytes(12288, 128, long.dtype, True)
        assert "causal_conv1d_bwd" in cc.causal_conv1d_bwd.lower(
            long, w, b, long).compile().as_text()


def _primitives(jaxpr):
    """Every primitive's name in a jaxpr, those of its sub-jaxprs (jit,
    loops, a kernel's body) among them."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("tier,scatters", [("pallas", False), ("jnp", True)])
def test_routed_experts_on_the_kernel_route_has_no_scatter_add(tier,
                                                               scatters):
    """The forward's combine and the backward's rows -> tokens are the
    ``moe_combine`` kernel on the kernel route: with
    ``router_task_gradient`` off neither traced op holds a ``scatter-add``
    (one update after another on a TPU), so the serial path cannot come
    back unnoticed. The twin's route is the control: it holds one each."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.registry import get_op_info

    class Ctx:
        attrs = dict(num_experts=8, top_k=2, router_task_gradient=False)

        def __init__(self, **env):
            self.env, self.out = env, {}

        def input(self, slot):
            return self.env[slot]

        def has_input(self, slot):
            return slot in self.env

        def attr(self, name, default=None):
            return self.attrs.get(name, default)

        def set_output(self, slot, value):
            self.out[slot] = value

    def forward(**env):
        ctx = Ctx(**env)
        get_op_info("routed_experts").forward(ctx)
        return ctx.out

    def backward(env, kept, dout):
        ctx = Ctx(**env, **{k: kept[k] for k in (
            "Gate", "Up", "RowAssign", "RowWeight", "ExpertLoad", "TopIdx",
            "Probs")}, **{"Out@GRAD": dout,
                          "AuxLoss@GRAD": jnp.ones((1,), jnp.float32)})
        get_op_info("routed_experts_grad").forward(ctx)
        return ctx.out

    f32 = jnp.zeros
    env = dict(X=f32((1, 256, 128)), RouterW=f32((128, 8)),
               WGate=f32((4, 128, 128)), WUp=f32((4, 128, 128)),
               WDown=f32((4, 128, 128)))
    fluid.set_flags({"kernel_tier": tier})
    try:
        fwd = jax.make_jaxpr(lambda env: forward(**env))(env)
        kept = jax.eval_shape(lambda env: forward(**env), env)
        bwd = jax.make_jaxpr(backward)(env, kept, env["X"])
    finally:
        fluid.set_flags({"kernel_tier": "auto"})
    for traced in fwd, bwd:
        names = set(_primitives(traced.jaxpr))
        assert ("scatter-add" in names) == scatters, sorted(names)
        assert ("pallas_call" in names) == (not scatters)


def test_ragged_dot_route_lowers_to_a_grouped_kernel_on_v5e(one_chip,
                                                             native_lowering):
    """Off the kernels routed_experts leans on XLA:TPU lowering
    ``ragged_dot`` to its own grouped kernel (work follows the rows held,
    not rows x experts) — which a ragged_dot_general that contracts the
    weights' last axis does NOT get: hence the transposed weights."""
    wide, narrow, w_up, _, _, _, sizes = _expert_shapes(one_chip)
    dense = 2 * wide.shape[0] * 2304 * 896

    def cost(fn, *args):
        return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]

    plain = lambda a, w, s: jax.lax.ragged_dot(   # noqa: E731
        a, w, s, preferred_element_type=jnp.float32)
    assert cost(plain, wide, w_up, sizes) < 1.1 * dense
    assert cost(lambda a, w, s: plain(a, jnp.swapaxes(w, 1, 2), s),
                narrow, w_up, sizes) < 1.1 * dense
    contract_last = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((1,), (2,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    assert cost(lambda a, w, s: jax.lax.ragged_dot_general(
        a, w, s, contract_last, preferred_element_type=jnp.float32),
        narrow, w_up, sizes) > 4 * dense


# ---- the Nemotron-3-Nano cell: 4096 tokens; the Mamba-2 core at 64 heads of
# 64 in 8 groups of state 128, chunks of 128; attention at 32 query and 2
# key/value heads of 128
SSD_T, SSD_HEADS, SSD_P, SSD_GROUPS, SSD_N, SSD_CHUNK = 4096, 64, 64, 8, 128, 128


def test_the_ssd_core_compiles_for_v5e_inside_a_gigabyte(one_chip):
    """``ssd_scan`` and its grad op at the cell's shape, bfloat16 x / B / C
    and raw step (the AMP types): the chunked program has no loop (the pass
    over the 32 chunk states is one product), keeps the states it says it
    keeps, and its temporaries (the [32, 64, 128, 128] pairwise decays and
    what the backward rebuilds of them) stay under a gigabyte by the
    compiler's own count."""
    from paddle_tpu.ops import state_space_ops as ss

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = s((1, SSD_T, SSD_HEADS * SSD_P))
    bc = s((1, SSD_T, SSD_GROUPS * SSD_N))
    dt = s((1, SSD_T, SSD_HEADS))
    head = s((SSD_HEADS,), jnp.float32)
    args = (x, dt, bc, bc, head, head, head)
    chunks = SSD_T // SSD_CHUNK
    states = s((1, chunks, SSD_HEADS, SSD_P, SSD_N), jnp.float32)
    fwd = lambda *a: ss.ssd_chunked(                    # noqa: E731
        *a, SSD_HEADS, SSD_GROUPS, SSD_CHUNK)
    bwd = lambda *a: ss.ssd_chunked_bwd(                # noqa: E731
        *a, SSD_HEADS, SSD_GROUPS, SSD_CHUNK)
    out, kept = jax.eval_shape(fwd, *args)
    assert (out.shape, out.dtype) == (x.shape, x.dtype)
    assert (kept.shape, kept.dtype) == (states.shape, states.dtype)
    grads = jax.eval_shape(bwd, *args, states, x)
    assert [g.shape for g in grads[:4]] == [a.shape for a in args[:4]]
    for fn, operands in ((fwd, args), (bwd, args + (states, x))):
        compiled = jax.jit(fn).lower(*operands).compile()
        assert " while(" not in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssd_scan_kernels_compile_for_v5e(one_chip, native_lowering,
                                          direction):
    """``ssd_scan_fwd`` / ``ssd_scan_bwd`` at the cell's shape, bfloat16 x,
    B, C and ``Out@GRAD``, float32 step, log-decays and kept states: a grid
    of (batch, 8 groups, 32 chunks), blocks of [128, 512] and [128, 128], a
    backward step's blocks and values inside ``vmem_bytes``'s count; the
    op's own dispatch reaches them under ``kernel_tier=pallas``, and heads
    of 48 do not reach Mosaic."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import state_space_ops as ss
    from paddle_tpu.ops.pallas import ssd_scan as kernels

    def s(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    chunks = SSD_T // SSD_CHUNK
    x = s(jnp.bfloat16, 1, SSD_T, SSD_HEADS * SSD_P)
    bc = s(jnp.bfloat16, 1, SSD_T, SSD_GROUPS * SSD_N)
    head = s(jnp.float32, SSD_HEADS)
    steps = s(jnp.float32, 1, chunks, SSD_CHUNK, SSD_HEADS)
    states = s(jnp.float32, 1, chunks, SSD_HEADS, SSD_P, SSD_N)
    assert kernels.supported(x, bc, SSD_HEADS, SSD_GROUPS, SSD_CHUNK)
    assert kernels.vmem_bytes(SSD_CHUNK, 512, SSD_N, x.dtype) < 8 << 20
    args = (x, bc, bc, head, steps, steps) + (
        () if direction == "fwd" else (states, x))
    entry = {"fwd": kernels.ssd_scan_fwd,
             "bwd": kernels.ssd_scan_bwd}[direction]
    # (the entry points are jitted: their own functions hold the call)
    assert _pallas_grids(lambda *a: entry.__wrapped__(
        *a, heads=SSD_HEADS, groups=SSD_GROUPS), *args) == {
        f"ssd_scan_{direction}": ((1, SSD_GROUPS, chunks), 0)}
    assert f"ssd_scan_{direction}" in entry.lower(
        *args, heads=SSD_HEADS, groups=SSD_GROUPS).compile().as_text()
    # the widest blocks ``supported`` admits of each kind still compile:
    # float32, chunks of 256, heads of 256 (a unit is one head's two lane
    # tiles), a state of 256
    wide = (s(jnp.float32, 1, 512, 4 * 256), s(jnp.float32, 1, 512, 2 * 256))
    assert kernels.supported(*wide, 4, 2, 256)
    wide_args = (wide[0], wide[1], wide[1], s(jnp.float32, 4),
                 s(jnp.float32, 1, 2, 256, 4), s(jnp.float32, 1, 2, 256, 4))
    if direction == "bwd":
        wide_args += (s(jnp.float32, 1, 2, 4, 256, 256), wide[0])
    assert f"ssd_scan_{direction}" in entry.lower(
        *wide_args, heads=4, groups=2).compile().as_text()

    raw = s(jnp.bfloat16, 1, SSD_T, SSD_HEADS)
    op_args = (x, raw, bc, bc, head, head, head) + (
        () if direction == "fwd" else (states, x))
    op = ss.ssd_chunked if direction == "fwd" else ss.ssd_chunked_bwd
    narrow = s(jnp.bfloat16, 1, SSD_T, SSD_HEADS * 48)

    def traced(fn, *operands):
        return set(_primitives(jax.make_jaxpr(lambda *a: fn(
            *a, SSD_HEADS, SSD_GROUPS, SSD_CHUNK))(*operands).jaxpr))
    fluid.set_flags({"kernel_tier": "pallas"})
    try:
        assert "pallas_call" in traced(op, *op_args)
        assert not kernels.supported(narrow, bc, SSD_HEADS, SSD_GROUPS,
                                     SSD_CHUNK)
        assert "pallas_call" not in traced(ss.ssd_chunked, narrow,
                                           *op_args[1:7])
    finally:
        fluid.set_flags({"kernel_tier": "auto"})


def test_attention_at_32_and_2_heads_reaches_the_kernels(one_chip,
                                                         native_lowering):
    """The cell's one attention layer: sixteen query heads a key/value head
    is a shape the three kernels take natively (no fallback), on the
    full-causal schedule of 4096 tokens in blocks of 512."""
    from paddle_tpu.ops.pallas import attention as att

    def s(heads):
        return jax.ShapeDtypeStruct((1, SSD_T, heads * 128), jnp.bfloat16,
                                    sharding=one_chip)
    q, kv = s(32), s(2)
    lse = jax.ShapeDtypeStruct((1, 32, SSD_T, 128), jnp.float32,
                               sharding=one_chip)
    assert att.attention_supported(q, 32, 2, 0, kv)
    entries = (SSD_T // 512) * (SSD_T // 512 + 1) // 2
    fwd = lambda q, k, v: att.attention_pallas(         # noqa: E731
        q, k, v, 32, 2, 0)
    bwd = lambda q, k, v, o, l, d: att.attention_pallas_bwd(  # noqa: E731
        q, k, v, o, l, d, 32, 2, 0)
    assert _pallas_grids(fwd, q, kv, kv) == {
        "attention_fwd": ((1, 32, entries), 3)}
    assert _pallas_grids(bwd, q, kv, kv, q, lse, q) == {
        "attention_dq": ((1, 32, entries), 3),
        "attention_dkv": ((1, 2, entries * 16), 4)}
    assert "attention_fwd" in jax.jit(fwd).lower(
        q, kv, kv).compile().as_text()
    text = jax.jit(bwd).lower(q, kv, kv, q, lse, q).compile().as_text()
    assert "attention_dq" in text and "attention_dkv" in text

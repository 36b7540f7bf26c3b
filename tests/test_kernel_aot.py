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
    from paddle_tpu.ops.pallas import rnn
    monkeypatch.setattr(rnn, "_on_cpu", lambda: False)
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

"""chip_smoke.py and the bring-up guards, on the CPU: the smoke's phases at a
tiny size under both kernel tiers, its refusal to pass without a TPU, the
compile-cache resolver, and the fallbacks that now raise instead of hiding
the device (TPUPlace, make_mesh, the planner's rate table)."""

import os
import subprocess
import sys

import pytest

import paddle_tpu.fluid as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# full WIDTH (64..256 channels of stage 1), depth cut to one bottleneck
TINY = dict(batch=4, image_size=16, class_dim=10, steps=3,
            depths=(1, 0, 0, 0))


@pytest.fixture
def kernel_tier():
    def set_tier(tier):
        fluid.set_flags({"kernel_tier": tier})
    yield set_tier
    fluid.set_flags({"kernel_tier": "auto"})


def test_train_phase_pallas_interpret_is_counted_and_refused(kernel_tier):
    """Under kernel_tier=pallas the flagship builds FUSED and its kernels
    dispatch — interpreted here, which is exactly what the smoke must
    refuse to call a pass on the chip."""
    from paddle_tpu.ops.pallas import dispatch_counts

    kernel_tier("pallas")
    before = dispatch_counts()
    out = chip_smoke.train_phase(**dict(TINY, batch=2, steps=2))
    assert out["fused"] and out["losses"][-1] < out["losses"][0]
    rep = chip_smoke.kernel_report()
    for family in ("conv_bn", "optimizer"):
        got = rep["dispatches"][family]
        was = before.get(family, {"native": 0, "interpret": 0})
        assert got["interpret"] > was["interpret"] and got["native"] == 0
    with pytest.raises(AssertionError, match="interpret=True"):
        chip_smoke.assert_native(rep)


def test_phases_jnp_one_device_and_dp4_agree(kernel_tier):
    kernel_tier("jnp")
    one = chip_smoke.train_phase(**dict(TINY, batch=8))
    assert not one["fused"]
    assert len(one["losses"]) == 3 and one["losses"][-1] < one["losses"][0]
    assert one["first_step_s"] > 0 and one["steady_step_ms"] > 0
    dp = chip_smoke.dp_phase(4, **dict(TINY, batch=8))
    assert dp["losses"][-1] < dp["losses"][0]
    assert abs(dp["losses"][0] - one["losses"][0]) < 1e-2 * one["losses"][0]
    assert dp["placement"]["img"] == {"devices": 4, "shard": [2, 16, 16, 3]}
    (acc,) = [k for k in dp["placement"] if k != "img"]
    assert "_velocity" in acc and dp["placement"][acc]["devices"] == 4


def test_smoke_exits_nonzero_and_says_why_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout          # no result line


# ---------------------------------------------------------------------------
# compile-cache resolver
# ---------------------------------------------------------------------------

_RESOLVE = ("import jax; from paddle_tpu.core import compile_cache as c; "
            "d, _ = c.enable(); "
            "print(d); print(jax.config.jax_compilation_cache_dir)")


def _resolve(env):
    r = subprocess.run([sys.executable, "-c", _RESOLVE], capture_output=True,
                       text=True, timeout=120, cwd=REPO,
                       env=dict(env, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_compile_cache_env_set_uses_it_and_sets_nothing_else(tmp_path):
    want = str(tmp_path / "from_env")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=want)
    assert _resolve(env) == [want, want]


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    first, second = _resolve(env), _resolve(env)
    assert first == second == [os.path.join(REPO, ".jax_cache")] * 2


# ---------------------------------------------------------------------------
# fallbacks that no longer hide the device
# ---------------------------------------------------------------------------

def test_tpu_place_raises_when_it_cannot_be_honoured():
    with pytest.raises(RuntimeError, match="no TPU"):
        fluid.Executor(fluid.TPUPlace(0))
    fluid.Executor(fluid.CPUPlace())        # an honest CPU place still works


def test_tpu_place_out_of_range_raises(monkeypatch):
    import jax
    from paddle_tpu.core import executor

    class FakeChip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeChip()])
    assert isinstance(executor._resolve_device(fluid.TPUPlace(0)), FakeChip)
    with pytest.raises(RuntimeError, match="only 1 TPU device"):
        executor._resolve_device(fluid.TPUPlace(3))


def test_make_mesh_with_too_few_devices_raises():
    import jax
    from paddle_tpu.parallel import make_mesh

    n = len(jax.devices())
    assert make_mesh(n).devices.size == n
    with pytest.raises(ValueError, match=f"{n + 1} devices asked for"):
        make_mesh(n + 1)


def test_planner_rates_are_keyed_by_device_kind():
    from paddle_tpu.parallel import planner

    v5e = planner.machine_rates("TPU v5 lite")
    assert (v5e["flops_s"], v5e["hbm_bytes_s"]) == (1.97e14, 8.19e11)
    assert planner.machine_rates()["flops_s"] > 0       # this host's cpu
    with pytest.raises(planner.PlanError, match="TPU v9"):
        planner.machine_rates("TPU v9")


def test_probe_records_why_a_kernel_was_dropped():
    """tools/kernel_probe.py: a kernel that cannot compile is a result with
    the compiler's text in it, at its first call or in the A/B's warm-up."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import kernel_probe
    finally:
        sys.path.pop(0)
    calls = []

    def broken_after(n):
        def run():
            calls.append(n)
            if len(calls) > n:
                raise ValueError("Mosaic says no")
            return 1.0
        return run

    for n, error in ((0, "ValueError: Mosaic says no"),
                     (1, "pallas: ValueError: Mosaic says no")):
        del calls[:]
        rec = kernel_probe.probe("case", "conv_bn", {
            "jnp": lambda: 1.0, "pallas": broken_after(n)}, repeats=1,
            inner=1)
        assert rec["error"] == error
        assert rec["lowered"] == (n > 0) and rec["pallas_ms"] is None

"""Serving fleet control plane: the versioned ModelRegistry (atomic
publish, immutability, corruption detection), ModelServer zero-downtime
hot reload, the FleetClient router (balancing, failover, overload
spillover, probation re-admission) against in-process servers, and the
spawned-replica FleetSupervisor end to end — rolling reload keeping ≥N−1
replicas ready, failed-canary rollback, and crash-failover-rejoin under a
deterministic FaultPlan.
"""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import FaultPlan, RemoteError, RetryPolicy
from paddle_tpu.distributed.launch import ChildSupervisor, PserverSupervisor
from paddle_tpu.serving import (FleetClient, FleetSupervisor, InferClient,
                                ModelRegistry, ModelServer, ServerOverloaded)


def _export_model(tmp_path, name="model", weight_shift=0.0, dim=6, hidden=8,
                  classes=3, n=16):
    """Export a tiny MLP; ``weight_shift`` perturbs the params post-init so
    two exports produce DIFFERENT models (init is deterministic per var
    name). Returns (model_dir, inputs, reference outputs)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[dim])
        h = fluid.layers.fc(input=x, size=hidden, act="relu")
        y = fluid.layers.fc(input=h, size=classes, act="softmax")
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    if weight_shift:
        for p in main.all_parameters():
            v = np.asarray(scope.find_var(p.name))
            scope.set(p.name, v + np.float32(weight_shift))
    d = str(tmp_path / name)
    fluid.io.save_inference_model(d, ["x"], [y], exe, main, scope=scope)
    rng = np.random.RandomState(0)
    xs = rng.normal(0, 1, (n, dim)).astype("float32")
    want = exe.run(main, feed={"x": xs}, fetch_list=[y], scope=scope)[0]
    return d, xs, want


# ---------------------------------------------------------------------------
# ModelRegistry: atomic versioned publish, resolve, corruption detection
# ---------------------------------------------------------------------------

def test_registry_publish_resolve_and_latest(tmp_path):
    d, _, _ = _export_model(tmp_path)
    reg = ModelRegistry(str(tmp_path / "registry"))
    assert reg.versions("mlp") == []
    v1 = reg.publish("mlp", d)
    v2 = reg.publish("mlp", d)
    assert (v1, v2) == (1, 2) and reg.versions("mlp") == [1, 2]
    path, v = reg.resolve("mlp", "latest")
    assert v == 2 and path.endswith(os.path.join("mlp", "2"))
    path1, _ = reg.resolve("mlp", 1)
    assert path1.endswith(os.path.join("mlp", "1"))
    assert reg.previous("mlp", 2) == 1 and reg.previous("mlp", 1) is None
    m = reg.verify("mlp", 2)
    assert m["content_hash"] and m["files"]      # hashes recorded + valid
    # versions are immutable
    with pytest.raises(ValueError, match="immutable"):
        reg.publish("mlp", d, version=1)


def test_registry_typed_errors(tmp_path):
    d, _, _ = _export_model(tmp_path)
    reg = ModelRegistry(str(tmp_path / "registry"))
    with pytest.raises(ValueError, match="no published versions"):
        reg.resolve("nope")
    reg.publish("mlp", d)
    with pytest.raises(ValueError, match="no published version 9"):
        reg.resolve("mlp", 9)
    with pytest.raises(ValueError, match="not a save_inference_model"):
        reg.publish("mlp", str(tmp_path))        # no __model__ there
    with pytest.raises(ValueError, match="one plain path component"):
        reg.resolve("a/b")


def test_registry_detects_corruption_and_torn_publish(tmp_path):
    d, _, _ = _export_model(tmp_path)
    reg = ModelRegistry(str(tmp_path / "registry"))
    v = reg.publish("mlp", d)
    path, _ = reg.resolve("mlp", v)
    # bit rot after publish: verify() re-hashes and raises typed
    npys = [f for f in os.listdir(path) if f.endswith(".npy")]
    with open(os.path.join(path, npys[0]), "r+b") as f:
        f.seek(-1, os.SEEK_END)
        f.write(b"\xff")
    with pytest.raises(ValueError, match="corrupt"):
        reg.verify("mlp", v)
    # a version dir WITHOUT its manifest (torn publish) is invisible
    torn = os.path.join(reg.model_dir("mlp"), "7")
    os.makedirs(torn)
    with open(os.path.join(torn, "__model__"), "w") as f:
        f.write("{}")
    assert reg.versions("mlp") == [v]
    # a resolvable version whose bundle is garbage fails the LOAD with
    # load_inference_model's typed error (the engine-side detection)
    bad_src = tmp_path / "bad"
    bad_src.mkdir()
    (bad_src / "__model__").write_text("not json at all")
    vb = reg.publish("mlp", str(bad_src))
    bad_path, _ = reg.resolve("mlp", vb)
    from paddle_tpu.serving import InferenceEngine
    with pytest.raises(ValueError, match="corrupt"):
        InferenceEngine(bad_path)


# ---------------------------------------------------------------------------
# ModelServer hot reload: zero downtime, version/reloads surfaced
# ---------------------------------------------------------------------------

def test_server_hot_reload_swaps_without_downtime(tmp_path):
    dA, xs, wantA = _export_model(tmp_path, "A")
    dB, _, wantB = _export_model(tmp_path, "B", weight_shift=0.25)
    assert not np.allclose(wantA, wantB)
    server = ModelServer(dA, buckets="1,2,4", max_delay_ms=1.0, version=1)
    server.start()
    errs = []
    stop = threading.Event()

    def hammer():
        with InferClient(server.address) as c:
            while not stop.is_set():
                try:
                    out = c.infer({"x": xs[:1]})[0]
                    # every answer is EXACTLY one model's — never a blend
                    if not (np.allclose(out, wantA[:1], rtol=1e-4,
                                        atol=1e-5)
                            or np.allclose(out, wantB[:1], rtol=1e-4,
                                           atol=1e-5)):
                        errs.append("blended answer")
                except Exception as e:
                    errs.append(e)

    ts = [threading.Thread(target=hammer) for _ in range(3)]
    for t in ts:
        t.start()
    time.sleep(0.1)                      # traffic established on A
    server.reload(dB, version=2)         # hot swap under load
    stop.set()
    for t in ts:
        t.join()
    assert not errs, errs[:3]
    with InferClient(server.address) as c:
        out = c.infer({"x": xs[:4]})
        np.testing.assert_allclose(out[0], wantB[:4], rtol=1e-5, atol=1e-6)
        st = c.stats()
        assert st["version"] == 2 and st["reloads"] == 1
        assert st["engine"]["hot_recompiles"] == 0   # warmed off hot path
        assert c.health()["version"] == 2
    server.shutdown()


def test_server_reload_failure_keeps_old_engine(tmp_path):
    dA, xs, wantA = _export_model(tmp_path, "A")
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "__model__").write_text("garbage")
    server = ModelServer(dA, buckets="1,2", max_delay_ms=1.0, version=1)
    server.start()
    with InferClient(server.address) as c:
        with pytest.raises(ValueError, match="corrupt"):
            server.reload(str(bad), version=2)    # typed, pre-swap failure
        out = c.infer({"x": xs[:2]})              # old engine still serves
        np.testing.assert_allclose(out[0], wantA[:2], rtol=1e-5, atol=1e-6)
        st = c.stats()
        assert st["version"] == 1 and st["reloads"] == 0
    server.shutdown()


# ---------------------------------------------------------------------------
# structured RPC error codes (replaces _OVERLOAD_MARK string sniffing)
# ---------------------------------------------------------------------------

def test_remote_error_carries_code_and_traceback(tmp_path):
    d, xs, _ = _export_model(tmp_path)
    server = ModelServer(d, buckets="1,2", max_delay_ms=1.0)
    server.start()
    with InferClient(server.address) as c:
        with pytest.raises(RemoteError) as ei:
            c.infer({"wrong_feed": xs[:1]})
        e = ei.value
        assert e.code == "ValueError"            # machine-checkable code
        assert "missing vars" in e.remote_message
        assert e.remote_traceback and "Traceback" in e.remote_traceback
        assert "missing vars" in str(e)          # message survives in str
    server.shutdown()


def test_overload_is_typed_via_code_not_message(tmp_path):
    """The overload mapping keys on the structured code, so a reworded
    message still re-raises typed — pinned by overloading through a
    handler whose message shares NO text with the type name."""
    d, xs, _ = _export_model(tmp_path)
    from paddle_tpu.serving.engine import InferenceEngine
    eng = InferenceEngine(d, buckets="1,2")
    release = threading.Event()
    inner = eng.infer

    def slow_infer(feed, fetch_list=None):
        release.wait(5.0)
        return inner(feed, fetch_list)

    eng.infer = slow_infer
    server = ModelServer(engine=eng, batching=True, queue_capacity=1,
                         max_delay_ms=1.0)
    server.start()
    outcomes = []

    def caller(i):
        with InferClient(server.address, retry=None) as c:
            try:
                c.infer({"x": xs[i:i + 1]})
                outcomes.append("ok")
            except ServerOverloaded:
                outcomes.append("overloaded")

    ts = [threading.Thread(target=caller, args=(i,)) for i in range(5)]
    for t in ts:
        t.start()
    deadline = time.monotonic() + 3.0
    while outcomes.count("overloaded") < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    release.set()
    for t in ts:
        t.join()
    assert outcomes.count("overloaded") >= 1
    server.shutdown()


# ---------------------------------------------------------------------------
# FleetClient router over in-process servers (fast: no child processes)
# ---------------------------------------------------------------------------

def _two_servers(d, **kw):
    s1 = ModelServer(d, buckets="1,2,4", max_delay_ms=1.0, **kw)
    s2 = ModelServer(d, buckets="1,2,4", max_delay_ms=1.0, **kw)
    s1.start()
    s2.start()
    return s1, s2


def test_router_balances_across_replicas(tmp_path):
    d, xs, want = _export_model(tmp_path)
    s1, s2 = _two_servers(d)
    with FleetClient([s1.address, s2.address]) as fc:
        for i in range(24):
            out = fc.infer({"x": xs[i % 8:i % 8 + 1]})
            np.testing.assert_allclose(out[0], want[i % 8:i % 8 + 1],
                                       rtol=1e-5, atol=1e-6)
        fs = fc.fleet_stats()
        assert fs["requests"] == 24 and fs["healthy"] == 2
        assert fs["p99_ms"] >= fs["p50_ms"] >= 0.0
        served = [r["server"]["wire"]["calls"].get("infer", {}).get(
            "count", 0) for r in fs["replicas"]]
        assert sum(served) == 24
        assert all(s > 0 for s in served), \
            f"power-of-two picks starved a replica: {served}"
        assert fs["engine"]["hot_recompiles"] == 0
    s1.shutdown()
    s2.shutdown()


def test_router_failover_eject_and_probation_readmit(tmp_path):
    d, xs, want = _export_model(tmp_path)
    s1, s2 = _two_servers(d)
    addr1 = s1.address
    with FleetClient([addr1, s2.address], probe_interval_ms=30,
                     probation_probes=2) as fc:
        for i in range(4):
            fc.infer({"x": xs[i:i + 1]})
        s1.kill()                        # crash replica 1
        for i in range(12):              # every request still answered
            out = fc.infer({"x": xs[i % 8:i % 8 + 1]})
            np.testing.assert_allclose(out[0], want[i % 8:i % 8 + 1],
                                       rtol=1e-5, atol=1e-6)
        fs = fc.fleet_stats(include_server_stats=False)
        assert fs["failovers"] >= 1 and fs["ejections"] >= 1
        assert fs["healthy"] == 1
        # restart on the SAME address: probation (2 consecutive healthy
        # probes at 30ms) re-admits it
        s1b = ModelServer(d, buckets="1,2,4", max_delay_ms=1.0,
                          address=addr1)
        s1b.start()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            fs = fc.fleet_stats(include_server_stats=False)
            if fs["healthy"] == 2:
                break
            time.sleep(0.05)
        assert fs["healthy"] == 2, fs
        # traffic reaches the re-admitted replica again
        before = s1b.stats()["wire"]["calls"].get("infer", {}).get(
            "count", 0)
        for i in range(16):
            fc.infer({"x": xs[i % 8:i % 8 + 1]})
        after = s1b.stats()["wire"]["calls"].get("infer", {}).get(
            "count", 0)
        assert after > before
        s1b.shutdown()
    s2.shutdown()


def test_router_overload_spills_then_surfaces_typed(tmp_path):
    d, xs, want = _export_model(tmp_path)
    from paddle_tpu.serving.engine import InferenceEngine

    def slow_server():
        eng = InferenceEngine(d, buckets="1,2")
        release = threading.Event()
        inner = eng.infer
        eng.infer = lambda feed, fetch_list=None: (
            release.wait(5.0), inner(feed, fetch_list))[1]
        s = ModelServer(engine=eng, batching=True, queue_capacity=1,
                        max_delay_ms=1.0)
        s.start()
        return s, release

    s1, rel1 = slow_server()            # saturates after ~2 requests
    s2 = ModelServer(d, buckets="1,2,4", max_delay_ms=1.0)
    s2.start()
    with FleetClient([s1.address, s2.address]) as fc:
        # hammer: requests landing on the wedged s1 beyond its queue spill
        # to s2 — no caller sees an overload while s2 has capacity
        results = []

        def one(i):
            try:
                out = fc.infer({"x": xs[i % 8:i % 8 + 1]})[0]
                np.testing.assert_allclose(out, want[i % 8:i % 8 + 1],
                                           rtol=1e-5, atol=1e-6)
                results.append("ok")
            except ServerOverloaded:
                results.append("overloaded")

        ts = [threading.Thread(target=one, args=(i,)) for i in range(10)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + 5.0
        while len(results) < 8 and time.monotonic() < deadline:
            time.sleep(0.01)
        rel1.set()
        for t in ts:
            t.join()
        assert results.count("ok") >= 8, results
        fs = fc.fleet_stats(include_server_stats=False)
        if fs["spillovers"]:
            # spillover happened and was invisible to those callers
            assert results.count("ok") + results.count("overloaded") == 10
    s1.shutdown()
    s2.shutdown()

    # both replicas saturated -> the typed overload DOES surface
    s1, rel1 = slow_server()
    s2, rel2 = slow_server()
    with FleetClient([s1.address, s2.address]) as fc:
        outcomes = []

        def one2(i):
            try:
                fc.infer({"x": xs[i % 8:i % 8 + 1]})
                outcomes.append("ok")
            except ServerOverloaded:
                outcomes.append("overloaded")

        ts = [threading.Thread(target=one2, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        deadline = time.monotonic() + 5.0
        while outcomes.count("overloaded") < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        rel1.set()
        rel2.set()
        for t in ts:
            t.join()
        assert outcomes.count("overloaded") >= 1, outcomes
    s1.shutdown()
    s2.shutdown()


# ---------------------------------------------------------------------------
# FleetSupervisor end to end (spawned replica children — slower)
# ---------------------------------------------------------------------------

def _publish_two_versions(tmp_path):
    dA, xs, wantA = _export_model(tmp_path, "A")
    dB, _, wantB = _export_model(tmp_path, "B", weight_shift=0.25)
    reg = ModelRegistry(str(tmp_path / "registry"))
    v1 = reg.publish("mlp", dA)
    v2 = reg.publish("mlp", dB)
    return reg, (v1, v2), xs, (wantA, wantB)


def test_fleet_rolling_reload_keeps_n_minus_1_ready_and_rolls_back(
        tmp_path):
    """The rollout contract end to end on 2 spawned replicas: (1) traffic
    through a rolling reload sees zero failures and ≥N−1 replicas stay
    ready at every polled instant; (2) every replica lands on the target
    version with zero hot recompiles; (3) a corrupt canary version rolls
    back and the fleet stays on the good version throughout."""
    reg, (v1, v2), xs, (wantA, wantB) = _publish_two_versions(tmp_path)
    with FleetSupervisor(reg, "mlp", version=v1, n_replicas=2,
                         buckets="1,2,4", max_delay_ms=1.0) as sup:
        assert sup.wait_ready(240.0), "fleet never became ready"
        assert sup.version == v1
        with FleetClient(sup.addresses) as fc:
            out = fc.infer({"x": xs[:2]})
            np.testing.assert_allclose(out[0], wantA[:2], rtol=1e-5,
                                       atol=1e-6)
            errs = []
            stop = threading.Event()
            min_ready = [2]

            def hammer():
                while not stop.is_set():
                    try:
                        out = fc.infer({"x": xs[:1]})[0]
                        ok = (np.allclose(out, wantA[:1], rtol=1e-4,
                                          atol=1e-5)
                              or np.allclose(out, wantB[:1], rtol=1e-4,
                                             atol=1e-5))
                        if not ok:
                            errs.append("wrong answer")
                    except Exception as e:
                        errs.append(e)

            def poll_ready():
                while not stop.is_set():
                    min_ready[0] = min(min_ready[0], sup.ready_count())
                    time.sleep(0.05)

            ts = [threading.Thread(target=hammer) for _ in range(2)]
            ts.append(threading.Thread(target=poll_ready))
            for t in ts:
                t.start()
            try:
                got = sup.rolling_reload(v2, wait_timeout=240.0)
            finally:
                stop.set()
                for t in ts:
                    t.join()
            assert got == v2 and sup.version == v2
            assert not errs, f"requests failed during rollout: {errs[:3]}"
            assert min_ready[0] >= 1, \
                f"rollout dropped below N-1 ready: {min_ready[0]}"
            stats = sup.replica_stats()
            for i, st in stats.items():
                assert st is not None
                assert st["version"] == v2, (i, st["version"])
                assert st["engine"]["hot_recompiles"] == 0
                assert st["reloads"] >= 1
            # post-rollout answers are the NEW model's
            out = fc.infer({"x": xs[:3]})
            np.testing.assert_allclose(out[0], wantB[:3], rtol=1e-5,
                                       atol=1e-6)

            # fleet-wide obs scrape: every replica answers the built-in
            # ``metrics`` RPC, and the merged view carries at least the
            # per-replica engine compile counts replica_stats reported
            fm = sup.fleet_metrics()
            assert all(s is not None for s in fm["replicas"].values())
            eng = fm["merged"]["paddle_tpu_engine_compiles"]
            merged_compiles = sum(v["value"] for v in eng["values"])
            assert merged_compiles >= sum(st["engine"]["compiles"]
                                          for st in stats.values())
            json.dumps(fm)     # the whole scrape is wire-safe
            # accelerator-identity stamps: device count + kind ride the
            # scrape so fleet views are comparable across hosts
            assert fm["n_devices"] == jax.device_count()
            assert fm["device_kind"] == str(getattr(
                jax.devices()[0], "device_kind", jax.devices()[0].platform))

            # ---- failed canary: corrupt v3 rolls back, fleet untouched
            bad_src = tmp_path / "bad"
            bad_src.mkdir()
            (bad_src / "__model__").write_text("not a model")
            v3 = reg.publish("mlp", str(bad_src))
            with pytest.raises(RuntimeError, match="canary"):
                sup.rolling_reload(v3, wait_timeout=240.0)
            assert sup.version == v2           # target never advanced
            for i in range(2):
                h = sup.replica_health(i)
                assert h is not None and h["version"] == v2, (i, h)
            out = fc.infer({"x": xs[:1]})      # still serving v2 answers
            np.testing.assert_allclose(out[0], wantB[:1], rtol=1e-5,
                                       atol=1e-6)


def test_fleet_replica_dies_mid_request_failover_restart_rejoin(tmp_path):
    """The satellite fault case: a FaultPlan kills replica 0's server mid
    ``infer`` — the FleetClient answers every request from the surviving
    replica (zero failures), the supervisor restarts the dead child from
    the registry's current version, and the router re-admits it through
    the probation path."""
    reg, (v1, _v2), xs, (wantA, _) = _publish_two_versions(tmp_path)
    # replica 0 dies BEFORE serving its 2nd infer; applied to the FIRST
    # spawn only (the restarted child must come back clean and rejoin)
    plan = FaultPlan().die("infer", 1, before=True)
    with FleetSupervisor(reg, "mlp", version=v1, n_replicas=2,
                         buckets="1,2,4", max_delay_ms=1.0,
                         fault_plans={0: plan}) as sup:
        assert sup.wait_ready(240.0)
        with FleetClient(sup.addresses, probe_interval_ms=50,
                         probation_probes=2,
                         retry=RetryPolicy(max_retries=10,
                                           backoff_base_s=0.05,
                                           backoff_max_s=0.5)) as fc:
            # sequential single-row infers: the random picks route ~half
            # to replica 0, whose 2nd infer triggers the die — the
            # failover must keep every answer correct
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                out = fc.infer({"x": xs[:1]})
                np.testing.assert_allclose(out[0], wantA[:1], rtol=1e-5,
                                           atol=1e-6)
                if fc.fleet_stats(
                        include_server_stats=False)["failovers"] >= 1:
                    break
            fs = fc.fleet_stats(include_server_stats=False)
            assert fs["failovers"] >= 1 and fs["ejections"] >= 1, fs
            # the supervisor restarts replica 0 from the registry's
            # current version; probation re-admits it
            deadline = time.monotonic() + 240.0
            while time.monotonic() < deadline:
                fs = fc.fleet_stats(include_server_stats=False)
                if fs["healthy"] == 2:
                    break
                time.sleep(0.25)
            assert fs["healthy"] == 2, f"replica never rejoined: {fs}"
            assert sup.restarts[0] >= 1
            h = sup.replica_health(0)
            assert h is not None and h["version"] == v1   # current version
            # and it serves correctly again
            for _ in range(8):
                out = fc.infer({"x": xs[:1]})
                np.testing.assert_allclose(out[0], wantA[:1], rtol=1e-5,
                                           atol=1e-6)


# ---------------------------------------------------------------------------
# ChildSupervisor: the shared supervision helper (regression net for the
# PserverSupervisor refactor, with cheap numpy-only fork children)
# ---------------------------------------------------------------------------

def _echo_child(address, token):
    from paddle_tpu.distributed.rpc import RpcServer

    class H:
        def stats(self):
            return {"token": token, "pid": os.getpid()}

    RpcServer(H(), tuple(address)).serve_forever()


def _suicide_child(address):
    return                               # exits immediately: crash loop


class _EchoSupervisor(ChildSupervisor):
    def _child_spec(self, i):
        return _echo_child, (self.addresses[i], i)


class _CrashLoopSupervisor(ChildSupervisor):
    def _child_spec(self, i):
        return _suicide_child, (self.addresses[i],)


def test_child_supervisor_restarts_on_same_address():
    from paddle_tpu.distributed.rpc import RpcClient
    with _EchoSupervisor(2, heartbeat_interval_s=0.1) as sup:
        assert sup.wait_ready(20.0)
        addr0 = sup.addresses[0]
        c = RpcClient(addr0, timeout=5.0, retry=RetryPolicy(
            max_retries=25, backoff_base_s=0.05, backoff_max_s=0.25))
        pid_before = c.call("stats")["pid"]
        sup.kill(0)
        # the retrying client reconnects straight through the restart to
        # the SAME address — a NEW process answering there
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            try:
                if c.call("stats")["pid"] != pid_before:
                    break
            except Exception:
                pass
            time.sleep(0.05)
        assert c.call("stats")["pid"] != pid_before
        assert sup.addresses[0] == addr0 and sup.restarts[0] == 1
        assert sup.child_alive(0)
        c.close()


def test_child_supervisor_gives_up_after_max_restarts():
    with _CrashLoopSupervisor(1, heartbeat_interval_s=0.05,
                              max_restarts=2) as sup:
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if sup.restarts[0] >= 2 and not sup.child_alive(0):
                break
            time.sleep(0.05)
        assert sup.restarts[0] == 2       # capped, not a hot loop
        assert not sup.child_alive(0)


def test_pserver_supervisor_rides_shared_helper():
    """Structural pin for the dedup satellite: PserverSupervisor IS a
    ChildSupervisor (same loop the fleet reuses), its heartbeat stays on
    the pserver ``stats`` surface, its children keep the fixed-address +
    per-shard-checkpoint spec, and the startup grace that the fleet needs
    stays ZERO here (original wedge-detection timing unchanged). The
    behavioral pin is test_fault_injection.py's kill-restore e2e."""
    import paddle_tpu.distributed.launch as launch
    assert issubclass(PserverSupervisor, ChildSupervisor)
    sup = PserverSupervisor.__new__(PserverSupervisor)
    sup._cfg = {}
    sup._ckpt_dir = "/tmp/x"
    sup.addresses = [("127.0.0.1", 1234)]
    target, args = sup._child_spec(0)
    assert target is launch._pserver_child
    assert args[0] == ("127.0.0.1", 1234)
    assert args[1] == sup.checkpoint_path(0)
    import inspect
    sig = inspect.signature(ChildSupervisor.__init__)
    assert sig.parameters["startup_grace_s"].default == 0.0
    assert sig.parameters["mp_start_method"].default == "fork"


# ---------------------------------------------------------------------------
# one process per chip: the supervising parent stays off JAX, and a replica
# that cannot get its device fails loudly and promptly
# ---------------------------------------------------------------------------

_OFF_JAX_PARENT = """
import sys, time
from jax._src import xla_bridge
from paddle_tpu.serving import FleetSupervisor

root = sys.argv[1]
# a fleet whose replicas are told to use a platform this host does not
# have, next to a healthy one: both supervised from THIS process
bad = FleetSupervisor(root, "mlp", n_replicas=1, jax_platform="tpu")
good = FleetSupervisor(root, "mlp", n_replicas=1)
try:
    assert good.wait_ready(120)
    fm = good.fleet_metrics()
    assert fm["device_kind"] == "cpu" and fm["n_devices"] >= 1, fm
    t0 = time.monotonic()
    try:
        bad.wait_ready(120)
    except RuntimeError as e:
        assert "could not get its accelerator" in str(e), e
    else:
        raise AssertionError("a replica without a device passed wait_ready")
    assert bad.restarts == [0] and not bad.child_alive(0)
    print("surfaced_after_s", round(time.monotonic() - t0, 1))
finally:
    good.stop()
    bad.stop()
assert not xla_bridge.backends_are_initialized(), "the parent touched jax"
print("PARENT_OFF_JAX_OK")
"""


def test_fleet_parent_stays_off_jax_and_deviceless_replica_fails_fast(
        tmp_path):
    import subprocess
    import sys

    d, _, _ = _export_model(tmp_path)
    root = str(tmp_path / "registry")
    ModelRegistry(root).publish("mlp", d)
    r = subprocess.run(
        [sys.executable, "-c", _OFF_JAX_PARENT, root], capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PARENT_OFF_JAX_OK" in r.stdout
    # the replica's own message names the cause; the supervisor repeats it
    assert "cannot get its accelerator (platform=tpu)" in r.stderr
    assert "exited code 75" in r.stderr

"""BENCHMARK.json held to the rules the driver refuses a manifest by, before
the driver does — among them the one that refused PR 22: every (config,
traffic) pair occurs once. No JAX is touched here."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(path):
    with open(path) as f:
        return json.load(f)


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 10 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert len(manifest["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # the full check with all 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_name_and_unit_is_well_formed(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer") and "metric"
                          or group, entry["name"]))
    assert len(names) == len(set(names)), "a name is given twice"
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)


def test_every_config_traffic_pair_occurs_once(manifest):
    """The rule that refused PR 22: the driver keys a cell by (config,
    traffic), not by chips."""
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:                    # setup_s, one more, one per layer
        assert cell in e2e["setup_s"]
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_every_file_the_manifest_names_exists_and_loads(manifest):
    paths = manifest["paths"]
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in paths)
    used = set()
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in paths)
        cfg = _load(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert 0 < cfg["reference"]["rel_tolerance"] <= 0.01
        assert all(0 < t <= 0.01 for t in cfg["reference"].get(
            "probe_rel_tolerance", {}).values())
        assert cfg["reference"]["reason"]
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(ROOT, c["file"])), "model.py"))
    for w in manifest["workloads"]:
        used.add(w["config"])
        traffic = _load(os.path.join(BENCH, "traffic",
                                     w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            BENCH, "generators", traffic["generator"] + ".py"))
    assert used == {c["name"] for c in manifest["configs"]}
    for m in manifest["per_layer"]:
        spec = _load(os.path.join(BENCH, "layer_metrics",
                                  m["name"] + ".json"))
        for key in ("layer", "unit", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    cmd = manifest["command"]
    assert os.path.exists(os.path.join(ROOT, cmd[1]))
    assert any(cmd[1].startswith(p + "/") for p in paths)


def test_modules_load_without_touching_a_device(manifest):
    """Every generator, reader and configuration module imports; importing
    them asks JAX for no device."""
    import sys
    sys.path.insert(0, ROOT)
    try:
        from benchmark import harness
        for kind in os.listdir(os.path.join(BENCH, "generators")):
            if kind.endswith(".py"):
                mod = harness.load_module(
                    os.path.join(BENCH, "generators", kind), "g_" + kind[:-3])
                assert callable(mod.open_session)
        for kind in os.listdir(os.path.join(BENCH, "readers")):
            if kind.endswith(".py"):
                mod = harness.load_module(
                    os.path.join(BENCH, "readers", kind), "r_" + kind[:-3])
                assert callable(mod.read)
        for c in manifest["configs"]:
            mod = harness.load_module(os.path.join(
                os.path.dirname(os.path.join(ROOT, c["file"])), "model.py"),
                "m_" + c["name"])
            for fn in ("build", "device_batch", "batch_counts",
                       "run_reference", "train_flops"):
                assert callable(getattr(mod, fn)), (c["name"], fn)
    finally:
        sys.path.remove(ROOT)

"""Packaging (SURVEY.md §2.6): the framework builds into an installable
wheel carrying every subpackage plus the native sources.

Reference: the CMake superbuild + manylinux wheel tooling
(/root/reference/CMakeLists.txt, tools/manylinux1/); here a setuptools
pyproject with lazily-compiled native pieces.
"""

import os
import subprocess
import sys
import zipfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_wheel_builds_with_all_subpackages(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps",
         "--no-build-isolation", "-w", str(tmp_path), REPO],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    wheels = [f for f in os.listdir(tmp_path) if f.endswith(".whl")]
    assert len(wheels) == 1, wheels

    names = set(zipfile.ZipFile(tmp_path / wheels[0]).namelist())
    # every user-facing subpackage ships
    for mod in ("paddle_tpu/__init__.py", "paddle_tpu/fluid/__init__.py",
                "paddle_tpu/fluid/analysis/__init__.py",
                "paddle_tpu/v2/__init__.py", "paddle_tpu/ops/__init__.py",
                "paddle_tpu/ops/pallas/__init__.py",
                "paddle_tpu/parallel/__init__.py",
                "paddle_tpu/parallel/planner.py",
                "paddle_tpu/distributed/__init__.py",
                "paddle_tpu/serving/__init__.py",
                "paddle_tpu/serving/autoscale.py",
                "paddle_tpu/serving/execcache.py",
                "paddle_tpu/serving/generate/__init__.py",
                "paddle_tpu/serving/generate/kvstore.py",
                "paddle_tpu/online/__init__.py",
                "paddle_tpu/obs/__init__.py",
                "paddle_tpu/obs/slo.py",
                "paddle_tpu/obs/recorder.py",
                "paddle_tpu/obs/perf.py",
                "paddle_tpu/dataset/__init__.py",
                "paddle_tpu/reader/__init__.py",
                "paddle_tpu/trainer/__init__.py",
                "paddle_tpu/utils/__init__.py",
                "paddle_tpu/trainer_config_helpers/__init__.py"):
        assert mod in names, mod
    # native sources ship for on-demand compilation
    assert "paddle_tpu/native/recordio.cc" in names
    assert "paddle_tpu/capi/paddle_tpu_capi.c" in names
    assert "paddle_tpu/capi/paddle_tpu_capi.h" in names
    # the paddle_trainer console entry point is declared
    meta = [n for n in names if n.endswith("entry_points.txt")]
    assert meta, names
    entry = zipfile.ZipFile(tmp_path / wheels[0]).read(meta[0]).decode()
    assert "paddle_trainer" in entry


def test_tools_scripts_compile():
    """Operator tools (not shipped in the wheel) at least exist and
    byte-compile — a syntax error here would only surface on an
    operator's box otherwise."""
    import py_compile

    for name in ("kernel_probe.py", "plan_parallel.py"):
        path = os.path.join(REPO, "tools", name)
        assert os.path.exists(path), path
        py_compile.compile(path, doraise=True)

"""The rehearsal tests run whole cells in this process, and a cell's check
(d), "no Pallas kernel ran interpreted", reads a count the program keeps
for the whole process. A test file that ran interpreted kernels earlier in
the same worker (``kernel_tier=pallas`` on the CPU) would fail it, so the
count starts at zero for every test of this directory."""

import sys

import pytest


@pytest.fixture(autouse=True)
def _pallas_dispatch_counts_start_at_zero():
    if "paddle_tpu.ops.pallas" in sys.modules:
        from paddle_tpu.obs.metrics import REGISTRY
        family = REGISTRY.get("paddle_tpu_pallas_dispatches")
        if family is not None:
            family.reset()
    yield

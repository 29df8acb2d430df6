"""Test harness config.

Default lane (per SURVEY.md §4.5): unit tests run on the CPU backend with
8 forced host devices (xla_force_host_platform_device_count) so
multi-device/kvstore/shard_map logic is exercised without TPU hardware;
under the harness's ``MX_FORCE_CPU=1`` pin `mx.tpu(i)` resolves to the
i-th host device.  Must run before jax is imported anywhere.

TPU lane (SURVEY.md §4.2 — "the rebuild's most important pattern"):
``MX_TEST_CTX=tpu python -m pytest tests/test_operator.py tests/test_gluon.py``
re-runs the suite with the REAL chip as the default context (mx.tpu(0) is
TPU device 0), in this one process — a chip belongs to one process at a
time.  With no chip every test of the lane FAILS (mx.tpu(0) raises); it
does not skip.  Multi-device mesh tests are not part of this lane (one
chip) — point it at the op battery and gluon files, the ctx-sensitive
surface.
"""
import os
import sys

TPU_LANE = os.environ.get("MX_TEST_CTX", "").lower() == "tpu"

if not TPU_LANE:
    # force: tests must not touch the chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["MX_FORCE_CPU"] = "1"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest


def pytest_configure(config):
    # chaos lane: fault-injection tests (tests/test_fault.py).  They run
    # inside tier-1's `not slow` selection — the FaultInjector's virtual
    # clock keeps retry/backoff schedules sleep-free, so determinism
    # comes from exact call ordinals, not wall-clock races.
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (virtual delays, "
        "no real sleeps; kept fast enough for tier-1)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `not slow` selection")


@pytest.fixture(autouse=True)
def _seeded():
    """Reference: @with_seed() — fixed seeds, logged for reproducibility;
    in the TPU lane every test additionally runs under a tpu(0) default
    context (the reference's ctx-parametrized GPU rerun)."""
    np.random.seed(1234)
    import mxnet_tpu as mx
    mx.random.seed(1234)
    if TPU_LANE:
        ctx = mx.tpu(0)
        ctx.jax_device          # no chip: raises, and the test fails
        with ctx:
            yield
    else:
        yield

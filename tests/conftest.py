"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

The reference has no offline test substrate at all (SURVEY.md §4: "no unit tests, no
CI config, no mocks"); its only gate is a live cluster smoke test. We do better per
SURVEY.md §4's recommendation: the whole engine runs under JAX_PLATFORMS=cpu with 8
virtual devices so sharding/parallelism is testable with zero TPUs.
"""

import os

# Must run before JAX initializes its backend. Unit tests are DEFINED to run on the
# virtual CPU mesh — on any machine, a chip attached or not: 8 host-platform devices
# make sharding testable with zero TPUs, and TPU default matmul precision would break
# the float32 parity tolerances. chip_smoke.py and bench.py are the on-chip paths.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# LockSan (serving/locksan.py): TPU_LOCKSAN=1 runs the whole session under
# the deterministic lock-order sanitizer. Install must precede the serving
# imports inside test modules so every serving/ lock construction is seen —
# conftest import time is before any collection, which guarantees that.
_LOCKSAN = os.environ.get("TPU_LOCKSAN") == "1"
if _LOCKSAN:
    from aws_k8s_ansible_provisioner_tpu.serving import locksan

    locksan.install()


@pytest.fixture(autouse=True, scope="session")
def _locksan_gate():
    """Fail the session if the sanitizer recorded any violation. Tests that
    provoke violations on purpose (tests/test_locksan.py) reset() before
    returning, so anything left here leaked from real serving code."""
    yield
    if _LOCKSAN:
        from aws_k8s_ansible_provisioner_tpu.serving import locksan

        vs = locksan.violations()
        assert not vs, "LockSan violations leaked from the run:\n" + \
            locksan.report()

# NOTE: do NOT enable jax's persistent compilation cache here — serializing
# INTERPRET-mode Pallas executables (the CPU test path for every kernel)
# segfaults in put_executable_and_time (observed: full-suite crash in
# test_sliding_window's pallas-interpret engine test). The bench/server
# caches are safe: on TPU the kernels lower to serializable Mosaic custom
# calls, and the CPU fallback resolves to the XLA attention path.


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled executables between test modules: hundreds of live XLA
    CPU programs in one process eventually segfault the compiler itself
    (observed at ~85% of a serial full-suite run, independent of which file
    lands there). Module granularity keeps module-scoped fixtures (shared
    engines/params) coherent — their traced functions just recompile on
    next use."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs

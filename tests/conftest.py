"""Test harness: force an 8-device simulated CPU mesh (SURVEY.md §4).

The reference has no tests at all; its de-facto strategy is a golden
input/output pair plus manual multi-process runs (SURVEY.md §4).  Here the
"cluster" for tests is JAX's CPU multi-device simulation, so distributed
behavior (shard_map, all_to_all, fault reassignment) runs in-process.

The platform is pinned both through the environment and through
``jax.config.update``, before any backend is initialized.
"""

import os

if os.environ.get("DSORT_TPU_TESTS") == "1":
    # Hardware-gate mode: leave the real backend in charge so
    # tests/test_tpu_smoke.py runs on the chip —
    #   DSORT_TPU_TESTS=1 python -m pytest tests/test_tpu_smoke.py -q
    import jax
else:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_enable_x64", True)  # 64-bit key dtypes (BASELINE config #3)
# In-process `dsort` calls turn on the persistent compile cache (a chip
# run's cache); the suite's CPU compiles stay out of it.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 simulated CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from dsort_tpu.parallel.mesh import local_device_mesh

    return local_device_mesh(8)


@pytest.fixture
def fresh_compile_ledger():
    """Empty the process-wide compile ledger before the test.

    Fleet agents advertise the ledger's labels for locality routing, so
    variants compiled by earlier tests in the same process would steer
    where a job lands.  Modules that build ``FleetAgent``s apply this to
    every test with ``pytestmark``."""
    from dsort_tpu.obs.prof import LEDGER

    LEDGER.reset()

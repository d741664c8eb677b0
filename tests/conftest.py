"""Test config: run on a virtual 8-device CPU mesh (no TPU needed in CI).

Mirrors the reference's approach of testing distributed behavior without a
cluster (SURVEY §4: local-cluster + transport mocks): JAX is forced onto CPU
with 8 virtual devices so sharding/collective paths compile and run.
"""
import os

# The tests run on the CPU backend wherever they run, a machine with a chip
# included: set both the environment (inherited by child processes) and
# jax.config (wins over an environment that names another platform).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({
        "spark.rapids.tpu.batchRowsMinBucket": 8,
        "spark.rapids.tpu.shuffle.partitions": 4,
    })


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Compiled-program caches accumulate across the whole suite (every
    jitted kernel x shape combo); XLA's CPU compiler can exhaust memory and
    segfault near the end. Dropping caches between modules keeps peak
    memory bounded while preserving within-module reuse."""
    yield
    import jax
    jax.clear_caches()
    from spark_rapids_tpu.utils.compile_cache import clear_cache
    clear_cache()


@pytest.fixture(autouse=True, scope="module")
def _drain_oom_telemetry_per_module():
    """OOM-ladder failures queue postmortem/retry records in process-wide
    stores for the event-log writer to fold into the NEXT query. Tests
    that exercise the ladder outside a query would otherwise leak those
    records into whichever module logs a query next — drain between
    modules so each starts clean."""
    yield
    from spark_rapids_tpu.memory.retry import reset_retry_state
    from spark_rapids_tpu.utils.memprof import active
    reset_retry_state()
    mp = active()
    if mp is not None:
        mp.drain_postmortems()


@pytest.fixture(autouse=True, scope="module")
def _drain_degradation_state_per_module():
    """The degradation layer's quarantine store, fallback ledger and
    deadline state are process-wide by design (exec/fallback.py,
    utils/deadline.py). A module that drove operators into quarantine
    would otherwise poison the NEXT module's planning (its operators
    silently route to host) — reset between modules, and restore the
    production defaults for the sticky fallback.* config."""
    yield
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec.fallback import (configure_fallback,
                                                reset_fallback_state)
    from spark_rapids_tpu.utils.deadline import reset_deadline
    reset_fallback_state()
    configure_fallback(RapidsConf({}))
    reset_deadline()


@pytest.fixture(autouse=True, scope="module")
def _drain_shuffle_observatory_per_module():
    """The shuffle observatory is process-wide and installed by whichever
    session configured it last (shuffle/telemetry.py). A module that
    turned it on would otherwise keep every later module's transfers
    recording — and its per-query accumulators would leak into the next
    module's shuffle_summary records. Reset between modules so the
    default (off, zero-overhead) state is restored."""
    yield
    from spark_rapids_tpu.shuffle.telemetry import reset_shuffle_telemetry
    reset_shuffle_telemetry()


@pytest.fixture(autouse=True, scope="module")
def _drain_movement_state_per_module():
    """The movement ledger is process-wide and installed by whichever
    session configured it last (utils/movement.py). A module that turned
    the observatory on would otherwise keep every later module's funnels
    recording — and its per-query accumulators would leak into the next
    module's movement_summary records. Clear the ledger between modules
    so the default (off, zero-overhead) state is restored."""
    yield
    from spark_rapids_tpu.utils.movement import reset_movement
    reset_movement()

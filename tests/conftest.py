"""Test configuration: run everything on a virtual 8-device CPU mesh so that
distributed (shard_map) paths are exercised without TPU hardware
(SURVEY.md §4: single-process multi-device testing the reference never had)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# persist even sub-second compiles: the suite lowers thousands of small
# programs and re-pays their compile time every run with the 1.0 s default
# (lightgbm_tpu.__init__ reads this knob when it configures the cache)
os.environ.setdefault("LGBM_TPU_JAX_CACHE_MIN_COMPILE_S", "0.05")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# lockwatch must patch threading.Lock/RLock BEFORE any product module runs
# and creates its locks, and importing lightgbm_tpu.analysis.lockwatch the
# normal way would pull in the full package (and jax) first — so load it by
# file path, registered under its canonical sys.modules key so later normal
# imports reuse this instance
import importlib.util as _ilu
import sys as _sys

_lw_spec = _ilu.spec_from_file_location(
    "lightgbm_tpu.analysis.lockwatch",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "lightgbm_tpu", "analysis", "lockwatch.py"))
lockwatch = _ilu.module_from_spec(_lw_spec)
_sys.modules["lightgbm_tpu.analysis.lockwatch"] = lockwatch
_lw_spec.loader.exec_module(lockwatch)
lockwatch.install()

import numpy as np
import pytest

import jax

# the suite runs on the CPU backend whatever the caller's environment says
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
jax.config.update("jax_persistent_cache_enable_xla_caches", "all")

# collectivewatch patches the multihost_utils collective entry points so the
# suite's DCN rendezvous land in a process-global ledger; unlike lockwatch it
# needs jax ALREADY importable, so the normal import is fine here. The pod
# drill workers install their own per-rank instances (see tests/_pod_worker.py)
from lightgbm_tpu.analysis import collectivewatch

collectivewatch.install()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# default wall budget for a @pytest.mark.chaos test: recovery paths that work
# finish in a few seconds on the CPU mesh, and a HUNG one (deadlocked queue,
# retry loop that never terminates) must fail here, not at the tier-1
# wall where it would take the whole suite down with it
CHAOS_TIMEOUT_S = 120


@pytest.fixture(autouse=True)
def _chaos_timeout(request):
    """SIGALRM watchdog for chaos-marked tests (pytest runs tests on the main
    thread, so the alarm interrupts even a blocking queue.get)."""
    import signal
    m = request.node.get_closest_marker("chaos")
    if m is None or not hasattr(signal, "SIGALRM"):
        yield
        return
    budget = int(m.kwargs.get("timeout", CHAOS_TIMEOUT_S))

    def _timed_out(signum, frame):
        raise TimeoutError(
            f"chaos test exceeded its {budget}s timeout guard — a recovery "
            "path is hung (see pytest.ini 'chaos' marker)")

    old = signal.signal(signal.SIGALRM, _timed_out)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)

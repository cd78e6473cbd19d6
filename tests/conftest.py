"""Test env: force the CPU backend with 8 virtual devices BEFORE jax loads,
so mesh/sharding tests exercise real collectives without TPU hardware
(SURVEY.md §4's prescribed strategy)."""

import os

# Force CPU even on a machine with a chip: tests must not occupy it and
# need 8 virtual devices. The environment covers the subprocesses tests
# spawn; the config update covers this process, where a plugin may have
# imported jax (latching JAX_PLATFORMS) before this file runs.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _reset_faults_and_metrics():
    from tfidf_tpu.utils.faults import global_injector
    from tfidf_tpu.utils.metrics import global_metrics
    from tfidf_tpu.utils.storage import global_storage
    yield
    global_injector.disarm()
    global_injector.fired.clear()
    global_storage.heal()
    global_storage.fired.clear()
    global_metrics.reset()


@pytest.fixture(scope="session", autouse=True)
def _lockdep_witness():
    """GRAFTCHECK_LOCKDEP=1 runs the WHOLE selected suite under the
    instrumented Lock (tools/graftcheck/witness.py): every lock the
    package constructs during the run is order-tracked, and at session
    end the observed acquisition orders must contain zero inversions
    and nothing the static lock graph cannot explain. The CI graftcheck
    job runs the chaos/resilience suites this way; plain runs are
    untouched (raw threading primitives)."""
    if os.environ.get("GRAFTCHECK_LOCKDEP") != "1":
        yield
        return
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # make sure every package module exists BEFORE install: the witness
    # patches already-imported module namespaces only
    import tfidf_tpu.cli  # noqa: F401
    import tfidf_tpu.cluster.node  # noqa: F401
    import tfidf_tpu.engine.pipeline  # noqa: F401
    import tfidf_tpu.parallel.mesh  # noqa: F401
    from tools.graftcheck.witness import LockdepWitness
    w = LockdepWitness()
    w.install()
    yield
    w.uninstall()
    # min_multilock_edges=1: a witness that observed NOTHING is a
    # broken witness (proxy bypassed, install ordering drifted), not a
    # clean run — the gate must fail vacuous passes
    rep = w.check(min_multilock_edges=1)
    print(f"\nlockdep witness: {len(rep['observed_edges'])} multi-lock "
          f"ordering(s) observed, 0 inversions, all statically "
          f"explained")


@pytest.fixture(scope="session", autouse=True)
def _protocol_witness():
    """GRAFTCHECK_PROTOCOL=1 runs the selected suite with the handler
    classes instrumented (tools/graftcheck/protocol_witness.py): every
    real HTTP exchange is recorded, and at session end each one must be
    explained by the statically computed wire contract (routes,
    statuses, required stamps) while the core scatter/mutation surface
    must actually have been exercised. `make protocol-witness` runs the
    router + partition suites this way; plain runs are untouched."""
    if os.environ.get("GRAFTCHECK_PROTOCOL") != "1":
        yield
        return
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.graftcheck.protocol_witness import (CORE_EXERCISED,
                                                   ProtocolWitness)
    w = ProtocolWitness()
    w.install()
    yield
    w.uninstall()
    rep = w.check(require_exercised=CORE_EXERCISED, min_exchanges=50)
    print(f"\nprotocol witness: "
          f"{sum(w.exchanges.values())} exchange(s) across "
          f"{len(rep['paths'])} endpoint(s) observed, all explained "
          f"by the static wire contract")


@pytest.fixture(scope="session", autouse=True)
def _device_witness():
    """GRAFTCHECK_DEVICE=1 runs the selected suite under the device
    witness (tools/graftcheck/device_witness.py): XLA compile events
    are counted and the ``np`` binding in every package module records
    d2h fetches of device arrays — at session end every observed
    transfer site must be explained by the static devicecheck cone
    (the named fetch stage or an allowlisted-with-reason site). The
    per-test compile churn of a suite is expected, so the suite-wide
    gate checks transfers only; the steady-state zero-recompile gate
    is the dedicated test in tests/test_devicecheck.py.
    GRAFTCHECK_DEVICE_MIN floors the observation count (vacuous-pass
    guard: `make device-witness` sets it, single-suite debugging runs
    need not). Plain runs are untouched (raw numpy)."""
    if os.environ.get("GRAFTCHECK_DEVICE") != "1":
        yield
        return
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # the witness patches already-imported module namespaces only
    import tfidf_tpu.engine.pipeline  # noqa: F401
    import tfidf_tpu.engine.searcher  # noqa: F401
    import tfidf_tpu.engine.tiering  # noqa: F401
    from tools.graftcheck.device_witness import DeviceWitness
    w = DeviceWitness()
    w.install()
    yield
    w.uninstall()
    w.check(min_observations=int(
        os.environ.get("GRAFTCHECK_DEVICE_MIN", "0")))
    print("\n" + w.report() + "\n  all transfer sites statically "
          "explained")

"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests run on CPU with 8 virtual devices so multi-chip sharding paths
(lane sharding over a Mesh) are exercised without an accelerator, per
the reference test strategy of substituting in-memory fakes for the
real transport (SURVEY.md section 4). The chip itself is reached only
through ``chip_smoke.py`` (README, "Running on the chip").

This must run before anything imports jax and initializes a backend.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

# tpusan must patch threading BEFORE jax (and the package under test)
# create any locks, so this sits above the jax import. Activated only
# by TENDERMINT_TPU_SANITIZE=1|hb|explore:<seed> (ci_checks.sh);
# install() parses the mode from the env var itself.
from tendermint_tpu.libs import sanitizer as _sanitizer

if _sanitizer.enabled_from_env():
    _sanitizer.install()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


def pytest_terminal_summary(terminalreporter):
    """With the sanitizer on, print its findings at the end of the run.
    ci_checks.sh greps the output for the LOCK-ORDER CYCLE and
    DATA RACE markers."""
    if _sanitizer.installed():
        class _Writer:
            def write(self, text):
                terminalreporter.write(text)

        terminalreporter.section("tpusan (concurrency sanitizer)")
        _sanitizer.print_report(_Writer())


@pytest.fixture(autouse=True)
def _tpusan_explore():
    """Under TENDERMINT_TPU_SANITIZE=explore:<seed>, serialize each
    test's threads through the seeded cooperative scheduler. Threads
    started outside the test (jax pools, leaked daemons) free-run; the
    per-test scope keeps the schedule a pure function of the seed."""
    if _sanitizer.active_mode() == "explore":
        with _sanitizer.explore_scope():
            yield
    else:
        yield


@pytest.fixture(autouse=True)
def _fresh_verify_caches(monkeypatch):
    """Pin the verify caches to a known state per test.

    The result cache defaults ON in production; under pytest the suite
    reuses identical (pk, msg, sig) triples across tests, so a default-on
    cache would short-circuit device paths other tests assert on
    (fallback counters, kernel dispatch warnings). Tests that exercise
    the caches opt back in with monkeypatch (tests/test_precompute.py).
    """
    from tendermint_tpu.ops import precompute
    from tendermint_tpu.parallel import mesh

    monkeypatch.setenv(precompute._RESULT_ENV, "0")
    precompute.reset()
    # Pin the sharded verify engine OFF for the general suite: with the
    # virtual 8-mesh above, any ≥256-lane verify would otherwise shard
    # and recompile per shape, blowing the tier-1 time budget. Mesh
    # tests (tests/test_mesh.py) opt back in with monkeypatch.
    monkeypatch.setenv(mesh.MESH_ENV, "1")
    mesh.manager.reset()
    yield
    mesh.manager.reset()


@pytest.fixture
def ring_tracer():
    """The program's tracer recording into its ring for one test."""
    from tendermint_tpu.libs import tracing

    tracing.configure("ring")
    tracing.tracer.clear()
    yield tracing.tracer
    tracing.configure("off")
    tracing.tracer.clear()

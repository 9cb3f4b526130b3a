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

import signal
import threading

import pytest

# The files the run starts with, longest first, each with the seconds it took
# in a run of the whole suite on a cold ``.jax_cache`` (six workers on 8 cores,
# PR 46): a file whose first test traces and compiles for minutes, and the
# file that runs the benchmark's rehearsal children one after another.
# ``--dist loadfile`` hands files to workers in the order they were collected,
# so a chain that long begun among the last files would be the run's tail;
# begun first it runs beside everything else. A new test file whose first
# compile runs for minutes is named here.
FIRST_FILES = (
    ("test_pallas_sr25519.py", 606),
    ("test_pallas_verify.py", 500),
    ("test_chipbench_rehearsals.py", 474),
    ("test_mesh.py", 274),
    ("test_pipeline_reference.py", 200),
)

# No test may take longer: twice what a whole file is meant to take cold, so
# that a first compile never decides a test. ``@pytest.mark.limit(seconds)``
# raises it for a test whose cold compile alone is known to take longer.
TEST_LIMIT_S = 300.0


def pytest_configure(config):
    # xdist would otherwise hand files out by their number of tests (its
    # ``--loadscope-reorder``, on by default), whatever order they were collected in.
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """FIRST_FILES to the front in their order, every other test where it
    was: the same on every worker, as xdist requires."""
    rank = {name: at for at, (name, _) in enumerate(FIRST_FILES)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


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


JAX_COMPILER = os.path.join("jax", "_src", "compiler.py")


def storing_a_compile(frame) -> bool:
    """Whether ``frame`` or a caller of it is jax's compile-and-store
    (``jax/_src/compiler.py``: the backend's compile, then the write to
    the persistent cache)."""
    while frame is not None:
        if frame.f_code.co_filename.endswith(JAX_COMPILER):
            return True
        frame = frame.f_back
    return False


@pytest.fixture(autouse=True)
def _own_limit(request):
    """Every test has a limit of its own: past it the test fails, by
    itself, and the worker goes on to the next. An interval timer whose
    SIGALRM handler raises in the main thread, which is where pytest and
    xdist run a test; anywhere else, and where there is no such timer,
    there is no limit. A call that holds the interpreter (one XLA
    compile) fails when it returns, not before; and a test that is then
    still inside jax's compile-and-store fails a second after that is
    over, so that the minutes it compiled for are in the cache and the
    next test does not compile them, and fail, again."""
    marker = request.node.get_closest_marker("limit")
    seconds = float(marker.args[0]) if marker else TEST_LIMIT_S
    if not hasattr(signal, "setitimer") or threading.current_thread() is not threading.main_thread():
        yield
        return

    def over(signum, frame):
        if storing_a_compile(frame):
            signal.setitimer(signal.ITIMER_REAL, 1.0)
            return
        pytest.fail("%s ran past its limit of %g s" % (request.node.nodeid, seconds), pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


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

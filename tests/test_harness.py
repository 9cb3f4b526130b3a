"""The suite's own harness: the order of the run and the limit every
test has (tests/conftest.py), the rehearsals' turn at the benchmark's one
trace directory and the ports a test gives its nodes (tests/helpers.py)."""

import os
import socket
import subprocess
import sys
import time

import pytest

from tests import conftest, helpers

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)


def test_every_file_the_run_starts_with_exists_and_is_named_once():
    names = [name for name, _ in conftest.FIRST_FILES]
    assert len(set(names)) == len(names)
    for name, cold_seconds in conftest.FIRST_FILES:
        assert os.path.isfile(os.path.join(TESTS, name)), name
        assert cold_seconds > 0
    # longest first: the run's wall is then the longest file or the sum over the workers
    assert [s for _, s in conftest.FIRST_FILES] == sorted((s for _, s in conftest.FIRST_FILES), reverse=True)


def test_the_run_starts_with_those_files_and_keeps_the_rest_in_order():
    class Item:
        def __init__(self, name):
            self.path = type("Path", (), {"name": name})()

    first = [name for name, _ in conftest.FIRST_FILES]
    rest = ["test_a.py", "test_a.py", "test_z.py", "test_b.py"]
    items = [Item(n) for n in rest[:2] + first[::-1] + rest[2:] + first[:1]]
    conftest.pytest_collection_modifyitems(items)
    assert [i.path.name for i in items] == first[:1] + first + rest


def test_a_limit_that_falls_inside_a_compile_waits_for_it_to_be_stored():
    """The handler's test of where the main thread is: not here, but in
    whatever ``jax/_src/compiler.py`` runs or calls."""
    import sys

    here = sys._getframe()
    assert not conftest.storing_a_compile(here)
    seen = {}
    compiler = compile("seen['inside'] = probe(frame())", os.path.join("site-packages", conftest.JAX_COMPILER), "exec")
    exec(compiler, {"seen": seen, "probe": conftest.storing_a_compile, "frame": lambda: sys._getframe(1)})
    assert seen == {"inside": True}


OVER_ITS_LIMIT = '''
import time
import pytest

@pytest.mark.limit(1)
def test_sleeps_past_its_limit():
    time.sleep(60)

def test_the_next_one_runs():
    pass
'''


def test_a_test_over_its_limit_fails_alone_and_its_worker_goes_on(tmp_path):
    """A child ``pytest`` with this suite's conftest as a plugin and one
    xdist worker: the test that sleeps for a minute under a limit of 1 s
    fails after about a second, saying so, and the test behind it on the
    same worker passes."""
    (tmp_path / "test_over.py").write_text(OVER_ITS_LIMIT)
    began = time.monotonic()
    child = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path / "test_over.py"), "-q", "-p", "tests.conftest",
         "-p", "no:cacheprovider", "-p", "xdist", "-n", "1", "--rootdir", str(tmp_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    took = time.monotonic() - began
    assert "1 failed, 1 passed" in child.stdout, child.stdout[-2000:] + child.stderr[-2000:]
    assert "test_sleeps_past_its_limit ran past its limit of 1 s" in child.stdout
    assert took < 50, took  # the sleep was cut short


@pytest.fixture
def lock_dir(monkeypatch, tmp_path):
    """The lock file of this test's own: no rehearsal of another worker waits on it."""
    monkeypatch.setattr(helpers.tempfile, "gettempdir", lambda: str(tmp_path))
    return tmp_path


@pytest.mark.parametrize(
    "held,wanted,gets_it",
    [(True, False, False), (False, True, False), (True, True, False), (False, False, True)],
    ids=["untraced_behind_traced", "traced_behind_untraced", "traced_behind_traced", "untraced_beside_untraced"],
)
def test_a_turn_at_the_trace_directory_is_waited_for_up_to_a_bound(lock_dir, held, wanted, gets_it):
    with helpers.trace_turn("/some/checkout", exclusive=held, bound=1):
        began = time.monotonic()
        if gets_it:
            with helpers.trace_turn("/some/checkout", exclusive=wanted, bound=1):
                pass
        else:
            with pytest.raises(AssertionError, match="waited 1 s for the trace lock"):
                with helpers.trace_turn("/some/checkout", exclusive=wanted, bound=1, every=0.05):
                    pass
            assert 1 <= time.monotonic() - began < 5
    with helpers.trace_turn("/some/checkout", exclusive=True, bound=1):  # given back
        pass
    with helpers.trace_turn("/another/checkout", exclusive=True, bound=1):
        with helpers.trace_turn("/some/checkout", exclusive=True, bound=1):  # a lock a checkout
            pass


def test_a_rehearsal_that_cannot_have_its_turn_fails_and_says_it_waited(lock_dir, monkeypatch):
    """Whoever holds the lock, ``rehearse_cell`` gives up after its
    child's own ``timeout`` and starts no child."""
    monkeypatch.setattr(helpers.subprocess, "run", lambda *a, **kw: pytest.fail("a child was started"))
    with helpers.trace_turn(ROOT, exclusive=True, bound=1):
        with pytest.raises(AssertionError, match=r"waited 1 s for the trace lock .* another rehearsal holds it"):
            helpers.rehearse_cell("no-such-benchmark.json", "no-such-cell", 1, 0, timeout=1)


def test_a_port_block_was_held_whole_before_any_of_it_was_released(monkeypatch):
    """The first candidate has a taken port in it and is passed over; of
    the block handed out every port was bound before the first was
    closed, and all are free afterwards."""
    events, plain_socket = [], socket.socket

    class Recorded(socket.socket):
        def bind(self, address):
            super().bind(address)
            events.append(("bound", address[1]))

        def close(self):
            if self.fileno() != -1:
                try:
                    events.append(("closed", self.getsockname()[1]))
                except OSError:
                    pass
            super().close()

    monkeypatch.setattr(helpers.socket, "socket", Recorded)
    taken_block, free_block = (helpers.free_port_block(8) for _ in range(2))
    del events[:]
    with plain_socket() as squatter:
        squatter.bind(("127.0.0.1", taken_block + 5))
        got = helpers.free_port_block(8, candidates=iter([taken_block, free_block]))
    assert got == free_block
    mine = [e for e in events if free_block <= e[1] < free_block + 8]
    assert mine[:8] == [("bound", free_block + i) for i in range(8)]
    assert sorted(mine[8:]) == [("closed", free_block + i) for i in range(8)]
    assert ("bound", taken_block + 5) not in events and ("closed", taken_block + 4) in events
    for port in range(free_block, free_block + 8):
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", port))


def test_port_blocks_lie_below_the_ports_the_kernel_hands_out():
    with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
        lowest_ephemeral = int(f.read().split()[0])
    for _ in range(20):
        base = helpers.free_port_block(8)
        assert 10_000 <= base and base + 8 <= lowest_ephemeral

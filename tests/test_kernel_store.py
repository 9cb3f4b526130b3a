"""ops/kernel_store.py: lowered programs kept beside the compile cache.

A hit must not trace (the function is never called), a file that is
not this program must never answer, and concurrent writers must leave
one whole file. Every function lowered here is a cheap one: no test
waits on a kernel's compile.
"""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tendermint_tpu.ops import introspect, kernel_store

AVALS = (
    jax.ShapeDtypeStruct((8, 4), jnp.uint8),
    jax.ShapeDtypeStruct((8,), jnp.int32),
)
ARGS = (np.arange(32, dtype=np.uint8).reshape(8, 4), np.arange(8, dtype=np.int32))


def cheap(rows, idx):
    return rows[:, 0].astype(jnp.int32) + 2 * idx


def boom(*args):
    raise AssertionError("a warm store must not trace the function")


def want():
    return ARGS[0][:, 0].astype(np.int32) + 2 * ARGS[1]


def stored():
    return dict(introspect.accountant.snapshot()["stored_programs"])


def files(root):
    return sorted(os.listdir(root)) if os.path.isdir(root) else []


@pytest.fixture
def store(tmp_path, monkeypatch):
    root = str(tmp_path / "kernel_store")
    monkeypatch.setattr(kernel_store, "directory", lambda: root)
    return root


def test_the_store_lives_in_the_compile_cache_directory(tmp_path):
    """Whatever keeps the compile cache warm keeps the store warm: it
    is a subdirectory of the directory jax was given."""
    base = jax.config.jax_compilation_cache_dir
    assert base  # ops/ed25519_batch set it, or the environment did
    assert kernel_store.directory() == os.path.join(base, "kernel_store")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        assert kernel_store.directory() == str(tmp_path / "kernel_store")
    finally:
        jax.config.update("jax_compilation_cache_dir", base)


def test_miss_writes_and_hit_never_traces(store):
    before = stored()
    call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu", key=("src-a",))
    assert outcome == "miss"
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(*ARGS)), want())
    (name,) = files(store)
    assert name.startswith("cheap-") and name.endswith(".jaxexport")
    # the function now raises when called: a hit hands back a working
    # program all the same, because nothing was traced
    call, outcome = kernel_store.fetch("cheap", boom, AVALS, "cpu", key=("src-a",))
    assert outcome == "hit"
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(*ARGS)), want())
    after = stored()
    assert after.get("miss", 0) - before.get("miss", 0) == 1
    assert after.get("hit", 0) - before.get("hit", 0) == 1
    assert files(store) == [name]


@pytest.mark.parametrize(
    "change",
    ["key", "name", "shape", "dtype"],
)
def test_anything_that_decides_the_program_names_another_file(store, change):
    """A changed source digest (``key``), kernel name or argument is a
    miss: the program made from the other sources is not even looked at."""
    kernel_store.fetch("cheap", cheap, AVALS, "cpu", key=("src-a",))
    (first,) = files(store)
    name, avals, key = "cheap", AVALS, ("src-a",)
    if change == "key":
        key = ("src-b",)
    elif change == "name":
        name = "cheap2"
    elif change == "shape":
        avals = (jax.ShapeDtypeStruct((16, 4), jnp.uint8), jax.ShapeDtypeStruct((16,), jnp.int32))
    else:
        avals = (AVALS[0], jax.ShapeDtypeStruct((8,), jnp.int16))
    with pytest.raises(AssertionError, match="must not trace"):
        kernel_store.fetch(name, boom, avals, "cpu", key=key)
    _, outcome = kernel_store.fetch(name, cheap, avals, "cpu", key=key)
    assert outcome == "miss"
    assert len(files(store)) == 2 and first in files(store)


@pytest.mark.parametrize("damage", ["truncated", "empty", "other_avals", "other_platform"])
def test_a_file_that_is_not_this_program_is_a_miss_and_is_rewritten(store, damage):
    kernel_store.fetch("cheap", cheap, AVALS, "cpu", key=("src-a",))
    (name,) = files(store)
    path = os.path.join(store, name)
    whole = open(path, "rb").read()
    if damage == "truncated":
        open(path, "wb").write(whole[: len(whole) // 2])
    elif damage == "empty":
        open(path, "wb").close()
    elif damage == "other_avals":
        other = (jax.ShapeDtypeStruct((4, 4), jnp.uint8), jax.ShapeDtypeStruct((4,), jnp.int32))
        blob = jax.export.export(jax.jit(cheap), platforms=["cpu"])(*other).serialize()
        open(path, "wb").write(blob)
    else:
        blob = jax.export.export(jax.jit(cheap), platforms=["tpu"])(*AVALS).serialize()
        open(path, "wb").write(blob)
    with pytest.raises(AssertionError, match="must not trace"):
        kernel_store.fetch("cheap", boom, AVALS, "cpu", key=("src-a",))
    call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu", key=("src-a",))
    assert outcome == "miss"
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(*ARGS)), want())
    assert open(path, "rb").read() == whole  # rewritten, and the bytes are deterministic
    _, outcome = kernel_store.fetch("cheap", boom, AVALS, "cpu", key=("src-a",))
    assert outcome == "hit"


def test_concurrent_writers_of_one_key_leave_one_whole_file(store):
    """More writers than cores, all missing at once: every one gets a
    working program, and what is left is one file that deserialises and
    no temporary beside it."""
    n = 2 * (os.cpu_count() or 4)
    start = threading.Barrier(n)
    got, errors = [], []

    def writer():
        try:
            start.wait(timeout=60)
            call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu", key=("race",))
            got.append((outcome, np.asarray(jax.jit(call)(*ARGS))))
        except Exception as exc:  # surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=writer) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors and len(got) == n
    for _, out in got:
        np.testing.assert_array_equal(out, want())
    (name,) = files(store)
    assert name.endswith(".jaxexport")
    _, outcome = kernel_store.fetch("cheap", boom, AVALS, "cpu", key=("race",))
    assert outcome == "hit"


def test_without_a_compile_cache_nothing_is_stored(monkeypatch, tmp_path):
    monkeypatch.setattr(kernel_store, "directory", lambda: None)
    call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu")
    assert outcome == "miss"
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(*ARGS)), want())
    call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu")
    assert outcome == "miss"


def test_a_store_that_cannot_be_written_costs_a_trace_not_the_call(monkeypatch, tmp_path):
    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.setattr(kernel_store, "directory", lambda: str(blocker / "kernel_store"))
    call, outcome = kernel_store.fetch("cheap", cheap, AVALS, "cpu")
    assert outcome == "miss"
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(*ARGS)), want())


def test_source_digest_follows_the_bytes_of_the_files(tmp_path):
    from types import SimpleNamespace

    a, b = tmp_path / "a.py", tmp_path / "b.py"
    a.write_text("X = 1\n")
    b.write_text("Y = 2\n")
    mods = [SimpleNamespace(__file__=str(a)), SimpleNamespace(__file__=str(b))]
    first = kernel_store.source_digest(*mods)
    assert first == kernel_store.source_digest(*mods)
    b.write_text("Y = 3\n")
    assert kernel_store.source_digest(*mods) != first

"""Batched SHA-512 (C extension or fallback) + mod-L reduction vs hashlib."""

import hashlib
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tendermint_tpu.crypto import hashing


def test_sha512_batch_matches_hashlib():
    msgs = [
        b"",
        b"a",
        b"x" * 111,  # one-block padding boundary
        b"y" * 112,  # forces two-block padding
        b"z" * 127,
        b"w" * 128,
        b"v" * 129,
        bytes(range(256)) * 3,
    ]
    got = hashing.sha512_batch(msgs)
    for i, m in enumerate(msgs):
        assert got[i].tobytes() == hashlib.sha512(m).digest(), f"msg {i}"


def test_sha512_batch_large_n():
    msgs = [b"msg-%d" % i for i in range(1000)]
    got = hashing.sha512_batch(msgs)
    for i in (0, 1, 499, 999):
        assert got[i].tobytes() == hashlib.sha512(msgs[i]).digest()


@pytest.mark.parametrize("n", [1, 150, 1023, 1024, 1025, 4096])
def test_both_batch_entries_match_hashlib_on_each_side_of_the_parallel_threshold(n):
    """Batches under ``PARALLEL_MIN_BATCH`` (1,024) are hashed by the
    caller's thread, larger ones by the OpenMP team: same digests, and
    the same challenge scalars from the prefixed entry."""
    rng = np.random.default_rng(n)
    msgs = [rng.bytes(100 + (i % 40)) for i in range(n)]
    prefix = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    plain = hashing.sha512_batch(msgs)
    prefixed = hashing.sha512_batch_prefixed_mod_l(prefix, msgs)
    for i in sorted({0, n // 3, n // 2, n - 2 if n > 1 else 0, n - 1}):
        assert plain[i].tobytes() == hashlib.sha512(msgs[i]).digest(), i
        digest = hashlib.sha512(prefix[i].tobytes() + msgs[i]).digest()
        assert prefixed[i].tobytes() == hashing.reduce_mod_l_int(digest), i


_MU = 2**512 // hashing.L
EDGE_VALUES = {
    "0": 0,
    "1": 1,
    "L-1": hashing.L - 1,
    "L": hashing.L,
    "L+1": hashing.L + 1,
    "2L-1": 2 * hashing.L - 1,
    "2L": 2 * hashing.L,
    "2^252": 2**252,
    "2^256-1": 2**256 - 1,
    "2^512-1": 2**512 - 1,
    "floor(2^512/L)*L-1": _MU * hashing.L - 1,
    "floor(2^512/L)*L": _MU * hashing.L,
    "floor(2^512/L)*L+1": _MU * hashing.L + 1,
}


def _digest_rows(values):
    return np.frombuffer(
        b"".join(v.to_bytes(64, "little") for v in values), dtype=np.uint8
    ).reshape(len(values), 64)


def _drop_library(monkeypatch):
    """The module as it is on a machine with no C compiler: hashlib and
    Python integers lane by lane."""
    monkeypatch.setattr(hashing, "_LIB", None)
    monkeypatch.setattr(hashing, "_LIB_TRIED", True)
    assert hashing.host_hash_impl() == "hashlib"


def _check_reduction(values):
    got = hashing.reduce512_mod_l(_digest_rows(values))
    assert got.shape == (len(values), 32) and got.dtype == np.uint8
    for i, v in enumerate(values):
        assert int.from_bytes(got[i].tobytes(), "little") == v % hashing.L, f"val {i}"
        assert got[i].tobytes() == hashing.reduce_mod_l_int(v.to_bytes(64, "little"))


@pytest.mark.parametrize("name", list(EDGE_VALUES) + ["random4096"])
def test_reduce512_mod_l_matches_python_integers(name):
    """The C reduction against ``int % L``: the values at which a
    Barrett quotient is off by one or two, and 4,096 random digests."""
    assert hashing.host_hash_impl() == "native"
    if name == "random4096":
        rng = np.random.default_rng(42)
        values = [int.from_bytes(rng.bytes(64), "little") for _ in range(4096)]
    else:
        values = [EDGE_VALUES[name]]
    _check_reduction(values)


def test_reduce512_mod_l_without_the_library(monkeypatch):
    _drop_library(monkeypatch)
    rng = np.random.default_rng(43)
    _check_reduction(
        list(EDGE_VALUES.values())
        + [int.from_bytes(rng.bytes(64), "little") for _ in range(64)]
    )
    assert hashing.reduce512_mod_l(np.zeros((0, 64), dtype=np.uint8)).shape == (0, 32)


# After the 64-byte prefix both of SHA-512's padding boundaries: 47 / 48
# end the first block's room for the length field, 111 / 112 the second
# block's; 116 / 117 are what votes sign; 128 and 300 run whole blocks.
CHALLENGE_LENGTHS = (0, 47, 48, 111, 112, 116, 117, 128, 300)


def _challenge_case(n, seed):
    rng = np.random.default_rng(seed)
    msgs = [rng.bytes(CHALLENGE_LENGTHS[(i + n) % len(CHALLENGE_LENGTHS)]) for i in range(n)]
    prefix = rng.integers(0, 256, (n, 64), dtype=np.uint8)
    want = b"".join(
        (
            int.from_bytes(hashlib.sha512(prefix[i].tobytes() + msgs[i]).digest(), "little")
            % hashing.L
        ).to_bytes(32, "little")
        for i in range(n)
    )
    return prefix, msgs, want


@pytest.mark.parametrize("n", [1, 150, 1023, 1024, 4096])
def test_prefixed_mod_l_matches_python_integers_on_each_side_of_the_parallel_threshold(n):
    """The fused entry, every lane: hashed and reduced by the caller's
    thread under ``PARALLEL_MIN_BATCH`` and by the OpenMP team from it."""
    assert hashing.host_hash_impl() == "native"
    prefix, msgs, want = _challenge_case(n, n)
    got = hashing.sha512_batch_prefixed_mod_l(prefix, msgs)
    assert got.shape == (n, 32) and got.dtype == np.uint8
    assert got.tobytes() == want
    # the prefix block may be a view (the engine passes a concatenate,
    # others a slice): same bytes
    wide = np.zeros((n, 80), dtype=np.uint8)
    wide[:, 8:72] = prefix
    assert hashing.sha512_batch_prefixed_mod_l(wide[:, 8:72], msgs).tobytes() == want


@pytest.mark.parametrize("length", CHALLENGE_LENGTHS)
def test_prefixed_mod_l_one_lane_at_each_padding_boundary(length):
    rng = np.random.default_rng(length)
    prefix = rng.integers(0, 256, (1, 64), dtype=np.uint8)
    msg = rng.bytes(length)
    want = hashing.reduce_mod_l_int(hashlib.sha512(prefix[0].tobytes() + msg).digest())
    assert hashing.sha512_batch_prefixed_mod_l(prefix, [msg]).tobytes() == want


@pytest.mark.parametrize("n", [0, 1, 150])
def test_hashlib_fallback_gives_the_native_bytes(n, monkeypatch):
    """No compiler on the machine: the per-lane hashlib loop reduces
    with Python integers, and every entry answers as the library does."""
    prefix, msgs, want = _challenge_case(n, 1000 + n)
    native = (
        hashing.sha512_batch_prefixed_mod_l(prefix, msgs),
        hashing.sha512_batch(msgs),
        hashing.sha512_batch_mod_l(msgs),
    )
    assert hashing.host_hash_impl() == "native"
    _drop_library(monkeypatch)
    fallback = (
        hashing.sha512_batch_prefixed_mod_l(prefix, msgs),
        hashing.sha512_batch(msgs),
        hashing.sha512_batch_mod_l(msgs),
    )
    assert fallback[0].tobytes() == want
    for a, b in zip(native, fallback):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_prefix_block_of_the_wrong_shape_is_refused():
    with pytest.raises(ValueError):
        hashing.sha512_batch_prefixed_mod_l(np.zeros((2, 64), dtype=np.uint8), [b"x"])
    with pytest.raises(ValueError):
        hashing.sha512_batch_prefixed_mod_l(np.zeros((1, 64), dtype=np.int32), [b"x"])
    with pytest.raises(ValueError):
        hashing.reduce512_mod_l(np.zeros((3, 32), dtype=np.uint8))


def test_sha512_batch_mod_l_end_to_end():
    msgs = [b"challenge-%d" % i for i in range(10)]
    got = hashing.sha512_batch_mod_l(msgs)
    assert got.shape == (10, 32)
    for m, g in zip(msgs, got):
        want = int.from_bytes(hashlib.sha512(m).digest(), "little") % hashing.L
        assert int.from_bytes(g.tobytes(), "little") == want
    assert hashing.sha512_batch_mod_l([]).shape == (0, 32)


def test_native_extension_builds():
    # The C path builds in this image (cc present); chip_smoke.py holds
    # the chip machine to the same.
    assert hashing._lib() is not None
    assert hashing.host_hash_impl() == "native"


# --- the loader --------------------------------------------------------------
#
# One library a machine and a source, named by the source's hash. The
# driver runs the parent commit's tree and the change's in turn on one
# machine: whatever the other left in the build directory, this module
# loads a library made from ITS source with every symbol it calls.

_SRC = os.path.join(os.path.dirname(hashing.__file__), os.pardir, "native", "sha512_batch.c")


def _cc(src, out):
    subprocess.run(
        ["cc", "-O1", "-shared", "-fPIC", "-fopenmp", src, "-o", out],
        check=True, capture_output=True, timeout=120,
    )


def _parent_source(tmp_path):
    """``sha512_batch.c`` cut where the reduction starts, as a source
    from before it was added: ``sha512_batch`` and neither
    ``reduce512_mod_l`` nor ``sha512_batch_prefixed_mod_l``."""
    with open(_SRC) as f:
        text = f.read()
    cut = text.index("/* --- reduction mod L")
    path = tmp_path / "parent_sha512_batch.c"
    path.write_text(text[:cut])
    return str(path)


def _hashed_name():
    """The library's name: a hash of every translation unit
    (``hashing._SOURCES``; three since PR 49's ``secp256k1_batch.c``)."""
    h = hashlib.sha256()
    for name in hashing._SOURCES:
        with open(os.path.join(os.path.dirname(_SRC), name), "rb") as f:
            h.update(f.read())
    return "libsha512batch-%s.so" % h.hexdigest()[:12]


def _assert_whole(lib):
    assert lib is not None
    rows = _digest_rows([hashing.L + 5])
    out = np.empty((1, 32), dtype=np.uint8)
    lib.reduce512_mod_l(hashing._ptr(rows), 1, hashing._ptr(out))
    assert int.from_bytes(out.tobytes(), "little") == 5


def _native_child():
    """A new process that loads the module under this one's environment
    (``TENDERMINT_TPU_BUILD_DIR`` with it) and prints which path it got
    and one challenge scalar."""
    code = (
        "import numpy as np; from tendermint_tpu.crypto import hashing;"
        "k = hashing.sha512_batch_prefixed_mod_l(np.zeros((1, 64), np.uint8), [b'm']);"
        "print(hashing.host_hash_impl(), k.tobytes().hex())"
    )
    root = os.path.abspath(os.path.join(os.path.dirname(hashing.__file__), os.pardir, os.pardir))
    return subprocess.Popen(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=root),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
    )


@pytest.fixture
def build_dir(tmp_path, monkeypatch):
    d = tmp_path / "native_build"
    d.mkdir()
    monkeypatch.setenv("TENDERMINT_TPU_BUILD_DIR", str(d))
    return d


@pytest.mark.parametrize("stale_as", ["old_name", "hashed_name", "cut_short"])
def test_loader_is_not_fooled_by_a_stale_library(stale_as, build_dir, tmp_path):
    """What the parent's tree leaves behind: its library under the old
    fixed name (ignored: this source's has another name), or, worse, a
    library of this source's own name without the new symbol or cut
    short (rebuilt once). Either way: native, every symbol there."""
    if stale_as == "old_name":
        _cc(_parent_source(tmp_path), str(build_dir / "libsha512batch.so"))
    elif stale_as == "hashed_name":
        _cc(_parent_source(tmp_path), str(build_dir / _hashed_name()))
        assert hashing._load(str(build_dir / _hashed_name())) is None
    else:
        (build_dir / _hashed_name()).write_bytes(b"\x7fELF not a library")
    _assert_whole(hashing._build_and_load())
    assert sorted(os.listdir(build_dir)) == sorted(
        {_hashed_name()} | ({"libsha512batch.so"} if stale_as == "old_name" else set())
    )
    # and what is there now is whole: the next process builds nothing
    # (this one cannot ask: a path once opened answers from the
    # process's own table of libraries, whatever the file holds now)
    before = os.stat(build_dir / _hashed_name()).st_mtime_ns
    assert _native_child().communicate(timeout=120)[0].split()[0] == b"native"
    assert os.stat(build_dir / _hashed_name()).st_mtime_ns == before


def test_loader_refuses_mangled_names(build_dir):
    """A C++ compiler mangles the entry points: such a library is not
    this module's, wherever it came from."""
    if shutil.which("g++") is None:
        pytest.skip("no g++ here to mangle the names with")
    mangled = str(build_dir / _hashed_name())
    subprocess.run(
        ["g++", "-x", "c++", "-O1", "-shared", "-fPIC", _SRC, "-o", mangled],
        check=True, capture_output=True, timeout=120,
    )
    assert hashing._load(mangled) is None
    _assert_whole(hashing._build_and_load())
    # replaced by a C build, as the next process finds
    assert _native_child().communicate(timeout=120)[0].split()[0] == b"native"


def test_two_processes_on_an_empty_directory_both_end_native(build_dir):
    procs = [_native_child() for _ in range(2)]
    want = hashing.reduce_mod_l_int(hashlib.sha512(bytes(64) + b"m").digest()).hex()
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err.decode()
        assert out.decode().split() == ["native", want]
    assert os.listdir(build_dir) == [_hashed_name()]  # no half-built file left


def test_the_process_that_builds_holds_the_library_under_its_final_name(build_dir):
    """Built under a name of the process's own, renamed, and loaded once
    more from where every later process finds it: ``_name`` is the
    hashed name in the first process of a machine too (a suite on a
    fresh build directory asks, ``tests/test_merlin_native.py``)."""
    lib = hashing._build_and_load()
    _assert_whole(lib)
    assert os.path.basename(lib._name) == _hashed_name()
    assert os.listdir(build_dir) == [_hashed_name()]


def test_loader_raises_where_the_build_should_have_worked(build_dir, monkeypatch):
    """Source and a compiler are there and no library results: an
    error, not the hashlib path in silence. No compiler at all is the
    one machine that hashes through hashlib."""
    monkeypatch.setattr(hashing, "_LIB", None)
    monkeypatch.setattr(hashing, "_LIB_TRIED", False)
    false = shutil.which("false")
    monkeypatch.setattr(hashing.shutil, "which", lambda name: false)
    with pytest.raises(RuntimeError, match="could not build"):
        hashing.host_hash_impl()
    with pytest.raises(RuntimeError, match="could not build"):  # and again: never cached as absent
        hashing.sha512_batch_prefixed_mod_l(np.zeros((1, 64), np.uint8), [b"m"])
    assert os.listdir(build_dir) == []
    monkeypatch.setattr(hashing.shutil, "which", lambda name: None)
    assert hashing.host_hash_impl() == "hashlib"

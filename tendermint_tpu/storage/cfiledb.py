"""ctypes binding for the C++ FileDB engine (native/filedb.cc).

Same KVStore contract and on-disk format as the pure-Python
storage/filedb.py; built lazily with the system compiler (the
hashing.py pattern). ``available()`` gates the storage factory's
engine choice.
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import tempfile
import threading
from typing import Iterator, Optional, Tuple

from tendermint_tpu.storage.kv import KVStore

_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False

_U8P = ctypes.POINTER(ctypes.c_uint8)
_UNBOUNDED = 0xFFFFFFFF
_OPHDR = struct.Struct("<BII")
_RNGHDR = struct.Struct("<II")


def _build_and_load() -> Optional[ctypes.CDLL]:
    src = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "native", "filedb.cc"
    )
    if not os.path.exists(src):
        return None
    build_dir = os.environ.get(
        "TENDERMINT_TPU_BUILD_DIR",
        os.path.join(tempfile.gettempdir(), "tendermint_tpu_native"),
    )
    os.makedirs(build_dir, exist_ok=True)
    lib_path = os.path.join(build_dir, "libfiledb.so")
    if not os.path.exists(lib_path) or os.path.getmtime(lib_path) < os.path.getmtime(
        src
    ):
        # built under a name of this process's own, then renamed:
        # processes that start together (a test run's workers on a
        # fresh build directory) each end with a whole library
        fd, tmp = tempfile.mkstemp(prefix="libfiledb.", suffix=".so", dir=build_dir)
        os.close(fd)
        try:
            for cc in ("g++", "c++"):
                try:
                    subprocess.run(
                        [cc, "-O2", "-shared", "-fPIC", src, "-lz", "-o", tmp],
                        check=True,
                        capture_output=True,
                        timeout=120,
                    )
                    os.chmod(tmp, 0o755)  # mkstemp made it the owner's alone
                    os.replace(tmp, lib_path)
                    break
                except Exception:
                    continue
            else:
                return None
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    try:
        lib = ctypes.CDLL(lib_path)
        lib.filedb_open.argtypes = [ctypes.c_char_p]
        lib.filedb_open.restype = ctypes.c_void_p
        lib.filedb_close.argtypes = [ctypes.c_void_p]
        lib.filedb_get.argtypes = [
            ctypes.c_void_p,
            _U8P,
            ctypes.c_uint32,
            ctypes.POINTER(_U8P),
        ]
        lib.filedb_get.restype = ctypes.c_int64
        lib.filedb_free.argtypes = [ctypes.c_void_p]
        lib.filedb_apply.argtypes = [
            ctypes.c_void_p,
            _U8P,
            ctypes.c_uint64,
            ctypes.c_int,
        ]
        lib.filedb_apply.restype = ctypes.c_int
        lib.filedb_sync.argtypes = [ctypes.c_void_p]
        lib.filedb_sync.restype = ctypes.c_int
        lib.filedb_count.argtypes = [ctypes.c_void_p]
        lib.filedb_count.restype = ctypes.c_uint64
        lib.filedb_garbage.argtypes = [ctypes.c_void_p]
        lib.filedb_garbage.restype = ctypes.c_uint64
        lib.filedb_range.argtypes = [
            ctypes.c_void_p,
            _U8P,
            ctypes.c_uint32,
            _U8P,
            ctypes.c_uint32,
            ctypes.c_int,
            ctypes.POINTER(_U8P),
        ]
        lib.filedb_range.restype = ctypes.c_int64
        lib.filedb_compact.argtypes = [ctypes.c_void_p]
        lib.filedb_compact.restype = ctypes.c_int
        return lib
    except Exception:
        return None


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB_TRIED = True
        _LIB = _build_and_load()
    return _LIB


def available() -> bool:
    return _lib() is not None


def _buf(b: bytes):
    return (ctypes.c_uint8 * max(len(b), 1)).from_buffer_copy(b or b"\0")


class CFileDB(KVStore):
    """KVStore over the native engine; one handle, internally locked."""

    def __init__(self, path: str, fsync_writes: bool = False):
        lib = _lib()
        if lib is None:
            raise RuntimeError("native filedb engine unavailable")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        from tendermint_tpu.storage.filedb import acquire_db_lock

        self._flock = acquire_db_lock(path)
        self._lib = lib
        self._fsync = fsync_writes
        self._h = lib.filedb_open(path.encode())
        if not self._h:
            raise IOError(f"filedb_open failed for {path}")
        self._lock = threading.RLock()

    def get(self, key: bytes) -> Optional[bytes]:
        out = _U8P()
        n = self._lib.filedb_get(self._h, _buf(key), len(key), ctypes.byref(out))
        if n < 0:
            return None
        try:
            return ctypes.string_at(out, n)
        finally:
            self._lib.filedb_free(out)

    COMPACT_MIN_GARBAGE = 4096

    def _apply(self, recs, sync: bool) -> None:
        blob = bytearray()
        for op, key, value in recs:
            blob += _OPHDR.pack(op, len(key), len(value))
            blob += key
            blob += value
        rc = self._lib.filedb_apply(
            self._h, _buf(bytes(blob)), len(blob), 1 if sync else 0
        )
        if rc != 0:
            raise IOError(f"filedb_apply failed rc={rc}")
        garbage = self._lib.filedb_garbage(self._h)
        if garbage >= max(
            self.COMPACT_MIN_GARBAGE, 4 * self._lib.filedb_count(self._h)
        ):
            self.compact()

    def set(self, key: bytes, value: bytes) -> None:
        self._apply([(1, bytes(key), bytes(value))], self._fsync)

    def delete(self, key: bytes) -> None:
        self._apply([(0, bytes(key), b"")], self._fsync)

    def apply_batch(self, ops) -> None:
        self._apply(
            [
                (1 if op == "set" else 0, bytes(k), bytes(v) if v else b"")
                for op, k, v in ops
            ],
            sync=True,
        )

    def _range(
        self, start: Optional[bytes], end: Optional[bytes], reverse: bool
    ) -> Iterator[Tuple[bytes, bytes]]:
        out = _U8P()
        slen = _UNBOUNDED if start is None else len(start)
        elen = _UNBOUNDED if end is None else len(end)
        n = self._lib.filedb_range(
            self._h,
            _buf(start or b""),
            slen,
            _buf(end or b""),
            elen,
            1 if reverse else 0,
            ctypes.byref(out),
        )
        if n < 0:
            raise IOError("filedb_range failed")
        try:
            data = ctypes.string_at(out, n)
        finally:
            self._lib.filedb_free(out)
        off = 0
        while off < len(data):
            klen, vlen = _RNGHDR.unpack_from(data, off)
            off += _RNGHDR.size
            yield data[off : off + klen], data[off + klen : off + klen + vlen]
            off += klen + vlen

    def iterator(self, start=None, end=None):
        return self._range(start, end, reverse=False)

    def reverse_iterator(self, start=None, end=None):
        return self._range(start, end, reverse=True)

    def sync(self) -> None:
        self._lib.filedb_sync(self._h)

    def compact(self) -> None:
        rc = self._lib.filedb_compact(self._h)
        if rc != 0:
            raise IOError(f"filedb_compact failed rc={rc}")

    def close(self) -> None:
        with self._lock:
            if self._h:
                self._lib.filedb_close(self._h)
                self._h = None
            if getattr(self, "_flock", None) is not None:
                from tendermint_tpu.storage.filedb import release_db_lock

                release_db_lock(self._flock)
                self._flock = None

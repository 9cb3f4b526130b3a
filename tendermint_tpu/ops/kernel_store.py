"""Lowered programs, kept beside the compile cache.

jax's persistent compile cache is keyed by the lowered program, so a
process must trace and lower a kernel before it can find out that the
compile is already paid. For the Pallas verifier that walk is the
dearer half: ~31,000 primitive binds and ~9,900 nested ``jit`` calls,
6-7 s a kernel shape on one device (4.3 s of Python, 1.7 s of
lowering) and 12-30 s under ``shard_map`` (PERF.md, PR 35 and PR 36),
every process, whatever the compile cache holds.

:func:`fetch` keeps the walk's result. A miss traces the function once,
lowers it with :mod:`jax.export` (Pallas/TPU kernels are on export's
list of custom calls with a stable ABI) and writes the serialised
module; a hit reads it back, and what the caller then stages is one
``call_exported`` with no kernel body for Python to walk. The bytes are
deterministic and hold no path of the process that loads them, so the
compile cache hits behind a stored program whatever stack reached the
first call, and a program exported for one device may be called under
``shard_map`` over any number of them. One device and a mesh are served
alike (ops/pallas_verify.stored_program, PR 50; the mesh since PR 36): a
restart pays a load of each kernel shape it meets (tenths of a second),
an upgrade — other sources, another jax — one walk and one compile a
shape, once a directory.

The files live in ``<compile cache dir>/kernel_store`` (the directory
``jax.config.jax_compilation_cache_dir`` names, see ops/ed25519_batch),
so whatever keeps one warm keeps the other; with no compile cache
nothing is stored. A file's name is a hash of everything that decides
the program: the jax and jaxlib versions, the platform, the name, every
argument's shape and dtype, and what the caller names in ``key`` (the
device kind, the block, a digest of the kernel's sources). A program
made from other sources never answers: it has another name. A store
that cannot be read or written costs a trace, never a call.
"""

from __future__ import annotations

import hashlib
import os
import tempfile
from typing import Callable, Optional, Sequence, Tuple

import jax
import jaxlib

from tendermint_tpu.ops import introspect

def directory() -> Optional[str]:
    base = jax.config.jax_compilation_cache_dir
    return os.path.join(base, "kernel_store") if base else None


def source_digest(*modules) -> str:
    """Hex digest of the named modules' source files."""
    h = hashlib.sha256()
    for mod in modules:
        with open(mod.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _signature(avals) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
    return tuple((tuple(a.shape), str(a.dtype)) for a in avals)


def _load(path: str, avals, platform: str):
    """The program in ``path`` if it is whole and is this one, else None."""
    try:
        with open(path, "rb") as f:
            exported = jax.export.deserialize(bytearray(f.read()))
    except Exception:  # absent, truncated, or written by another jax
        return None
    if _signature(exported.in_avals) != _signature(avals):
        return None
    if platform not in exported.platforms:
        return None
    return exported


def _write(path: str, blob: bytes) -> None:
    """``blob`` at ``path``, whole or not at all: written under a name
    of the writer's own and renamed into place. A store that cannot be
    written costs the next process a trace, never this call."""
    root = os.path.dirname(path)
    try:
        os.makedirs(root, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=root, suffix=".tmp")
    except OSError:
        return
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError:
        if os.path.exists(tmp):
            os.unlink(tmp)


def fetch(
    name: str, fn: Callable, avals: Sequence, platform: str, key: Sequence = ()
) -> Tuple[Callable, str]:
    """``(callable, "hit" | "miss")`` for ``fn`` at ``avals`` on ``platform``.

    A hit never calls ``fn``. A miss traces and lowers it, and leaves
    the program for the next process; concurrent writers of one key
    leave one whole file. A file that does not deserialise, or whose
    arguments are not ``avals``, is a miss and is rewritten.
    """
    path = None
    root = directory()
    if root is not None:
        ident = repr(
            (jax.__version__, jaxlib.__version__, platform, name,
             _signature(avals), tuple(str(k) for k in key))
        )
        digest = hashlib.sha256(ident.encode()).hexdigest()[:32]
        path = os.path.join(root, "%s-%s.jaxexport" % (name, digest))
        exported = _load(path, avals, platform)
        if exported is not None:
            introspect.note_stored_program("hit")
            return exported.call, "hit"
    exported = jax.export.export(jax.jit(fn), platforms=[platform])(*avals)
    introspect.note_stored_program("miss")
    if path is not None:
        _write(path, exported.serialize())
    return exported.call, "miss"

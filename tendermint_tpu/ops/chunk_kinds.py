"""What a chunk kind is, said once.

A *chunk* is one padded slice of a batch handed to one verify kernel.
Its *kind* fixes everything about that hand-over: which kernel, which
arguments in which order, which of them carry lanes and on which axis,
and what a pad lane looks like. The engines (ops/ed25519_batch,
ops/sr25519_batch) define one :class:`ChunkKind` next to each kernel;
the compile factory, the runner, the dispatch loop and the mesh
(parallel/sharding) read the record and nothing else, so none of them
names a kind. This module imports neither: both sides import it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import numpy as np


@dataclass(frozen=True, eq=False)
class ChunkInput:
    """One kernel argument. ``lane_axis`` is the axis its lanes lie on;
    None marks an input without lanes (the resident store): already on
    the device, replicated on a mesh, untouched by padding and not
    counted as shipped. ``pad()`` is one pad lane's value, of the
    input's dtype and shaped like the input with its lane axis removed."""

    name: str
    lane_axis: Optional[int] = 0
    pad: Optional[Callable[[], np.ndarray]] = None


@dataclass(frozen=True, eq=False)
class ChunkKind:
    name: str  # the spans' ``kind``
    engine: str  # spans, health machine, fault sites (``<engine>.chunk``)
    kernel_name: str  # the ``kernel_compile`` span's ``kernel``
    kernel: Callable  # the XLA graph
    pallas: Optional[str]  # entry point in ops/pallas_verify, if it has one
    inputs: Tuple[ChunkInput, ...]  # in the kernel's argument order
    # what the jitted function is called, so the program's name on a
    # device trace (``jit_<program>``): ``run*`` is what the
    # benchmark's kernel metrics match
    program: str = "run"

    @property
    def store_bound(self) -> bool:
        """An input without lanes lives on the device already, committed
        to the context it was uploaded for (the chunk's ``mesh_key``: one
        mesh's devices, or None for one single device); only that
        context can consume the chunk as it is."""
        return any(i.lane_axis is None for i in self.inputs)

    def args(self, inputs: dict) -> tuple:
        return tuple(inputs[i.name] for i in self.inputs)

    def lanes(self, inputs: dict) -> int:
        first = next(i for i in self.inputs if i.lane_axis is not None)
        return int(inputs[first.name].shape[first.lane_axis])

    def h2d_bytes(self, inputs: dict) -> int:
        """Bytes a prepared chunk hands to its kernel from the host."""
        return sum(
            int(inputs[i.name].nbytes) for i in self.inputs if i.lane_axis is not None
        )

    def pad_lanes(self, inputs: dict, extra: int) -> dict:
        """``inputs`` with ``extra`` pad lanes behind every lane-carrying
        input. Pad lanes verify true and are sliced off at collect."""
        if extra <= 0:
            return inputs
        out = dict(inputs)
        for i in self.inputs:
            if i.lane_axis is None:
                continue
            arr = inputs[i.name]
            shape = list(arr.shape)
            shape[i.lane_axis] = extra
            block = np.broadcast_to(np.expand_dims(i.pad(), i.lane_axis), shape)
            out[i.name] = np.concatenate([arr, block], axis=i.lane_axis)
        return out

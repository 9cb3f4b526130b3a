"""The block-sync apply loop with pipelined device verification.

The reference applies one block per iteration: peek (first, second),
VerifyCommitLight(first <- second.LastCommit), validate, save, apply
(internal/blocksync/reactor.go:538-650). Here the loop peeks a WINDOW of
consecutive blocks and verifies all their commits in one device batch
(parallel/pipeline.py) before applying them in order — the multi-commit
pipeline from SURVEY.md §7 step 8. Over a validator set of mixed key
types (ed25519, sr25519, secp256k1) the window is still one call: the
pipeline plans its lanes by key type across the blocks, one device
sub-batch a type that batches and the secp256k1 lanes of every block in
one host call while those run. A bad verdict falls back to
per-block attribution, bans the peer, and rescheduling.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Callable, List, Optional

from tendermint_tpu.blocksync.pool import BlockPool
from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import State
from tendermint_tpu.storage.blockstore import BlockStore
from tendermint_tpu.types.block import BLOCK_PART_SIZE_BYTES, BlockID
from tendermint_tpu.types.part_set import PartSet

DEFAULT_VERIFY_WINDOW = 16


class PeerTransport:
    """What the syncer needs from the network: ask a peer for a block;
    delivery comes back via pool.add_block (the reactor wires this)."""

    def request_block(self, peer_id: str, height: int) -> None:
        raise NotImplementedError


class BlockSyncer:
    def __init__(
        self,
        state: State,
        block_exec: BlockExecutor,
        block_store: BlockStore,
        transport: PeerTransport,
        pool: Optional[BlockPool] = None,
        verify_window: int = DEFAULT_VERIFY_WINDOW,
        mesh=None,
        use_device: Optional[bool] = None,
        on_caught_up: Optional[Callable[[State], None]] = None,
    ):
        self.state = state
        self.block_exec = block_exec
        self.block_store = block_store
        self.transport = transport
        self.pool = pool or BlockPool(
            max(block_store.height() + 1, state.initial_height)
        )
        self.verify_window = verify_window
        self.mesh = mesh
        self.use_device = use_device
        self.on_caught_up = on_caught_up
        self._stop_flag = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # --- driving -------------------------------------------------------------

    def start(self) -> None:
        self._stop_flag.clear()
        self._thread = threading.Thread(
            target=self._run, name="blocksync", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop_flag.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=10)
        self._thread = None

    def _run(self) -> None:
        while not self._stop_flag.is_set():
            self.step()
            if self.pool.is_caught_up() and self.pool.num_pending() == 0:
                if self.on_caught_up is not None:
                    self.on_caught_up(self.state)
                return
            _time.sleep(0.002)

    def step(self) -> int:
        """One scheduling + apply pass; returns blocks applied."""
        for height, peer_id in self.pool.make_requests():
            self.transport.request_block(peer_id, height)
        self.pool.check_timeouts()
        return self._apply_ready_blocks()

    def _apply_ready_blocks(self) -> int:
        """Peek a window, batch-verify every (block_i <- block_{i+1}.LastCommit)
        pair in ONE device call, then apply the verified prefix."""
        window = self.pool.peek_blocks(self.verify_window + 1)
        if len(window) < 2:
            return 0
        # One valset covers the window only while validators_hash is stable;
        # truncate at the first change (that block is verified next pass,
        # with the post-apply state, exactly like the reference's serial
        # loop would).
        vals = self.state.validators
        stable_hash = window[0].header.validators_hash
        tasks: List[CommitTask] = []
        part_sets: List[PartSet] = []
        for first, second in zip(window, window[1:]):
            if first.header.validators_hash != stable_hash:
                break
            parts = PartSet.from_data(first.to_proto_bytes(), BLOCK_PART_SIZE_BYTES)
            part_sets.append(parts)
            block_id = BlockID(first.hash(), parts.header())
            tasks.append(
                CommitTask(
                    chain_id=self.state.chain_id,
                    vals=vals,
                    block_id=block_id,
                    height=first.header.height,
                    commit=second.last_commit,
                )
            )
            if len(tasks) >= self.verify_window:
                break
        if not tasks:
            return 0
        verdicts = verify_commits_pipelined(
            tasks, mesh=self.mesh, use_device=self.use_device
        )
        applied = 0
        for (first, second), task, parts, verdict in zip(
            zip(window, window[1:]), tasks, part_sets, verdicts
        ):
            if not verdict.ok:
                self.pool.redo_request(first.header.height)
                self.pool.redo_request(second.header.height)
                break
            self.block_store.save_block(first, parts, second.last_commit)
            self.state = self.block_exec.apply_block(
                self.state, task.block_id, first
            )
            self.pool.pop_request()
            applied += 1
        return applied


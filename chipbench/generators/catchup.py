"""Blocksync catch-up: windows of commits of consecutive heights over
one validator set, each window verified in one call of
``parallel/pipeline.verify_commits_pipelined``, exactly as
``blocksync/syncer.BlockSyncer._apply_ready_blocks`` calls it (a list of
``CommitTask``s sharing one ``ValidatorSet`` object, ``mesh=None``,
``use_device=None``). The syncer too builds its task list outside the
verify call, so the lists are built once, in set-up.

At every height a few validators are absent and a few vote nil, drawn
from the seed, so light verification's early exit falls at another
commit index in every block. With equal powers every block still sends
the same number of lanes, which ``run.py`` needs constant."""

from __future__ import annotations

import math
import os
import re
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

from chipbench import reference, reference_light, workload

FAULTS = ("tampered_included", "tampered_skipped", "short_of_power")
REFERENCE_WORKERS = 12
WARM_UP_CALLS = 2


def verify_blocks_in_parallel(blocks: list) -> list:
    """``reference_light.verify_block`` over many blocks. A window of
    16 blocks at 500 validators is 5,344 big-integer verifications of
    ~5 ms; the processes are fresh interpreters that import the plain
    reference and nothing else (no jax: the chip stays this one's)."""
    workers = min(REFERENCE_WORKERS, os.cpu_count() or 1, len(blocks))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as pool:
        return list(pool.map(reference_light.verify_block, *zip(*blocks)))


class Catchup:
    def __init__(self, ctx):
        from tendermint_tpu.ops import precompute
        from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
        from tendermint_tpu.types.validation import NotEnoughVotingPowerError

        from chipbench.generators import cycle_length

        self._verify = verify_commits_pipelined
        self._task = CommitTask
        self._short = NotEnoughVotingPowerError
        self.seed = ctx.seed
        n = int(ctx.config["validators"])
        self.window = int(ctx.config["verify_window"])
        self.n_absent = math.ceil(float(ctx.config["absent_share"]) * n)
        self.n_nil = math.ceil(float(ctx.config["nil_share"]) * n)
        signers = workload.make_signers(ctx.seed, "validators", n)
        self.signers, self.vset = workload.make_validator_set(signers)
        self.addresses = [v.address for v in self.vset.validators]
        self.validators = [(s.pub, 10) for s in self.signers]
        # equal powers: a block sends the votes that pass 2/3 and no more
        self.quorum = n * 2 // 3 + 1
        if n - self.n_absent - self.n_nil < self.quorum:
            raise SystemExit("chipbench: %d validators less absent and nil votes cannot pass 2/3" % n)
        self.lanes_per_call = self.window * self.quorum
        self.count = cycle_length(ctx.traffic, self.lanes_per_call, precompute.results.cap)
        self.windows = [self._window(k) for k in range(self.count)]
        ctx.say(
            "traffic: %d validators, %d absent and %d nil at each height; %d windows "
            "of %d commits cycled, %d lanes a block, %d a call (%d signatures between "
            "two visits of one window; verdict cache holds %d)"
            % (n, self.n_absent, self.n_nil, self.count, self.window, self.quorum,
               self.lanes_per_call, (self.count - 1) * self.lanes_per_call,
               precompute.results.cap)
        )

    # --- generation -------------------------------------------------------

    def _commit(self, height: int, short_of_power: bool = False):
        """The commit of ``height``: ``n_absent`` validators absent,
        ``n_nil`` voting nil, the rest for the block, each vote signed
        over its own canonical sign-bytes. ``short_of_power`` turns
        votes for the block absent until one fewer than a quorum is
        left."""
        from tendermint_tpu.encoding.canonical import Timestamp
        from tendermint_tpu.types import (
            BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, Commit, CommitSig,
        )

        n = len(self.signers)
        order = workload.rng_for(self.seed, "flags", height).permutation(n)
        n_absent = n - self.n_nil - (self.quorum - 1) if short_of_power else self.n_absent
        absent = set(int(i) for i in order[:n_absent])
        nil = set(int(i) for i in order[n_absent:n_absent + self.n_nil])
        times = workload.vote_times(self.seed, "chain", height, n)
        commit = Commit(height=height, round=0, block_id=workload.block_id(self.seed, "chain", height))
        commit.signatures = [
            CommitSig.absent() if i in absent else CommitSig(
                BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT,
                self.addresses[i], Timestamp.from_unix_ns(int(times[i])), b"",
            )
            for i in range(n)
        ]
        for i, cs in enumerate(commit.signatures):
            if i not in absent:
                cs.signature = self.signers[i].sign(commit.vote_sign_bytes(workload.CHAIN_ID, i))
        return commit

    def _window(self, k: int, short_block=None) -> list:
        """The tasks of the k-th window, as the syncer builds them."""
        first = 1 + k * self.window
        commits = [
            self._commit(first + b, short_of_power=(b == short_block))
            for b in range(self.window)
        ]
        return [
            self._task(workload.CHAIN_ID, self.vset, c.block_id, c.height, c)
            for c in commits
        ]

    @staticmethod
    def _for_block(commit) -> list:
        """Commit indices of the votes for the block."""
        return [
            i for i, cs in enumerate(commit.signatures)
            if cs.block_id_flag == reference_light.FLAG_COMMIT
        ]

    def _plain(self, task):
        """A block as the plain reference takes it."""
        commit = task.commit
        return self.validators, [
            (cs.block_id_flag,
             commit.vote_sign_bytes(workload.CHAIN_ID, i) if cs.signature else b"",
             cs.signature)
            for i, cs in enumerate(commit.signatures)
        ]

    def _answer(self, verdict) -> tuple:
        """The program's verdict in the plain reference's words."""
        if verdict.ok:
            return reference_light.OK
        if isinstance(verdict.error, self._short):
            return reference_light.INSUFFICIENT
        m = re.search(r"wrong signature \(#(\d+)\)", str(verdict.error))
        return ("wrong signature", int(m.group(1))) if m else ("refused", str(verdict.error))

    def _faulted(self, j: int, fault: str):
        """(tasks, expected answers): a fresh window past the cycle
        with one fault in one block drawn from the seed."""
        rng = workload.rng_for(self.seed, "fault", fault)
        block = int(rng.integers(self.window))
        k = self.count + j
        tasks = self._window(k, short_block=block if fault == "short_of_power" else None)
        want = [reference_light.OK] * self.window
        commit = tasks[block].commit
        if fault == "short_of_power":
            want[block] = reference_light.INSUFFICIENT
            return tasks, want
        # light verification sends the first ``quorum`` votes for the block
        votes = self._for_block(commit)
        if fault == "tampered_included":
            # s + L: the curve equation holds and only ``s < L`` refuses
            # it, the check an engine is tempted to drop; a flipped bit
            # refuses itself under any equation
            idx = votes[int(rng.integers(self.quorum))]
            want[block] = ("wrong signature", idx)
            kind = "s>=L"
        else:
            idx = votes[int(rng.integers(self.quorum, len(votes)))]
            kind = workload.TAMPER_KINDS[int(rng.integers(len(workload.TAMPER_KINDS)))]
        cs = commit.signatures[idx]
        cs.signature = workload.tamper_signature(cs.signature, kind)
        return tasks, want

    # --- the calls ----------------------------------------------------------

    def warm(self) -> None:
        """The first two windows, as the first two steps of a node that
        catches up. A later window may carry a validator these two never
        reached (its absences move the early exit), and a program that
        compiles anything for that key does so inside a timed call:
        ``compilations_in_window`` is what sees it. The check's window
        with a block short of 2/3 is a shorter batch, and the narrower
        kernel it ends in compiles there, after the window."""
        sound = [reference_light.OK] * self.window
        for tasks in self.windows[:WARM_UP_CALLS]:
            got = [self._answer(v) for v in self._verify(tasks)]
            if got != sound:
                raise RuntimeError("warm-up window: wrong verdicts %s" % got)

    def _timed(self, i: int) -> list:
        return self.windows[(WARM_UP_CALLS + i) % self.count]

    def call(self, i: int):
        return self._verify(self._timed(i))

    def check(self, outcomes, results) -> None:
        # every timed window is sound: the program must have accepted every block
        results.compare(
            "timed_blocks_refused",
            sum(1 for verdicts in outcomes for v in verdicts if not v.ok)
            + sum(self.window - len(verdicts) for verdicts in outcomes),
            0,
        )
        # three fresh windows, one fault in one block of each
        wrong = 0
        blocks, answers = [], []
        for j, fault in enumerate(FAULTS):
            tasks, want = self._faulted(j, fault)
            got = [self._answer(v) for v in self._verify(tasks)]
            if got != want:
                wrong += 1
            blocks += [self._plain(t) for t in tasks]
            answers += got
        results.compare("windows_with_a_wrong_block_verdict", wrong, 0)
        # the plain reference on those three windows, block by block, and
        # on a seeded sample of the lanes the timed windows sent
        bad = sum(1 for ref, got in zip(verify_blocks_in_parallel(blocks), answers) if ref != got)
        rng = workload.rng_for(self.seed, "sample", "catchup")
        for _ in range(results.sample_lanes if outcomes else 0):
            k = int(rng.integers(min(len(outcomes), self.count)))
            block = int(rng.integers(self.window))
            commit = self._timed(k)[block].commit
            idx = self._for_block(commit)[int(rng.integers(self.quorum))]
            valid = reference.verify(
                self.signers[idx].pub,
                commit.vote_sign_bytes(workload.CHAIN_ID, idx),
                commit.signatures[idx].signature,
            )
            if block >= len(outcomes[k]) or valid != outcomes[k][block].ok:
                bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return Catchup(ctx)

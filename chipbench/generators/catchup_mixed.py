"""Blocksync catch-up on a chain whose validators hold consensus keys of
three types: ``catchup.py``'s windows — commits of consecutive heights
over one stable set, a few validators absent and a few voting nil at
each height, each window one call of
``parallel/pipeline.verify_commits_pipelined`` as
``blocksync/syncer.BlockSyncer._apply_ready_blocks`` makes it — over
``commits_mixed.py``'s committee of ed25519, sr25519 and secp256k1
signers (its key draw over the seats, its signers, its sr25519 walk and
batch Merlin; its own cycle of commits is cut to the least and unused).

What the device is sent is the lanes of the two types that batch, and
``lanes_per_call`` counts those, as in ``commits_mixed``: the secp256k1
lanes are the host's by design, every call. ``run.py`` holds a traced
run's ``dispatch_chunk`` lanes to ``lanes_per_call`` x calls exactly,
and with three key types the device's share of a block's quorum would
move by a lane or two with where the early exit falls. So a height's
draw of absent and nil voters is repeated — the same stream's next draw
— until the lanes light verification includes hold one count of each
key type, (E, S, P) with E + S + P the quorum: the count that height
1's first 1,024 draws give most often among those that include a lane
of every type. (The first draw's own count was the rule until the chip
showed its tail: a rare first draw is met again once in thousands, and
one seed of eight spent 23 s of set-up drawing 6,552 times a height.
The most frequent count is met again within ten draws or so on every
seed.) Every type has to be included, or the cell's host call and the
check's fault of that type would have nothing to run on; a committee
none of whose five secp256k1 keys sits before the early exit (one seed
in ~250) is drawn again from the next seed of its own sequence. Only
ed25519 verdicts enter the program's verdict cache, so the cycle of
windows is sized from the ed25519 lanes.
"""

from __future__ import annotations

import math
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

from chipbench import reference_light_mixed, reference_mixed, workload
from chipbench.generators import catchup, commits_mixed, cycle_length

KEY_TYPES = commits_mixed.KEY_TYPES
POWER = 10
DRAWS = 64  # of a height's votes at a time
LEARN = 16  # such lots of height 1's draws, to learn the lanes by key type from
NEXT_COMMITTEE = 1 << 40  # between the seeds a run's committee is drawn from, in turn
# the check's one fresh window: a tampered included lane of each key
# type, a tampered lane past the early exit, a block left at 2/3
FAULTS = tuple("tampered_included_" + kt for kt in KEY_TYPES) + ("short_of_power", "tampered_skipped")


def verify_blocks_in_parallel(blocks: list) -> list:
    """``reference_light_mixed.verify_block`` over many blocks, in fresh
    interpreters that import the plain references and nothing else (no
    jax: the chip stays this process's). A window of 16 blocks at 500
    validators is ~5,300 big-integer verifications of 7-9 ms."""
    workers = min(catchup.REFERENCE_WORKERS, os.cpu_count() or 1, len(blocks))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as pool:
        return list(pool.map(reference_light_mixed.verify_block, *zip(*blocks)))


class _Committee:
    """What ``commits_mixed`` is handed to build the committee alone:
    the cell's files with the least cycle of commits (two), which this
    generator never verifies, and nothing said."""

    def __init__(self, ctx, attempt: int):
        self.cell, self.config = ctx.cell, ctx.config
        self.seed = ctx.seed + attempt * NEXT_COMMITTEE
        self.traffic = dict(ctx.traffic, cycle_over_verdict_cache=0.0, _allow_cache_answers=True)

    def say(self, text: str) -> None:
        pass


class CatchupMixed(catchup.Catchup):
    def __init__(self, ctx):
        from tendermint_tpu.ops import precompute
        from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
        from tendermint_tpu.types.validation import NotEnoughVotingPowerError

        self._verify = verify_commits_pipelined
        self._task = CommitTask
        self._short = NotEnoughVotingPowerError
        self.seed = ctx.seed
        n = int(ctx.config["validators"])
        self.window = int(ctx.config["verify_window"])
        self.n_absent = math.ceil(float(ctx.config["absent_share"]) * n)
        self.n_nil = math.ceil(float(ctx.config["nil_share"]) * n)
        # equal powers: a block sends the votes that pass 2/3 and no more
        self.quorum = n * 2 // 3 + 1
        if n - self.n_absent - self.n_nil < self.quorum:
            raise SystemExit("chipbench: %d validators less absent and nil votes cannot pass 2/3" % n)
        if self.window < len(FAULTS) - 1:
            raise SystemExit("chipbench: a window of %d cannot hold the check's faults" % self.window)
        # the first committee that seats a key of every type before the early exit
        self.committees = 0
        while True:
            self.committee = commits_mixed.build(_Committee(ctx, self.committees))
            self.committees += 1
            if all(seats[0] < self.quorum for seats in self.committee.lanes_of.values()):
                break
        self.vset, self.signers = self.committee.vset, self.committee.signers
        self.validators = [(s.key_type, s.pub, POWER) for s in self.signers]
        self._one_hot = np.eye(len(KEY_TYPES), dtype=np.int64)[
            [KEY_TYPES.index(s.key_type) for s in self.signers]
        ]
        self._draws = self._heights = 0
        # what every block's included lanes hold of each key type
        self._want = self._most_frequent_lanes_by_type()
        self.by_type = dict(zip(KEY_TYPES, (int(c) for c in self._want)))
        self.lanes_per_call = self.window * sum(self.by_type[kt] for kt in commits_mixed.BATCHED)
        self.count = cycle_length(
            ctx.traffic, self.window * self.by_type["ed25519"], precompute.results.cap
        )
        self.windows = [self._window(k) for k in range(self.count)]
        ctx.say(
            "traffic: %d validators (%s), %d absent and %d nil at each height; %d windows of %d "
            "commits cycled; %d lanes a block (%s), a height's votes drawn %.2f times on average "
            "until they are; %d lanes a call sent to the device, %d verified on the host (%d "
            "ed25519 signatures between two visits of one window; the verdict cache, which holds "
            "ed25519 verdicts only, holds %d); the committee is the seed's draw number %d"
            % (n, ", ".join("%d %s" % (len(self.committee.lanes_of[kt]), kt) for kt in KEY_TYPES),
               self.n_absent, self.n_nil, self.count, self.window, self.quorum,
               ", ".join("%d %s" % (self.by_type[kt], kt) for kt in KEY_TYPES),
               self._draws / self._heights, self.lanes_per_call,
               self.window * self.by_type["secp256k1"],
               (self.count - 1) * self.window * self.by_type["ed25519"], precompute.results.cap,
               self.committees)
        )

    # --- generation -------------------------------------------------------

    def _compositions(self, orders) -> np.ndarray:
        """For each row of ``orders`` — a height whose first
        ``n_absent`` of the row are absent and next ``n_nil`` vote nil —
        the lanes of each key type among those light verification
        includes: the first ``quorum`` votes for the block in the set's
        order."""
        out = np.zeros(orders.shape, dtype=bool)
        np.put_along_axis(out, orders[:, : self.n_absent + self.n_nil], True, axis=1)
        included = ~out & (np.cumsum(~out, axis=1) <= self.quorum)
        return included @ self._one_hot

    def _draws_of(self, height: int):
        """The height's stream of draws of who is absent and who votes
        nil, ``DRAWS`` at a time, each lot beside what its rows include
        of each key type."""
        rng = workload.rng_for(self.seed, "flags", height)
        seats = np.tile(np.arange(len(self.signers)), (DRAWS, 1))
        while True:
            orders = rng.permuted(seats, axis=1)
            yield orders, self._compositions(orders)

    def _most_frequent_lanes_by_type(self):
        """(E, S, P): what height 1's first ``LEARN`` lots of draws
        include most often, among those that include every key type."""
        lots = self._draws_of(1)
        held = np.concatenate([next(lots)[1] for _ in range(LEARN)])
        counts, times = np.unique(held[(held > 0).all(axis=1)], axis=0, return_counts=True)
        return counts[np.argmax(times)]

    def _order(self, height: int):
        """The height's draw: its stream's first whose included lanes
        hold ``by_type``."""
        self._heights += 1
        for orders, held in self._draws_of(height):
            hits = np.flatnonzero((held == self._want).all(axis=1))
            self._draws += int(hits[0]) + 1 if len(hits) else DRAWS
            if len(hits):
                return orders[hits[0]]

    def _sign_one(self, i: int, msg: bytes) -> bytes:
        """Validator i's signature over ``msg``, outside the commit's
        batch: a nil vote's, over its own sign-bytes."""
        signer = self.signers[i]
        if signer.key_type != "sr25519":
            return signer.sign(msg)
        r, r_enc = self.committee._walk.step()
        k = self.committee._challenges(
            np.frombuffer(signer.pub, dtype=np.uint8).reshape(1, 32),
            np.frombuffer(r_enc, dtype=np.uint8).reshape(1, 32), [msg],
        )[0]
        s = (int.from_bytes(k.tobytes(), "little") * signer.scalar + r) % workload.L
        return r_enc + (s | commits_mixed.MARKER).to_bytes(32, "little")

    def _commit(self, height: int, short_of_power: bool = False):
        """The commit of ``height``: the committee's, in which every
        validator signs the block (``commits_mixed``), with ``n_absent``
        of them turned absent and ``n_nil`` voting nil, each nil vote
        signed over its own canonical sign-bytes. ``short_of_power``
        turns votes for the block absent until one fewer than a quorum
        is left."""
        from tendermint_tpu.types import BLOCK_ID_FLAG_NIL, CommitSig

        n = len(self.signers)
        if short_of_power:
            order = workload.rng_for(self.seed, "flags", height).permutation(n)
            n_absent = n - self.n_nil - (self.quorum - 1)
        else:
            order, n_absent = self._order(height), self.n_absent
        commit = self.committee._sign(height)
        for i in order[:n_absent]:
            commit.signatures[int(i)] = CommitSig.absent()
        for i in (int(i) for i in order[n_absent:n_absent + self.n_nil]):
            cs = commit.signatures[i]
            commit.signatures[i] = CommitSig(BLOCK_ID_FLAG_NIL, cs.validator_address, cs.timestamp, b"")
            commit.signatures[i].signature = self._sign_one(i, commit.vote_sign_bytes(workload.CHAIN_ID, i))
        return commit

    def _included(self, commit, key_type=None) -> list:
        """Commit indices of the lanes light verification includes; of
        one key type alone."""
        return [
            i for i in self._for_block(commit)[: self.quorum]
            if key_type is None or self.signers[i].key_type == key_type
        ]

    def _faulted(self):
        """(tasks, expected answers): one fresh window past the cycle
        with five faulted blocks drawn from the seed and eleven sound
        ones."""
        rng = workload.rng_for(self.seed, "fault", "catchup_mixed")
        # a window shorter than the faults (a tiny twin's): the lane past
        # the early exit shares the first fault's block, whose verdict it
        # must not change either
        drawn = [int(b) for b in rng.permutation(self.window)]
        blocks = {fault: drawn[j % len(drawn)] for j, fault in enumerate(FAULTS)}
        tasks = self._window(self.count, short_block=blocks["short_of_power"])
        want = [reference_light_mixed.OK] * self.window
        want[blocks["short_of_power"]] = reference_light_mixed.INSUFFICIENT
        for kt in KEY_TYPES:
            # the fault only a canonicity rule refuses (s + L; n - s): the
            # check an engine is tempted to drop
            block = blocks["tampered_included_" + kt]
            idx = int(rng.choice(self._included(tasks[block].commit, kt)))
            self._tamper(tasks[block].commit, idx, commits_mixed.TAMPER_KINDS[kt][-1])
            want[block] = ("wrong signature", idx)
        commit = tasks[blocks["tampered_skipped"]].commit
        idx = int(rng.choice(self._for_block(commit)[self.quorum:]))
        kinds = commits_mixed.TAMPER_KINDS[self.signers[idx].key_type]
        self._tamper(commit, idx, kinds[int(rng.integers(len(kinds)))])
        return tasks, want

    def _tamper(self, commit, idx: int, kind: str) -> None:
        cs = commit.signatures[idx]
        cs.signature = commits_mixed.tamper(self.signers[idx].key_type, cs.signature, kind)

    # --- the check ----------------------------------------------------------

    def check(self, outcomes, results) -> None:
        # every timed window is sound: the program must have accepted every block
        results.compare(
            "timed_blocks_refused",
            sum(1 for verdicts in outcomes for v in verdicts if not v.ok)
            + sum(self.window - len(verdicts) for verdicts in outcomes),
            0,
        )
        # one fresh window, five faults in five blocks: every verdict as generation knows it
        tasks, want = self._faulted()
        got = [self._answer(v) for v in self._verify(tasks)]
        results.compare(
            "fault_window_blocks_with_a_wrong_verdict",
            sum(1 for g, w in zip(got, want) if g != w) + abs(len(got) - len(want)), 0,
        )
        # the plain reference on that window, block by block, and on a
        # seeded sample of the lanes the timed windows sent, each type in it
        plain = verify_blocks_in_parallel([self._plain(t) for t in tasks])
        bad = sum(1 for ref, g in zip(plain, got) if ref != g)
        rng = workload.rng_for(self.seed, "sample", "catchup_mixed")
        rest = results.sample_lanes - commits_mixed.SAMPLE_SECP
        share = {"secp256k1": commits_mixed.SAMPLE_SECP, "ed25519": rest - rest // 2, "sr25519": rest // 2}
        for kt in KEY_TYPES:
            for _ in range(share[kt] if outcomes else 0):
                k = int(rng.integers(min(len(outcomes), self.count)))
                block = int(rng.integers(self.window))
                commit = self._timed(k)[block].commit
                idx = int(rng.choice(self._included(commit, kt)))
                valid = reference_mixed.verify(
                    kt, self.signers[idx].pub,
                    commit.vote_sign_bytes(workload.CHAIN_ID, idx),
                    commit.signatures[idx].signature,
                )
                if block >= len(outcomes[k]) or valid != outcomes[k][block].ok:
                    bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return CatchupMixed(ctx)

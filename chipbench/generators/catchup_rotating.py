"""Blocksync catch-up over a chain whose validator set changes: every
``rotate_every`` heights one validator leaves and one joins, and each
call is the window ``blocksync/syncer.BlockSyncer._apply_ready_blocks``
would build there. The syncer peeks ``verify_window`` blocks and cuts
the window at the first change of ``validators_hash``, so after the
first cut every window is the commits of consecutive heights under one
``ValidatorSet`` object, and consecutive sets differ in one seat.

The sets are the program's own: ``update_with_change_set`` on a
``copy()`` of the previous set, as ``state/execution`` produces them.
What the check holds them to is ``reference_rotation``'s, recomputed
from the schedule in plain Python.

The seat ring. ``ring_candidates`` keys trade ``ring_seats`` seats, the
rest of the committee never changes: set s holds candidates s .. s +
seats - 1 of the ring, so a change retires the candidate seated
longest and seats the next one, and a candidate is away for
``candidates - seats`` sets before it returns: more than the live sets
``ops/precompute`` remembers, so it comes back unknown. The candidates'
addresses are drawn from the first ``ring_address_share`` of the address
space: light verification stops at 2/3, and a seat past that point is
never verified by a node that blocksyncs, whoever holds it.

Everything else is ``catchup``'s: absences and nil votes drawn at each
height, equal powers, pre-signed windows cycled past the verdict cache.
"""

from __future__ import annotations

import math

from chipbench import reference, reference_light, reference_rotation, workload
from chipbench.generators import catchup, cycle_length

ROTATION_FAULT = "old_key_in_new_seat"
FAULTS = (ROTATION_FAULT,) + catchup.FAULTS
POWER = 10


class RotatingCatchup(catchup.Catchup):
    def __init__(self, ctx):
        from tendermint_tpu.ops import precompute
        from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined
        from tendermint_tpu.types.validation import NotEnoughVotingPowerError

        self._verify = verify_commits_pipelined
        self._task = CommitTask
        self._short = NotEnoughVotingPowerError
        self._tables = precompute.tables
        self.seed = ctx.seed
        cfg = ctx.config
        n = int(cfg["validators"])
        self.verify_window = int(cfg["verify_window"])
        self.n_absent = math.ceil(float(cfg["absent_share"]) * n)
        self.n_nil = math.ceil(float(cfg["nil_share"]) * n)
        self.every = int(cfg["rotate_every"])
        self.first_change = int(cfg["first_change_height"])
        self.seats = int(cfg["ring_seats"])
        self.ring_size = int(cfg["ring_candidates"])
        if self.every > self.verify_window and self.every % self.verify_window:
            raise SystemExit("chipbench: rotate_every must divide into whole windows")
        if not 1 < self.first_change <= self.verify_window:
            raise SystemExit("chipbench: the first change must cut the first window")
        self.quorum = n * 2 // 3 + 1
        if n - self.n_absent - self.n_nil < self.quorum:
            raise SystemExit("chipbench: %d validators less absent and nil votes cannot pass 2/3" % n)
        # a set's span of heights is cut into this many windows, each of
        # ``window`` commits: the syncer's, or the span where that is shorter
        self.per_set = math.ceil(self.every / self.verify_window)
        self.window = min(self.every, self.verify_window)
        self.lanes_per_call = self.window * self.quorum

        # the cycle: whole turns of the ring, and as long as the verdict
        # cache asks
        asked = cycle_length(ctx.traffic, self.lanes_per_call, precompute.results.cap)
        turn = self.ring_size * self.per_set
        self.n_sets = self.ring_size * math.ceil(asked / turn)
        self.count = self.n_sets * self.per_set
        self.warm_windows = int(ctx.traffic["warm_up_sets"]) * self.per_set
        if self.warm_windows >= self.count:
            raise SystemExit("chipbench: warm-up longer than the cycle")
        # control.py's cache_answers cuts the cycle so that the cache
        # answers; the ring's closure would keep it long
        self.period = asked if ctx.traffic.get("_allow_cache_answers") else self.count

        self._make_sets(n, float(cfg["ring_address_share"]))
        self.opening = self._tasks(list(range(1, self.first_change)))
        self.windows = [self._window(a) for a in range(self.count)]
        ctx.say(
            "traffic: %d validators, %d of them in %d seats traded by a ring of %d; one "
            "replaced every %d heights from height %d; %d absent and %d nil at each height; "
            "%d sets = %d windows of %d commits cycled (the ring's closure asks %d windows, "
            "the verdict cache %d: it holds %d), %d lanes a block, %d a call; warm-up 1 + %d calls"
            % (n, self.seats, self.seats, self.ring_size, self.every, self.first_change,
               self.n_absent, self.n_nil, self.n_sets, self.count, self.window,
               turn, asked, precompute.results.cap, self.quorum, self.lanes_per_call,
               self.warm_windows)
        )

    # --- the sets ---------------------------------------------------------

    def _make_sets(self, n: int, share: float) -> None:
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.types import Validator

        core = workload.make_signers(self.seed, "validators", n - self.seats)
        ring, batch = [], 0
        while len(ring) < self.ring_size:
            drawn = workload.make_signers(self.seed, "ring-%d" % batch, 4 * self.ring_size)
            ring += [s for s in drawn if reference_rotation.address(s.pub)[0] < 256 * share]
            batch += 1
        self.ring = ring[: self.ring_size]
        self._by_pub = {s.pub: s for s in core + self.ring}
        _, vset = workload.make_validator_set(core + self.ring[: self.seats], POWER)
        self.sets = [vset]
        self.schedule = []
        for s in range(1, self.n_sets + 1):
            leaves, joins = self._leaver(s), self._newcomer(s)
            vset = vset.copy()
            vset.update_with_change_set([
                Validator(Ed25519PubKey(leaves.pub), 0),
                Validator(Ed25519PubKey(joins.pub), POWER),
            ])
            self.sets.append(vset)
        # the plain reference's chain runs on past the cycle, as far as
        # the check's fresh windows can reach
        for s in range(1, 2 * self.n_sets + len(FAULTS) + 4):
            self.schedule.append((
                self.first_change + (s - 1) * self.every,
                [(self._leaver(s).pub, 0), (self._newcomer(s).pub, POWER)],
            ))
        self.chain = reference_rotation.Chain(
            [(v.pub_key.bytes(), POWER) for v in self.sets[0].validators], self.schedule
        )
        self._signers_of = [
            [self._by_pub[v.pub_key.bytes()] for v in vs.validators] for vs in self.sets
        ]
        self._addresses_of = [[v.address for v in vs.validators] for vs in self.sets]

    def _leaver(self, s: int):
        """Who is in set s - 1 and not in set s."""
        return self.ring[(s - 1) % self.ring_size]

    def _newcomer(self, s: int):
        return self.ring[(s - 1 + self.seats) % self.ring_size]

    def _set_index(self, height: int) -> int:
        """Which set signs ``height``; past the cycle the ring goes on
        turning, so the sets of the cycle come round again."""
        if height < self.first_change:
            return 0
        s = (height - self.first_change) // self.every + 1
        return (s - 1) % self.n_sets + 1

    def _heights(self, a: int) -> list:
        """Heights of the a-th window after the first change, however
        far past the cycle."""
        s, piece = divmod(a, self.per_set)
        first = self.first_change + s * self.every + piece * self.window
        return list(range(first, first + self.window))

    # --- generation -------------------------------------------------------

    def _commit(self, height: int, short_of_power: bool = False):
        s = self._set_index(height)
        self.signers, self.addresses = self._signers_of[s], self._addresses_of[s]
        return super()._commit(height, short_of_power)

    def _tasks(self, heights: list, short_block=None) -> list:
        """The tasks of consecutive heights under one set, as the syncer
        builds them: one ``ValidatorSet`` object for all of them."""
        vset = self.sets[self._set_index(heights[0])]
        if self._set_index(heights[-1]) != self._set_index(heights[0]):
            raise RuntimeError("a window spans a change of the set")
        commits = [
            self._commit(h, short_of_power=(b == short_block)) for b, h in enumerate(heights)
        ]
        return [self._task(workload.CHAIN_ID, vset, c.block_id, c.height, c) for c in commits]

    def _plain(self, task):
        """A block as the plain reference takes it: the validators are
        the reference's own for that height."""
        commit = task.commit
        return self.chain.validators_at(task.height), [
            (cs.block_id_flag,
             commit.vote_sign_bytes(workload.CHAIN_ID, i) if cs.signature else b"",
             cs.signature)
            for i, cs in enumerate(commit.signatures)
        ]

    def _window(self, a: int, short_block=None) -> list:
        return self._tasks(self._heights(a), short_block)

    def _faulted(self, j: int, fault: str):
        """(tasks, expected answers): the j-th fresh window past the
        cycle with one fault in one block drawn from the seed;
        ``catchup``'s three, and the newcomer's seat signed by the key
        that just left, in a window that opens a new set and a block
        whose walk to 2/3 reaches that seat."""
        if fault != ROTATION_FAULT:
            return super()._faulted(j, fault)
        tasks = self._window(self.count + j)
        s = (tasks[0].height - self.first_change) // self.every + 1
        joins, leaves = self._newcomer(s), self._leaver(s)
        idx = self._signers_of[self._set_index(tasks[0].height)].index(joins)
        reached = [
            b for b, t in enumerate(tasks) if idx in self._for_block(t.commit)[: self.quorum]
        ]
        if not reached:
            raise SystemExit("chipbench: no block's walk reaches the newcomer's seat; take another seed")
        rng = workload.rng_for(self.seed, "fault", fault)
        block = reached[int(rng.integers(len(reached)))]
        commit = tasks[block].commit
        commit.signatures[idx].signature = leaves.sign(commit.vote_sign_bytes(workload.CHAIN_ID, idx))
        want = [reference_light.OK] * self.window
        want[block] = ("wrong signature", idx)
        return tasks, want

    # --- the calls ----------------------------------------------------------

    def _sound(self, tasks) -> None:
        got = [self._answer(v) for v in self._verify(tasks)]
        if got != [reference_light.OK] * len(tasks):
            raise RuntimeError("warm-up window: wrong verdicts %s" % got)

    def warm(self) -> None:
        """The node's first steps: the heights before the first change,
        then the first ``warm_up_sets`` sets of the cycle, one change
        each (the traffic file says why that many)."""
        for tasks in [self.opening] + self.windows[: self.warm_windows]:
            self._sound(tasks)
        self._hashed_before = self._tables.stats()["active_set_hashed"]

    def _position(self, i: int) -> int:
        return (self.warm_windows + i % self.period) % self.count

    def _timed(self, i: int) -> list:
        return self.windows[self._position(i)]

    def check(self, outcomes, results) -> None:
        registered = self._tables.stats()["active_set_hashed"] - self._hashed_before
        results.compare(
            "timed_blocks_refused",
            sum(1 for verdicts in outcomes for v in verdicts if not v.ok)
            + sum(self.window - len(verdicts) for verdicts in outcomes),
            0,
        )
        # a call whose set is not the previous call's brings one unseen
        # set, which the program has to have registered
        sets_met = [(self._position(i)) // self.per_set for i in range(-1, len(outcomes))]
        changes = sum(1 for before, now in zip(sets_met, sets_met[1:]) if before != now)
        results.compare("sets_registered_in_window", abs(registered - changes), 0)
        # fresh windows past the cycle that continue the chain where the
        # last timed call left it, one fault in one block of each; the
        # first is the next to open a new set
        j = self._position(len(outcomes) - 1) + 1
        j += -(self.count + j) % self.per_set
        wrong = 0
        plain, answers = [], []
        for fault in FAULTS:
            tasks, want = self._faulted(j, fault)
            j += 1
            got = [self._answer(v) for v in self._verify(tasks)]
            if got != want:
                wrong += 1
            plain += [self._plain(t) for t in tasks]
            answers += got
        results.compare("windows_with_a_wrong_block_verdict", wrong, 0)
        # the plain reference, over its own sets, on those windows block
        # by block and on a seeded sample of the lanes the timed windows sent
        bad = sum(
            1 for ref, got in zip(catchup.verify_blocks_in_parallel(plain), answers) if ref != got
        )
        rng = workload.rng_for(self.seed, "sample", "catchup")
        for _ in range(results.sample_lanes if outcomes else 0):
            k = int(rng.integers(min(len(outcomes), self.period)))
            block = int(rng.integers(self.window))
            task = self._timed(k)[block]
            idx = self._for_block(task.commit)[int(rng.integers(self.quorum))]
            valid = reference.verify(
                self.chain.validators_at(task.height)[idx][0],
                task.commit.vote_sign_bytes(workload.CHAIN_ID, idx),
                task.commit.signatures[idx].signature,
            )
            if block >= len(outcomes[k]) or valid != outcomes[k][block].ok:
                bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return RotatingCatchup(ctx)

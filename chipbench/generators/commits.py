"""Commits of consecutive heights over one validator set, verified one
after another by one caller through ``types.validation.verify_commit``:
what a node does with every block of a chain whose set is stable."""

from __future__ import annotations

import re

from chipbench import reference, workload

WARM_CALLS = 2  # the first builds and uploads the set's tables; the second runs as every later one


class Commits:
    def __init__(self, ctx):
        from tendermint_tpu.ops import precompute
        from tendermint_tpu.types.validation import InvalidCommitError, verify_commit

        from chipbench.generators import cycle_length

        self._verify = verify_commit
        self._refused = InvalidCommitError
        self.seed = ctx.seed
        n = int(ctx.config["validators"])
        self.lanes_per_call = n
        self.heights = cycle_length(ctx.traffic, n, precompute.results.cap)
        signers = workload.make_signers(ctx.seed, "validators", n)
        self.signers, self.vset = workload.make_validator_set(signers)
        self.addresses = [v.address for v in self.vset.validators]
        self.pubs = [s.pub for s in self.signers]
        self.commits = [self._sign(h) for h in range(1, self.heights + 1)]
        ctx.say(
            "traffic: %d validators, %d commits of consecutive heights cycled "
            "(%d signatures between two visits of one commit; verdict cache holds %d)"
            % (n, self.heights, (self.heights - 1) * n, precompute.results.cap)
        )

    def _sign(self, height: int):
        return workload.make_commit(
            self.seed, "chain", height, self.addresses, self.signers
        )

    def _verify_commit(self, commit):
        try:
            self._verify(
                workload.CHAIN_ID, self.vset, commit.block_id, commit.height, commit
            )
        except self._refused as exc:
            return exc
        return None

    def warm(self) -> None:
        for commit in self.commits[:WARM_CALLS]:
            refused = self._verify_commit(commit)
            if refused is not None:
                raise RuntimeError("warm-up commit refused: %s" % refused)

    def call(self, i: int):
        return self._verify_commit(self.commits[(WARM_CALLS + i) % self.heights])

    def check(self, outcomes, results) -> None:
        n = self.lanes_per_call
        # every timed commit is valid: the program must have accepted it
        results.compare(
            "timed_commits_refused",
            sum(1 for o in outcomes if o is not None),
            0,
        )
        # three fresh heights, one tampered lane each: refused, and the
        # blame on the tampered lane
        picks = workload.tamper_lanes(self.seed, "commits", n)
        wrong = 0
        tampered = []
        for j, (lane, kind) in enumerate(sorted(picks.items())):
            commit = self._sign(self.heights + 1 + j)
            cs = commit.signatures[lane]
            cs.signature = workload.tamper_signature(cs.signature, kind)
            refused = self._verify_commit(commit)
            m = re.search(r"#(\d+)", str(refused)) if refused is not None else None
            if m is None or int(m.group(1)) != lane:
                wrong += 1
            _, msgs, sigs = workload.commit_lanes(commit, self.pubs)
            tampered.append((self.pubs[lane], msgs[lane], sigs[lane]))
        results.compare("tampered_commits_not_blamed_on_their_lane", wrong, 0)
        # the plain reference on the tampered lanes and on a seeded
        # sample of the lanes the window accepted
        bad = sum(1 for lane in tampered if reference.verify(*lane))
        rng = workload.rng_for(self.seed, "sample", "commits")
        for _ in range(results.sample_lanes if outcomes else 0):
            k = int(rng.integers(min(len(outcomes), self.heights)))
            commit = self.commits[(WARM_CALLS + k) % self.heights]
            lane = int(rng.integers(n))
            valid = reference.verify(
                self.pubs[lane],
                commit.vote_sign_bytes(workload.CHAIN_ID, lane),
                commit.signatures[lane].signature,
            )
            if valid != (outcomes[k] is None):
                bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return Commits(ctx)

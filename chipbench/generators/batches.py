"""Ad-hoc batches: (key, sign-bytes, signature) triples from keys that
belong to no validator set the program knows, handed to a fresh
``crypto.batch`` verifier in every call. What upstream's
``crypto/ed25519/bench_test.go`` batch benchmark does (a fresh key per
lane), and what a node does with signatures that arrive outside a
commit. Building the verifier and adding the lanes is inside the call."""

from __future__ import annotations

from chipbench import reference, workload

WARM_CALLS = 1


class Batches:
    def __init__(self, ctx):
        from tendermint_tpu.crypto import batch as crypto_batch
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.ops import precompute

        from chipbench.generators import cycle_length

        self._create = crypto_batch.create_batch_verifier
        self.seed = ctx.seed
        n = int(ctx.config["validators"])
        self.lanes_per_call = n
        self.count = cycle_length(ctx.traffic, n, precompute.results.cap)
        self.batches = []
        for b in range(self.count):
            signers = workload.make_signers(ctx.seed, "batch-%d" % b, n)
            addresses = [s.pub[:20] for s in signers]
            commit = workload.make_commit(ctx.seed, "batch-%d" % b, 1 + b, addresses, signers)
            pubs, msgs, sigs = workload.commit_lanes(commit, [s.pub for s in signers])
            picks = workload.tamper_lanes(ctx.seed, "batch-%d" % b, n)
            for lane, kind in picks.items():
                sigs[lane] = workload.tamper_signature(sigs[lane], kind)
            expected = [i not in picks for i in range(n)]
            keys = [Ed25519PubKey(p) for p in pubs]
            self.batches.append((keys, msgs, sigs, expected, sorted(picks)))
        ctx.say(
            "traffic: %d batches of %d lanes, a fresh key per lane, three "
            "tampered lanes in each (%d signatures between two visits of one "
            "batch; verdict cache holds %d)"
            % (self.count, n, (self.count - 1) * n, precompute.results.cap)
        )

    def _verify(self, batch):
        keys, msgs, sigs = batch[0], batch[1], batch[2]
        bv = self._create(keys[0])
        for key, msg, sig in zip(keys, msgs, sigs):
            bv.add(key, msg, sig)
        return bv.verify()[1]

    def warm(self) -> None:
        for batch in self.batches[:WARM_CALLS]:
            if list(self._verify(batch)) != batch[3]:
                raise RuntimeError("warm-up batch: wrong verdicts")

    def call(self, i: int):
        return self._verify(self.batches[(WARM_CALLS + i) % self.count])

    def check(self, outcomes, results) -> None:
        n = self.lanes_per_call
        wrong = 0
        for k, verdicts in enumerate(outcomes):
            expected = self.batches[(WARM_CALLS + k) % self.count][3]
            if len(verdicts) != n:
                wrong += n
            else:
                wrong += sum(1 for v, e in zip(verdicts, expected) if bool(v) != e)
        results.compare("lanes_with_wrong_verdict", wrong, 0)
        # the plain reference on every tampered lane of the batches the
        # window visited and on a seeded sample of their other lanes
        bad = 0
        rng = workload.rng_for(self.seed, "sample", "batches")
        visited = min(len(outcomes), self.count)
        rows = [
            (k, lane)
            for k in range(visited)
            for lane in self.batches[(WARM_CALLS + k) % self.count][4]
        ]
        rows += [
            (int(rng.integers(visited)), int(rng.integers(n)))
            for _ in range(results.sample_lanes if visited else 0)
        ]
        for k, lane in rows:
            keys, msgs, sigs = self.batches[(WARM_CALLS + k) % self.count][:3]
            valid = reference.verify(keys[lane].bytes(), msgs[lane], sigs[lane])
            if len(outcomes[k]) != n or valid != bool(outcomes[k][lane]):
                bad += 1
        results.compare("lanes_where_reference_disagrees", bad, 0)


def build(ctx):
    return Batches(ctx)

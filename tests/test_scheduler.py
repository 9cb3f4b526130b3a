"""Accumulate-with-deadline verify scheduler tests (SURVEY §7 latency
duality seam)."""

import threading
import time

import pytest

from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.crypto.ed25519_ref import verify_zip215
from tendermint_tpu.crypto.scheduler import VerifyScheduler


def host_verify(pks, msgs, sigs):
    return [verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]


@pytest.fixture()
def sched():
    s = VerifyScheduler(host_verify, max_batch=32, max_delay=0.05)
    s.start()
    yield s
    s.stop()


def _signed(i: int):
    priv = Ed25519PrivKey.from_seed(bytes([i]) * 32)
    msg = b"sched-msg-%d" % i
    return priv.pub_key().bytes(), msg, priv.sign(msg)


class TestDeadline:
    def test_lone_entry_answers_within_deadline(self, sched):
        pk, msg, sig = _signed(1)
        t0 = time.monotonic()
        assert sched.verify(pk, msg, sig)
        elapsed = time.monotonic() - t0
        # one flush, no batch partners: the deadline bounds the wait
        assert elapsed < 1.0
        assert sched.flushes == 1

    def test_bad_signature_fails_only_itself(self, sched):
        good = [_signed(i) for i in range(4)]
        results = {}

        def submit(idx, pk, msg, sig):
            results[idx] = sched.verify(pk, msg, sig)

        threads = []
        for i, (pk, msg, sig) in enumerate(good):
            bad_sig = bytes(64) if i == 2 else sig
            t = threading.Thread(target=submit, args=(i, pk, msg, bad_sig))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=10)
        assert results == {0: True, 1: True, 2: False, 3: True}


class TestBatching:
    def test_concurrent_callers_share_flushes(self):
        calls = []

        def counting_verify(pks, msgs, sigs):
            calls.append(len(pks))
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(counting_verify, max_batch=64, max_delay=0.2)
        s.start()
        try:
            entries = [_signed(i % 8) for i in range(40)]
            results = [None] * 40

            def submit(i):
                pk, msg, sig = entries[i]
                results[i] = s.verify(pk, msg, sig)

            threads = [
                threading.Thread(target=submit, args=(i,)) for i in range(40)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
            assert all(results)
            # 40 concurrent verifies amortized into far fewer flushes;
            # only the 8 unique (pk, msg, sig) triples cost verifier
            # lanes — duplicates within a flush coalesce.
            assert len(calls) < 10, calls
            assert 8 <= sum(calls) <= 40
            assert s.entries_verified == 40
            assert sum(calls) + s.entries_coalesced == 40
        finally:
            s.stop()

    def test_duplicate_submissions_coalesce_to_one_lane(self):
        calls = []

        def counting_verify(pks, msgs, sigs):
            calls.append(len(pks))
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(counting_verify, max_batch=64, max_delay=60.0)
        s.start()
        try:
            good = _signed(1)
            bad = (good[0], good[1], bytes(64))
            handles = [s.submit(*good) for _ in range(5)]
            handles += [s.submit(*bad) for _ in range(3)]
            # force the flush now rather than waiting out the deadline
            with s._wake:
                s.max_delay = 0.0
                s._wake.notify_all()
            oks = [s.wait(h) for h in handles]
            assert oks == [True] * 5 + [False] * 3
            # 8 submissions, 2 unique triples, 1 flush
            assert calls == [2], calls
            assert s.entries_coalesced == 6
            assert s.entries_verified == 8
        finally:
            s.stop()

    def test_max_batch_flushes_without_deadline(self):
        s = VerifyScheduler(host_verify, max_batch=4, max_delay=60.0)
        s.start()
        try:
            entries = [_signed(i) for i in range(4)]
            results = [None] * 4
            threads = [
                threading.Thread(
                    target=lambda i=i: results.__setitem__(
                        i, s.verify(*entries[i])
                    )
                )
                for i in range(4)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            # the batch-size trigger fired: nowhere near the 60s deadline
            assert time.monotonic() - t0 < 10
            assert all(results)
        finally:
            s.stop()


class TestContinuousBatching:
    def test_admits_lanes_while_dispatch_in_flight(self):
        """The tentpole property: lanes submitted while a kernel runs
        join the NEXT dispatch instead of waiting out a flush barrier."""
        release = threading.Event()
        entered = threading.Event()
        calls = []

        def gated_verify(pks, msgs, sigs):
            calls.append(len(pks))
            if len(calls) == 1:
                entered.set()
                release.wait(timeout=10)
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(
            gated_verify, max_batch=4, max_delay=0.01,
            continuous=True, pipeline_depth=2,
        )
        s.start()
        try:
            first = [s.submit(*_signed(i)) for i in range(4)]  # size flush
            assert entered.wait(timeout=5)  # dispatch 1 is on the device
            # submit while in flight: these must be admitted, counted,
            # and dispatched without waiting for dispatch 1 to return
            second = [s.submit(*_signed(4 + i)) for i in range(4)]
            deadline = time.monotonic() + 5
            # poll through the locked stats() snapshot: the dispatcher is
            # still writing these counters, so a raw attribute read here
            # is a data race (tpusan hb mode flags it)
            while (
                s.stats()["dispatch_handoffs"] < 2
                and time.monotonic() < deadline
            ):
                time.sleep(0.005)
            stats = s.stats()
            assert stats["dispatch_handoffs"] >= 2
            assert stats["inflight_admissions"] >= 1
            # the second batch resolves while the first is STILL blocked
            assert s.wait_many(second, timeout=5) == [True] * 4
            assert not first[0].done.is_set()
            release.set()
            assert s.wait_many(first, timeout=5) == [True] * 4
        finally:
            release.set()
            s.stop()

    def test_pipeline_depth_bounds_outstanding_dispatches(self):
        release = threading.Event()

        def gated_verify(pks, msgs, sigs):
            release.wait(timeout=10)
            return host_verify(pks, msgs, sigs)

        # size-only flushes (huge deadline): every batch is exactly
        # max_batch lanes, so the depth arithmetic below is exact
        s = VerifyScheduler(
            gated_verify, max_batch=2, max_delay=60.0,
            continuous=True, pipeline_depth=2,
        )
        s.start()
        try:
            handles = [s.submit(*_signed(i)) for i in range(8)]
            deadline = time.monotonic() + 5
            while s.dispatch_depth() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            # both slots taken; the rest back-pressures into the
            # accumulator rather than growing the hand-off queue
            time.sleep(0.05)
            assert s.dispatch_depth() == 2
            assert s.pending_depth() == 4
            assert s.load_depth() == 8
            release.set()
            assert s.wait_many(handles, timeout=10) == [True] * 8
            assert s.load_depth() == 0
        finally:
            release.set()
            s.stop()

    def test_on_dispatch_reports_occupancy(self):
        seen = []
        release = threading.Event()

        def gated_verify(pks, msgs, sigs):
            release.wait(timeout=10)
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(
            gated_verify, max_batch=2, max_delay=0.005,
            continuous=True, pipeline_depth=2,
            on_dispatch=lambda depth, lanes, reason: seen.append(
                (depth, lanes, reason)
            ),
        )
        s.start()
        try:
            handles = [s.submit(*_signed(i)) for i in range(4)]
            deadline = time.monotonic() + 5
            while len(seen) < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            release.set()
            s.wait_many(handles, timeout=10)
            assert len(seen) >= 2
            assert sum(lanes for _, lanes, _ in seen) == 4
            # with both batches held on the device, a later hand-off
            # observed occupancy 2 — the pipeline genuinely overlapped
            assert max(d for d, _, _ in seen) == 2
        finally:
            release.set()
            s.stop()

    def test_barrier_mode_spawns_no_workers(self):
        s = VerifyScheduler(host_verify, max_batch=8, continuous=False)
        s.start()
        try:
            assert s._workers == []
            assert s.verify(*_signed(1))
            assert s.dispatch_handoffs == 0  # flushed inline
        finally:
            s.stop()

    def test_submit_many_is_atomic_against_max_pending(self):
        release = threading.Event()

        def gated_verify(pks, msgs, sigs):
            release.wait(timeout=10)
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(
            gated_verify, max_batch=4, max_delay=0.005,
            max_pending=6, continuous=True, pipeline_depth=1,
        )
        s.start()
        try:
            first = s.submit_many([_signed(i) for i in range(4)])
            deadline = time.monotonic() + 5
            while s.pending_depth() and time.monotonic() < deadline:
                time.sleep(0.005)
            filler = s.submit_many([_signed(10 + i) for i in range(4)])
            # 4 pending of 6: a group of 3 must be rejected WHOLE —
            # never 2 admitted + 1 shed
            from tendermint_tpu.crypto.scheduler import (
                SchedulerSaturatedError,
            )
            with pytest.raises(SchedulerSaturatedError):
                s.submit_many([_signed(20 + i) for i in range(3)])
            assert s.pending_depth() == 4
            release.set()
            assert all(s.wait_many(first + filler, timeout=10))
            assert s.entries_verified == 8  # nothing from the shed group
        finally:
            release.set()
            s.stop()

    @staticmethod
    def _gated(max_batch, continuous=True):
        """A scheduler with one dispatch slot, held by a first lane
        until ``release`` is set; ``flushes`` records every verify_fn
        call as the messages it carried."""
        flushes = []
        release = threading.Event()

        def recording(pks, msgs, sigs):
            release.wait(timeout=10)
            flushes.append(list(msgs))
            return host_verify(pks, msgs, sigs)

        s = VerifyScheduler(
            recording, max_batch=max_batch, max_delay=0.002,
            continuous=continuous, pipeline_depth=1,
        )
        s.start()
        gate = s.submit(*_signed(0))  # holds the one dispatch slot
        deadline = time.monotonic() + 5
        while s.pending_depth() and time.monotonic() < deadline:
            time.sleep(0.002)
        return s, gate, flushes, release

    @pytest.mark.parametrize("continuous", [True, False])
    def test_group_submitted_whole_leaves_in_one_flush(self, continuous):
        """PR 30: ``submit_many(whole=True)`` longer than ``max_batch``
        is one verify_fn call, not ``max_batch``-lane pieces, beside
        whatever was already pending before it; single submits are
        still cut at the limit."""
        s, gate, flushes, release = self._gated(4, continuous)
        try:
            singles = [s.submit(*_signed(1 + i)) for i in range(2)]
            group = s.submit_many([_signed(3 + i) for i in range(11)], whole=True)
            tail = [s.submit(*_signed(14 + i)) for i in range(5)]
            release.set()
            assert all(s.wait_many([gate] + singles + group + tail, timeout=10))
            # the two singles fit under the limit of 4 with the group's
            # first two lanes; the cut falls inside the group, which
            # leaves whole; the five after it are cut at 4 again
            assert [len(f) for f in flushes] == [1, 13, 4, 1]
            assert len({id(e.group) for e in group}) == 1 and group[0].group is not None
        finally:
            release.set()
            s.stop()

    @pytest.mark.parametrize("whole,want", [(False, [1, 4, 4, 3]), (True, [1, 11])])
    def test_urgent_lane_behind_a_long_low_priority_group(self, whole, want):
        """A long low-priority group ahead of a high-priority submit
        (verifyd: a bulk rpc request, then a consensus vote). Cut at
        ``max_batch``, as verifyd submits it, the vote leaves in the
        next flush with three of the bulk lanes and waits for no other;
        where the caller submitted the group whole, that flush is the
        whole group: the vote's wait is one call of its length."""
        s, gate, flushes, release = self._gated(4)
        try:
            bulk = [_signed(1 + i) for i in range(10)]
            group = s.submit_many(bulk, priority=3, whole=whole)
            vote = s.submit(*_signed(20), priority=0)
            release.set()
            assert all(s.wait_many([gate] + group + [vote], timeout=10))
            assert [len(f) for f in flushes] == want
            assert flushes[1][0] == _signed(20)[1]  # the urgent lane first
            assert all(e.group is None for e in group) != whole
        finally:
            release.set()
            s.stop()

    def test_submit_many_groups_race_continuous_dispatcher(self):
        """Many atomic groups racing the dispatch workers: every group
        resolves all-or-nothing and no lane is lost or double-counted."""
        s = VerifyScheduler(
            host_verify, max_batch=8, max_delay=0.002,
            max_pending=64, continuous=True, pipeline_depth=2,
        )
        s.start()
        try:
            outcomes = {}

            def one_group(g):
                lanes = [_signed((g * 5 + i) % 16) for i in range(5)]
                try:
                    handles = s.submit_many(lanes)
                except Exception:
                    outcomes[g] = "shed"
                    return
                oks = s.wait_many(handles, timeout=10)
                outcomes[g] = "ok" if all(oks) else "partial"

            threads = [
                threading.Thread(target=one_group, args=(g,))
                for g in range(12)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
            assert len(outcomes) == 12
            assert "partial" not in outcomes.values()
            admitted = sum(1 for v in outcomes.values() if v == "ok")
            assert admitted >= 1
            assert s.entries_verified == admitted * 5
        finally:
            s.stop()


class TestFailureModes:
    def test_verifier_exception_fails_closed(self):
        def broken(pks, msgs, sigs):
            raise RuntimeError("device on fire")

        s = VerifyScheduler(broken, max_batch=8, max_delay=0.01)
        s.start()
        try:
            pk, msg, sig = _signed(1)
            assert s.verify(pk, msg, sig) is False
        finally:
            s.stop()

    def test_stop_fails_pending_closed(self):
        started = threading.Event()

        def slow(pks, msgs, sigs):
            started.set()
            time.sleep(0.5)
            return [True] * len(pks)

        s = VerifyScheduler(slow, max_batch=1, max_delay=0.01)
        s.start()
        pk, msg, sig = _signed(1)
        out = {}
        t = threading.Thread(target=lambda: out.setdefault("r", s.verify(pk, msg, sig)))
        t.start()
        started.wait(timeout=5)
        s.stop()
        t.join(timeout=5)
        assert out["r"] in (True, False)  # resolved, never hung

    def test_submit_after_stop_raises(self):
        s = VerifyScheduler(host_verify)
        s.start()
        s.stop()
        with pytest.raises(RuntimeError):
            s.verify(b"\x00" * 32, b"m", b"\x00" * 64)

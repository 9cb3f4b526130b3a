"""Chaos scenario: the device path is killed and revived MID-RUN while
consensus-shaped verification load keeps flowing.

The acceptance bar (ISSUE: fault-tolerant accelerator verification):
zero failed verifications across the outage — every commit that should
verify verifies, every forged signature is still rejected — and the
health machine recovers to HEALTHY on its own once the device returns.

The load is the real consensus path: ``verify_commit`` over a
24-validator set rides ``_verify_commit_batch`` -> Ed25519BatchVerifier
-> ops.verify_batch (24 >= DEVICE_THRESHOLD), i.e. the same code a node
runs when validating a block's LastCommit. The scheduler flood variant
covers the concurrent-submitter path (vote storms).

These tests use real (short) cooldown clocks, not fakes: the point is
the end-to-end loop including the half-open probe re-admission.
"""

import threading
import time

import pytest

from tendermint_tpu.crypto.ed25519_ref import generate_keypair, sign
from tendermint_tpu.ops import device_policy, fault_injection
from tendermint_tpu.ops.device_policy import (
    COOLDOWN,
    HEALTHY,
    DeviceHealth,
)
from tendermint_tpu.types.validation import verify_commit
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_validators, warm_verify

pytestmark = [
    pytest.mark.chaos,
    # chunk-fallback warnings are the expected noise of the outage
    pytest.mark.filterwarnings("ignore::UserWarning"),
]


@pytest.fixture(autouse=True)
def _pristine():
    fault_injection.uninstall()
    device_policy.shared.reset()
    yield
    fault_injection.uninstall()
    device_policy.shared.reset()


def test_device_killed_and_revived_mid_consensus(monkeypatch):
    h = DeviceHealth(retry_budget=1, cooldown_base=0.05, cooldown_max=0.1)
    monkeypatch.setattr(device_policy, "shared", h)
    privs, vset = make_validators(24)
    block_id = make_block_id()

    plan = fault_injection.install(fault_injection.FaultPlan(site="ed25519"))

    def run_height(height):
        commit = make_commit(block_id, height, 0, vset, privs)
        # must NOT raise — ever — regardless of device state
        verify_commit(CHAIN_ID, vset, block_id, height, commit)

    # healthy rounds: device path serves
    for ht in (1, 2):
        run_height(ht)
    assert h.state == HEALTHY

    # kill the device mid-consensus: every chunk dispatch now faults
    plan.kill()
    for ht in (3, 4, 5):
        run_height(ht)
    assert h.state == COOLDOWN
    assert plan.faults_raised >= 1

    # revive; after the cooldown expires the next commit is the probe
    plan.revive()
    deadline = time.monotonic() + 5.0
    ht = 6
    while h.state != HEALTHY and time.monotonic() < deadline:
        time.sleep(0.06)
        run_height(ht)
        ht += 1
    assert h.state == HEALTHY, f"no recovery: {h.snapshot()}"
    assert (COOLDOWN, HEALTHY) in h.transitions

    # forged commits are still rejected after the whole episode
    bad = make_commit(block_id, ht, 0, vset, privs)
    bad.signatures[0].signature = b"\x13" * 64
    with pytest.raises(Exception):
        verify_commit(CHAIN_ID, vset, block_id, ht, bad)


def test_scheduler_flood_survives_device_outage(monkeypatch):
    """Concurrent submitters flood a scheduler whose flush rides the
    device engine; the device dies mid-flood and comes back. Every
    verdict must be correct — zero false negatives, zero false
    positives — and no caller may hang."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215
    from tendermint_tpu.crypto.scheduler import VerifyScheduler
    from tendermint_tpu.ops.ed25519_batch import verify_batch

    # the flood's kernel is compiled before the first caller starts its clock
    warm_verify()
    h = DeviceHealth(retry_budget=1, cooldown_base=0.05, cooldown_max=0.1)
    monkeypatch.setattr(device_policy, "shared", h)

    def host(pks, msgs, sigs):
        return [verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]

    sched = VerifyScheduler(verify_batch, max_delay=0.005, fallback_fn=host)
    sched.start()
    plan = fault_injection.install(fault_injection.FaultPlan(site="ed25519"))

    n = 96
    entries = []
    for i in range(n):
        sk, pk = generate_keypair()
        m = b"flood-%d" % i
        s = sign(sk, m) if i % 7 else b"\x07" * 64  # every 7th is forged
        entries.append((pk, m, s, bool(i % 7)))

    results = [None] * n
    timed_out = []

    def submitter(idx):
        # ``sched.verify`` answers False for a caller that timed out (fail
        # closed): here that is a caller that hung, not a verdict
        pk, m, s, _ = entries[idx]
        entry = sched.submit(pk, m, s)
        if entry.done.wait(timeout=30.0):
            results[idx] = entry.ok
        else:
            timed_out.append(idx)

    threads = []
    try:
        # first third with a healthy device
        for i in range(0, n // 3):
            t = threading.Thread(target=submitter, args=(i,))
            t.start()
            threads.append(t)
        time.sleep(0.05)
        plan.kill()  # outage strikes mid-flood
        for i in range(n // 3, 2 * n // 3):
            t = threading.Thread(target=submitter, args=(i,))
            t.start()
            threads.append(t)
        time.sleep(0.15)
        plan.revive()
        time.sleep(0.1)  # let the cooldown lapse so the probe can win
        for i in range(2 * n // 3, n):
            t = threading.Thread(target=submitter, args=(i,))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=60.0)
            assert not t.is_alive(), "a caller hung through the outage"
    finally:
        fault_injection.uninstall()
        sched.stop()

    assert not timed_out, f"callers {sorted(timed_out)} timed out after 30 s (state={h.state})"
    for i, (_, _, _, genuine) in enumerate(entries):
        assert results[i] == genuine, (
            f"entry {i}: expected {genuine}, got {results[i]} "
            f"(state={h.state}, snapshot={h.snapshot()})"
        )

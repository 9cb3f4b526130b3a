"""Device health state machine (ops/device_policy.py) and the
fault-injection harness (ops/fault_injection.py) that proves it.

The battery covers the ISSUE's acceptance criteria directly:

- injected transient failure -> ZERO failed verifications (CPU fallback
  absorbs the chunk) and the machine walks HEALTHY -> COOLDOWN ->
  HEALTHY (recovery via the half-open probe);
- injected permanent failure -> all verifications still complete on the
  CPU path, the machine lands in DISABLED, and metrics expose it.
"""

import threading

import pytest

from tendermint_tpu.crypto.ed25519_ref import generate_keypair, sign
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import device_policy, fault_injection
from tendermint_tpu.ops.device_policy import (
    COOLDOWN,
    DEGRADED,
    DISABLED,
    HEALTHY,
    PERMANENT,
    TRANSIENT,
    DeviceHealth,
    DeviceStallError,
    classify_failure,
)
from tendermint_tpu.ops.ed25519_batch import verify_batch


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def _pristine():
    fault_injection.uninstall()
    device_policy.shared.reset()
    yield
    fault_injection.uninstall()
    device_policy.shared.reset()


def make_batch(n=20, bad=()):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sk, pk = generate_keypair()
        m = b"vote-%d" % i
        s = sign(sk, m)
        if i in bad:
            s = b"\x01" * 64
        pks.append(pk)
        msgs.append(m)
        sigs.append(s)
    return pks, msgs, sigs


# --- classification ---------------------------------------------------------


def test_classification_by_signature_not_substring():
    assert (
        classify_failure(RuntimeError("unable to initialize backend 'tpu'"))
        == PERMANENT
    )
    assert classify_failure(ImportError("no module named jax")) == PERMANENT
    # a transient hiccup merely MENTIONING a platform must stay transient
    assert (
        classify_failure(RuntimeError("transfer to platform device timed out"))
        == TRANSIENT
    )
    assert classify_failure(ValueError("shape mismatch")) == TRANSIENT
    assert classify_failure(DeviceStallError("wedged")) == TRANSIENT


def test_explicit_permanent_attribute_wins():
    assert (
        classify_failure(fault_injection.DeviceFault("x", permanent=True))
        == PERMANENT
    )
    # explicit False even with a permanent-looking message
    err = RuntimeError("unable to initialize backend")
    err.permanent = False
    assert classify_failure(err) == TRANSIENT


@pytest.mark.parametrize(
    "msg",
    [
        # ROADMAP known debt: transient XLA/runtime hiccups that merely
        # MENTION "backend"/"platform" must never be classified as a
        # permanent init failure (the old substring matching was too
        # broad — one transient blip disabled the device path for the
        # process lifetime).
        "transfer to platform device timed out",
        "backend compile deadline exceeded on worker 0",
        "unknown backend configuration flag --xla_foo ignored",
        "the backend returned RESOURCE_EXHAUSTED while allocating 2.1G",
        "stream executor platform reported a transient DMA error",
        "platform event pool exhausted; retry the launch",
        "backend 'tpu' heartbeat lost; reconnecting",
        "watchdog: no response from backend within 30s",
    ],
)
def test_backend_platform_mentions_stay_transient(msg):
    assert classify_failure(RuntimeError(msg)) == TRANSIENT


@pytest.mark.parametrize(
    "msg",
    [
        # ...while the specific jax backend-INIT signatures stay
        # permanent, in the exact shapes xla_bridge raises them.
        "Unable to initialize backend 'tpu': UNAVAILABLE: no TPU found",
        "Backend 'tpu' failed to initialize: device is held by another process",
        "Unknown backend: 'tpu' requested, but no platforms are present",
        "unknown backend tpu",
        "No devices found for platform tpu",
        "platform 'tpu' is not registered",
    ],
)
def test_backend_init_signatures_stay_permanent(msg):
    assert classify_failure(RuntimeError(msg)) == PERMANENT


def test_classify_failure_text_matches_exception_classification():
    """bench/runner.py classifies dead section children by their stderr
    tail; the text path must agree with the exception path."""
    from tendermint_tpu.ops.device_policy import classify_failure_text

    for msg, want in [
        ("RuntimeError: Unable to initialize backend 'tpu': gone", PERMANENT),
        ("jaxlib.xla_extension.XlaRuntimeError: transfer timed out", TRANSIENT),
        ("unknown backend configuration flag", TRANSIENT),
        ("", TRANSIENT),
    ]:
        assert classify_failure_text(msg) == want, msg
        assert classify_failure(RuntimeError(msg)) == want, msg


# --- state machine unit tests (fake clock, no device) ------------------------


def test_transient_failures_ride_degraded_until_budget():
    clk = FakeClock()
    h = DeviceHealth(retry_budget=3, cooldown_base=1.0, clock=clk)
    for i in range(2):
        assert h.begin_attempt() is not None
        h.record_failure(RuntimeError("flaky launch"))
        assert h.state == DEGRADED
    # attempts are still admitted while DEGRADED
    a = h.begin_attempt()
    assert a is not None and not a.probe
    h.record_failure(RuntimeError("flaky launch"), a)  # budget spent
    assert h.state == COOLDOWN
    assert h.transitions == [
        (HEALTHY, DEGRADED),
        (DEGRADED, COOLDOWN),
    ]


def test_cooldown_answers_instantly_then_admits_one_probe():
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=2.0, clock=clk)
    h.record_failure(RuntimeError("boom"), h.begin_attempt())
    assert h.state == COOLDOWN
    # circuit open: instant None, no blocking, no device attempts
    assert h.begin_attempt() is None
    clk.advance(1.0)
    assert h.begin_attempt() is None
    # backoff expired: exactly ONE caller becomes the half-open probe
    clk.advance(1.5)
    probe = h.begin_attempt()
    assert probe is not None and probe.probe
    assert h.begin_attempt() is None  # second caller: still open
    h.record_success(probe)
    assert h.state == HEALTHY
    assert h.begin_attempt() is not None


def test_probe_failure_rearms_with_doubled_backoff():
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=1.0, cooldown_max=3.0, clock=clk)
    h.record_failure(RuntimeError("boom"), h.begin_attempt())
    clk.advance(1.1)
    probe = h.begin_attempt()
    assert probe is not None and probe.probe
    h.record_failure(RuntimeError("boom again"), probe)
    assert h.state == COOLDOWN
    # first cooldown was 1.0; the re-arm uses the doubled 2.0
    clk.advance(1.5)
    assert h.begin_attempt() is None
    clk.advance(0.6)
    probe2 = h.begin_attempt()
    assert probe2 is not None and probe2.probe
    # success resets the backoff to base
    h.record_success(probe2)
    snap = h.snapshot()
    assert snap["state"] == HEALTHY
    assert snap["next_cooldown"] == 1.0


def test_backoff_is_capped():
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=1.0, cooldown_max=4.0, clock=clk)
    for _ in range(6):
        a = h.begin_attempt()
        if a is None:
            clk.advance(100.0)
            a = h.begin_attempt()
        h.record_failure(RuntimeError("boom"), a)
    assert h.snapshot()["next_cooldown"] == 4.0


def test_permanent_failure_disables_terminally():
    clk = FakeClock()
    h = DeviceHealth(clock=clk)
    h.record_failure(RuntimeError("unable to initialize backend"), h.begin_attempt())
    assert h.state == DISABLED and h.broken
    assert h.begin_attempt() is None
    # neither time nor a stray success resurrects a DISABLED device
    clk.advance(10_000.0)
    assert h.begin_attempt() is None
    h.record_success()
    assert h.state == DISABLED


def test_success_resets_consecutive_failures():
    h = DeviceHealth(retry_budget=3, clock=FakeClock())
    h.record_failure(RuntimeError("a"))
    h.record_failure(RuntimeError("b"))
    h.record_success(h.begin_attempt())
    assert h.state == HEALTHY
    # the budget is full again: two more transients stay DEGRADED
    h.record_failure(RuntimeError("c"))
    h.record_failure(RuntimeError("d"))
    assert h.state == DEGRADED


def test_only_one_probe_under_concurrency():
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=1.0, clock=clk)
    h.record_failure(RuntimeError("boom"), h.begin_attempt())
    clk.advance(2.0)
    admitted = []
    barrier = threading.Barrier(8)

    def contend():
        barrier.wait()
        a = h.begin_attempt()
        if a is not None:
            admitted.append(a)

    threads = [threading.Thread(target=contend) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(admitted) == 1 and admitted[0].probe


def test_metrics_mirroring():
    reg = Registry()
    m = OpsMetrics(reg)
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=1.0, clock=clk)
    h.bind_metrics(m)
    h.record_failure(RuntimeError("boom"), h.begin_attempt())
    clk.advance(1.1)
    h.record_success(h.begin_attempt())
    h.record_failure(RuntimeError("unable to initialize backend"))
    h.count_fallback("ed25519", 20)
    text = reg.expose()
    assert "tendermint_ops_device_health_state 3" in text
    assert (
        'tendermint_ops_device_health_transitions_total{from_state="healthy",'
        'to_state="cooldown"} 1' in text
    )
    assert (
        'tendermint_ops_device_health_transitions_total{from_state="cooldown",'
        'to_state="healthy"} 1' in text
    )
    assert 'tendermint_ops_device_failures_total{kind="transient"} 1' in text
    assert 'tendermint_ops_device_failures_total{kind="permanent"} 1' in text
    assert 'tendermint_ops_device_fallbacks_total{engine="ed25519"} 1' in text
    assert (
        'tendermint_ops_device_fallback_lanes_total{engine="ed25519"} 20'
        in text
    )
    assert "tendermint_ops_device_probe_seconds_count 1" in text


# --- fault-injection harness -------------------------------------------------


def test_fault_plan_raise_on_nth_call():
    plan = fault_injection.FaultPlan(site="x", fail_calls=(2,))
    plan.on_call("x.a")  # 1: ok
    with pytest.raises(fault_injection.DeviceFault):
        plan.on_call("x.b")  # 2: boom
    plan.on_call("x.c")  # 3: ok
    assert plan.calls == 3 and plan.faults_raised == 1
    plan.on_call("other.site")  # filtered: not counted
    assert plan.calls == 3


def test_fault_plan_window_and_kill_revive():
    plan = fault_injection.FaultPlan(fail_from=2, fail_count=2)
    plan.on_call("s")
    for _ in range(2):
        with pytest.raises(fault_injection.DeviceFault):
            plan.on_call("s")
    plan.on_call("s")  # window passed
    plan.kill()
    with pytest.raises(fault_injection.DeviceFault):
        plan.on_call("s")
    plan.revive()
    plan.on_call("s")


def test_env_plan_parsing():
    plan = fault_injection._parse_env_plan(
        "site=ed25519;fail_from=1;fail_count=5;permanent=1;latency=0.5"
    )
    assert plan.site == "ed25519"
    assert plan.fail_from == 1 and plan.fail_count == 5
    assert plan.permanent and plan.latency == 0.5
    with pytest.raises(ValueError):
        fault_injection._parse_env_plan("bogus_key=1")


# --- acceptance: the real verify path under injected faults ------------------
#
# Both engines reach the device through one loop (ops/ed25519_batch
# ._dispatch_jobs, then ``collect()``), so each of these runs once an
# engine: ``eng.name`` is the engine's fault-site prefix, ``eng.verify``
# its batch entry point and
# ``eng.batch(bad=...)`` well-formed lanes with wrong signatures at
# ``bad`` (sr25519 batches are small: its host prep is pure Python).


class _Engine:
    def __init__(self, name, verify, lanes, make):
        self.name, self.verify, self.lanes, self._make = name, verify, lanes, make

    def batch(self, n=None, bad=()):
        return self._make(self.lanes if n is None else n, bad)


def _make_batch_sr(n, bad=()):
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = Sr25519PrivKey.from_secret(b"policy-%d" % i)
        pks.append(priv.pub_key().bytes())
        msgs.append(b"tampered" if i in bad else b"vote-%d" % i)
        sigs.append(priv.sign(b"vote-%d" % i))
    return pks, msgs, sigs


def _verify_batch_sr(pks, msgs, sigs):
    from tendermint_tpu.ops.sr25519_batch import verify_batch_sr

    return verify_batch_sr(pks, msgs, sigs)


@pytest.fixture(
    params=[
        _Engine("ed25519", verify_batch, 20, make_batch),
        _Engine("sr25519", _verify_batch_sr, 8, _make_batch_sr),
    ],
    ids=lambda e: e.name,
)
def eng(request):
    return request.param


def test_transient_fault_zero_failed_verifications_and_recovery(monkeypatch, eng):
    """ISSUE acceptance: a transient device failure mid-run costs ZERO
    failed verifications (CPU fallback absorbs the chunk) and the
    machine recovers HEALTHY -> COOLDOWN -> HEALTHY automatically."""
    clk = FakeClock()
    h = DeviceHealth(retry_budget=1, cooldown_base=1.0, clock=clk)
    monkeypatch.setattr(device_policy, "shared", h)
    pks, msgs, sigs = eng.batch()

    with fault_injection.inject(site=eng.name, fail_calls=(1,)):
        with pytest.warns(UserWarning):
            oks = eng.verify(pks, msgs, sigs)
    assert all(oks), "CPU fallback must absorb the injected fault"
    assert h.state == COOLDOWN  # retry_budget=1: straight to cooldown
    assert (HEALTHY, COOLDOWN) in h.transitions

    # during cooldown the whole batch takes the CPU path instantly
    before = h.snapshot()["fallback_batches"]
    assert all(eng.verify(pks, msgs, sigs))
    assert h.snapshot()["fallback_batches"] > before
    assert h.state == COOLDOWN

    # backoff expires -> the next batch is the half-open probe -> HEALTHY
    clk.advance(1.5)
    assert all(eng.verify(pks, msgs, sigs))
    assert h.state == HEALTHY
    assert h.transitions == [(HEALTHY, COOLDOWN), (COOLDOWN, HEALTHY)]


def test_transient_fault_still_rejects_bad_signatures(monkeypatch, eng):
    """The CPU fallback is a verifier, not a rubber stamp."""
    h = DeviceHealth(retry_budget=1, clock=FakeClock())
    monkeypatch.setattr(device_policy, "shared", h)
    pks, msgs, sigs = eng.batch(bad=(3, 7))
    with fault_injection.inject(site=eng.name, fail_from=1, fail_count=100):
        with pytest.warns(UserWarning):
            oks = eng.verify(pks, msgs, sigs)
    assert oks[3] is False and oks[7] is False
    assert sum(oks) == eng.lanes - 2


def test_permanent_fault_disables_and_completes_on_cpu(monkeypatch, eng):
    """ISSUE acceptance: a permanent failure leaves every verification
    answered (on CPU), the machine DISABLED, and metrics exposing it."""
    reg = Registry()
    h = DeviceHealth(clock=FakeClock())
    h.bind_metrics(OpsMetrics(reg))
    monkeypatch.setattr(device_policy, "shared", h)
    pks, msgs, sigs = eng.batch(bad=(5,))

    with fault_injection.inject(site=eng.name, fail_calls=(1,), permanent=True):
        with pytest.warns(UserWarning):
            oks = eng.verify(pks, msgs, sigs)
    assert sum(oks) == eng.lanes - 1 and oks[5] is False
    assert h.state == DISABLED and h.broken

    # later batches never touch the device again, still all answered
    oks = eng.verify(pks, msgs, sigs)
    assert sum(oks) == eng.lanes - 1
    text = reg.expose()
    assert "tendermint_ops_device_health_state 3" in text
    assert 'tendermint_ops_device_failures_total{kind="permanent"} 1' in text
    assert 'tendermint_ops_device_fallbacks_total{engine="%s"}' % eng.name in text


def test_collect_phase_fault_patched_per_chunk(monkeypatch, eng):
    """Async dispatch surfaces runtime errors at materialization; a
    collect-phase fault must be absorbed chunk-locally too."""
    h = DeviceHealth(retry_budget=5, clock=FakeClock())
    monkeypatch.setattr(device_policy, "shared", h)
    pks, msgs, sigs = eng.batch()
    with fault_injection.inject(site=eng.name + ".collect", fail_calls=(1,)):
        with pytest.warns(UserWarning):
            oks = eng.verify(pks, msgs, sigs)
    assert all(oks)
    assert h.failure_counts[TRANSIENT] == 1


def test_injected_latency_does_not_fail_calls(monkeypatch, eng):
    h = DeviceHealth(clock=FakeClock())
    monkeypatch.setattr(device_policy, "shared", h)
    pks, msgs, sigs = eng.batch(4)
    with fault_injection.inject(site=eng.name, latency=0.01) as plan:
        oks = eng.verify(pks, msgs, sigs)
    assert all(oks)
    assert plan.calls >= 1 and plan.faults_raised == 0
    assert h.state == HEALTHY


def test_scheduler_keeps_draining_with_fallback():
    """A flush whose primary verifier raises must still produce real
    verdicts via the fallback — the scheduler never wedges and never
    fails a whole flush closed when the host oracle can answer it."""
    from tendermint_tpu.crypto.ed25519_ref import verify_zip215
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    def primary(pks, msgs, sigs):
        raise fault_injection.DeviceFault("device gone")

    def host(pks, msgs, sigs):
        return [verify_zip215(p, m, s) for p, m, s in zip(pks, msgs, sigs)]

    sched = VerifyScheduler(primary, max_delay=0.005, fallback_fn=host)
    sched.start()
    try:
        pks, msgs, sigs = make_batch(4, bad=(2,))
        handles = [
            sched.submit(p, m, s) for p, m, s in zip(pks, msgs, sigs)
        ]
        oks = [sched.wait(hdl, timeout=5.0) for hdl in handles]
        assert oks == [True, True, False, True]
        assert sched.flush_errors >= 1
        assert sched.fallback_flushes >= 1
    finally:
        sched.stop()

"""MXU-first field-multiply autotuner (ops/autotune.py).

Pins the precedence ladder (explicit env > tuner > field32 default),
the per-(platform, bucket) keying, the persisted-winner JSON cache —
including the acceptance property that a warm cache file SHORT-CIRCUITS
the timing pass entirely — and end-to-end verify parity when the tuner
picks each impl.
"""

import json

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.libs.metrics import OpsMetrics, Registry
from tendermint_tpu.ops import autotune, ed25519_batch, field32


@pytest.fixture(autouse=True)
def _tuner_isolated(tmp_path, monkeypatch):
    """Every test gets the tuner ON, a private cache file, and a clean
    in-memory state; the repo-level default cache path is never touched."""
    monkeypatch.setenv("TENDERMINT_TPU_AUTOTUNE", "on")
    monkeypatch.setenv(
        "TENDERMINT_TPU_AUTOTUNE_CACHE", str(tmp_path / "autotune.json")
    )
    monkeypatch.delenv("TENDERMINT_TPU_FIELD_MUL", raising=False)
    autotune.reset()
    yield
    autotune.reset()


def _pin_measure(monkeypatch, result):
    calls = []

    def fake_measure(backend, lanes):
        calls.append((backend, lanes))
        return dict(result)

    monkeypatch.setattr(autotune, "_measure", fake_measure)
    return calls


# --- keying -----------------------------------------------------------------


def test_bucket_mirrors_kernel_widths():
    assert autotune.bucket(1) == 64
    assert autotune.bucket(64) == 64
    assert autotune.bucket(65) == 256
    assert autotune.bucket(4096) == 4096
    assert autotune.bucket(100_000) == 4096


def test_disabled_modes(monkeypatch):
    monkeypatch.setenv("TENDERMINT_TPU_AUTOTUNE", "off")
    assert not autotune.enabled()
    # auto keeps CPU on the deterministic default — no timing pass ever.
    monkeypatch.setenv("TENDERMINT_TPU_AUTOTUNE", "auto")
    assert not autotune.enabled()


# --- precedence -------------------------------------------------------------


def test_explicit_env_beats_tuner(monkeypatch):
    calls = _pin_measure(monkeypatch, {"vpu": 9.0, "mxu": 1.0})
    monkeypatch.setenv("TENDERMINT_TPU_FIELD_MUL", "vpu")
    assert autotune.mul_impl_for(None, 64) == "vpu"
    assert calls == [], "operator choice must never pay a timing pass"


def test_disabled_falls_back_to_field32(monkeypatch):
    calls = _pin_measure(monkeypatch, {"vpu": 1.0, "mxu": 9.0})
    monkeypatch.setenv("TENDERMINT_TPU_AUTOTUNE", "off")
    assert autotune.mul_impl_for(None, 64) == field32.get_mul_impl()
    assert calls == []


# --- measurement + persistence ----------------------------------------------


def test_winner_selected_and_persisted(monkeypatch):
    calls = _pin_measure(monkeypatch, {"vpu": 5.0, "mxu": 2.0})
    assert autotune.mul_impl_for(None, 33) == "mxu"
    assert calls == [(None, 64)], "one timing pass at the bucket width"
    with open(autotune.cache_path(), encoding="utf-8") as fh:
        data = json.load(fh)
    assert data["selections"]["cpu:64"] == {
        "impl": "mxu",
        "ms": {"vpu": 5.0, "mxu": 2.0},
    }
    # Same bucket resolves from memory — still exactly one measurement.
    assert autotune.mul_impl_for(None, 64) == "mxu"
    assert len(calls) == 1
    # A different bucket is its own key.
    autotune.mul_impl_for(None, 300)
    assert calls[-1] == (None, 1024)


def test_persisted_cache_short_circuits_timing(monkeypatch):
    """Acceptance pin: a later process (fresh in-memory state) reads the
    winner from the JSON file and never re-times."""
    _pin_measure(monkeypatch, {"vpu": 5.0, "mxu": 2.0})
    assert autotune.mul_impl_for(None, 64) == "mxu"
    autotune.reset()  # "new process": memory gone, file survives

    def explode(backend, lanes):
        raise AssertionError("warm cache must not re-measure")

    monkeypatch.setattr(autotune, "_measure", explode)
    assert autotune.mul_impl_for(None, 64) == "mxu"
    assert autotune.stats()["selections"] == {"cpu:64": "mxu"}


def test_corrupt_cache_file_re_times(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    monkeypatch.setenv("TENDERMINT_TPU_AUTOTUNE_CACHE", str(path))
    _pin_measure(monkeypatch, {"vpu": 1.0, "mxu": 9.0})
    assert autotune.mul_impl_for(None, 64) == "vpu"


def test_measure_failure_raises(monkeypatch):
    """A timing kernel that cannot compile or run is a device failure:
    it reaches the caller (the dispatching engine's health machine), it
    is not turned into the default multiplier."""

    def explode(backend, lanes):
        raise RuntimeError("backend cannot time")

    monkeypatch.setattr(autotune, "_measure", explode)
    with pytest.raises(RuntimeError, match="cannot time"):
        autotune.mul_impl_for(None, 64)
    assert autotune.stats()["selections"] == {}


# --- metrics ----------------------------------------------------------------


def test_selection_counted_once_per_key(monkeypatch):
    reg = Registry()
    ops = OpsMetrics(reg)
    autotune.bind_metrics(ops)
    _pin_measure(monkeypatch, {"vpu": 5.0, "mxu": 2.0})
    for _ in range(3):
        autotune.mul_impl_for(None, 64)
    key = (("impl", "mxu"),)
    assert ops.autotune_selections._values.get(key, 0.0) == 1
    # The persisted-cache path counts too (fresh process, same file).
    autotune.reset()
    autotune.mul_impl_for(None, 64)
    assert ops.autotune_selections._values.get(key, 0.0) == 2
    autotune.bind_metrics(None)


# --- real timing + end-to-end parity ----------------------------------------


def test_real_measure_runs_on_cpu():
    """The actual timing kernel compiles and returns sane numbers for
    both impls (no monkeypatching) at the smallest bucket."""
    ms = autotune._measure(None, 64)
    assert set(ms) == {"vpu", "mxu"}
    assert all(v > 0.0 for v in ms.values())


@pytest.mark.parametrize("winner", ["vpu", "mxu"])
def test_verify_parity_under_each_winner(monkeypatch, winner):
    """verify_batch verdicts are identical whichever impl the tuner
    adopts — the autotuned default can never change answers."""
    loser = "mxu" if winner == "vpu" else "vpu"
    _pin_measure(monkeypatch, {winner: 1.0, loser: 9.0})
    pks, msgs, sigs = [], [], []
    for i in range(6):
        sk, pk = ref.keypair_from_seed(bytes([i + 60]) * 32)
        m = b"autotune lane %d" % i
        pks.append(pk)
        msgs.append(m)
        sigs.append(ref.sign(sk, m))
    sigs[2] = bytes(64)
    assert autotune.mul_impl_for(None, len(pks)) == winner
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert not oks[2] and sum(oks) == 5

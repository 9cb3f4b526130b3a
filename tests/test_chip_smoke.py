"""chip_smoke.py on the CPU: the checks that make a hidden device fail.

The smoke itself only passes on a TPU. Here its phase functions run
with the expected platform passed as ``"cpu"`` so that the counter
checks execute, and the properties the bring-up PR established are
pinned: no CPU mode in ``main()``, a failing kernel fails the phase
instead of passing on the oracle, a failing implementation is counted
by the health machine rather than switched, and the compile cache can
be placed from outside.

The two tests that compile the table/resident kernels and start the
daemon are marked ``slow``: tier-1 already runs into its time limit on
a cold compile cache, and every second spent here would push a test
off its end.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from tendermint_tpu.ops import device_policy, ed25519_batch  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_engine_state(monkeypatch):
    """Result cache off (repeats must reach the device) and a pristine
    health machine before and after."""
    monkeypatch.setenv("TENDERMINT_TPU_RESULT_CACHE", "0")
    monkeypatch.delenv("TENDERMINT_TPU_VERIFY_REMOTE", raising=False)
    device_policy.shared.reset()
    yield
    device_policy.shared.reset()


def _run_main(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


# --- main(): no CPU mode, no result on failure --------------------------------


def test_main_exits_nonzero_off_tpu_and_names_jax_platforms():
    proc = _run_main(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert "want 'tpu'" in proc.stderr and "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_main_exits_nonzero_alone_in_a_directory(tmp_path):
    script = shutil.copy(os.path.join(REPO, "chip_smoke.py"), str(tmp_path))
    proc = _run_main(str(tmp_path), script)
    assert proc.returncode != 0
    assert "repository is not beside chip_smoke.py" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phase_fails_on_the_wrong_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="want 'tpu'"):
        chip_smoke.library_phase("tpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=0, mixed_sync=None)


# --- the library phase's checks, live, on the CPU -----------------------------


def test_edge_vectors_phase_passes_on_cpu():
    """The phase's first step — ZIP-215 edge vectors through
    ops.verify_batch, lane for lane against the oracle, lanes
    dispatched == lanes sent, health counters flat — on the 64-lane
    legacy kernel other suites compile anyway."""
    report = chip_smoke.library_phase("cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=0, mixed_sync=None)
    assert report["device"]["platform"] == "cpu"
    assert report["impl"] == "xla" and report["host_hash"] == report["host_secp256k1"] == "native"
    edge = report["edge"]
    assert 0 < edge["accepted"] < edge["lanes"]
    assert report["sr25519"] == [] and report["mixed_committee"] is None
    # more than one device: the same lanes through the sharded path, on
    # the 512-lane 8-way legacy kernel tests/test_mesh.py compiles
    sharded = report["sharded_edge"]
    assert (sharded["lanes"], sharded["accepted"]) == (edge["lanes"], edge["accepted"])
    assert sharded["sharded"] == [("legacy", 8, 512, "xla")]
    # the XLA graph's mesh kernels record no first-call span
    assert chip_smoke._sharded_first_calls(report) == []
    json.dumps(report)


def test_the_early_begin_case_passes_on_cpu(monkeypatch):
    """One engine job + a tail through ``crypto.BatchVerifier`` lane by
    lane, at a job of 32 lanes (the accessor stood in: the chip's is
    4,096): the job is begun before ``verify()``, the lanes on both
    sides of the seam and the last are refused, on 64-lane legacy
    kernels."""
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: 32)
    # as after the sizes, which this call leaves out: a full job has run
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519"})
    report = chip_smoke.library_phase(
        "cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=20, mixed_sync=None
    )
    assert report["early_begin"]["refused"] == [31, 32, 51]
    assert (report["early_begin"]["job"], report["early_begin"]["tail"]) == (32, 20)
    json.dumps(report)


def test_the_early_begin_case_fails_where_nothing_is_begun_early(monkeypatch):
    """A verifier that waits for ``verify()`` is what the case exists
    to catch: with ``add`` kept from looking, the verdicts are still
    right and the phase fails on the blocks it reads."""
    from tendermint_tpu.crypto import batch as crypto_batch

    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: 32)
    monkeypatch.setattr(ed25519_batch, "_ENGINES_WITH_A_JOB_RUN", {"ed25519"})
    monkeypatch.setattr(crypto_batch.DeviceBatchVerifier, "_look", lambda self: None)
    with pytest.raises(chip_smoke.SmokeFailure, match="blocks begun"):
        chip_smoke.library_phase(
            "cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=20, mixed_sync=None
        )


def test_sr25519_and_the_mixed_committee_pass_on_cpu():
    """The phase's last steps at their smallest: a full 64-lane bucket
    of sr25519 lanes against the schnorrkel oracle, and a committee of
    the three key types through verify_commit, sound and tampered."""
    report = chip_smoke.library_phase(
        "cpu", sizes=(), sync=None, sr_buckets=(64,), mixed=45, early_tail=0, mixed_sync=None
    )
    (sr,) = report["sr25519"]
    assert sr["lanes"] == 64 and 0 < sr["accepted"] < 64
    mixed = report["mixed_committee"]
    assert mixed["sent"] == {"ed25519": 21, "sr25519": 21, "secp256k1": 3}
    assert len(mixed["tampered"]) == 3
    json.dumps(report)


def test_the_mixed_catch_up_window_passes_on_cpu():
    """The phase's last step at its smallest: a window of 4 commits over
    45 validators of three key types, absent and nil votes, one included
    lane of each type tampered, through ``verify_commits_pipelined``:
    two launches and one host call a window, every block's verdict the
    oracles'."""
    report = chip_smoke.library_phase(
        "cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=0, mixed_sync=(45, 4)
    )
    window = report["mixed_window"]
    assert window["sent"]["secp256k1"] > 0 and sum(window["sent"].values()) == 4 * 31
    assert len(window["tampered"]) == 3 and window["launches"] == 2
    assert report["mixed_committee"] is None
    json.dumps(report)


def test_the_mixed_catch_up_window_fails_where_each_block_is_verified_alone(monkeypatch):
    """The road the pipeline took until PR 51 — a block holding a key
    that is not ed25519 given to ``verify_commit_light`` alone — gives
    every verdict right and is what the step exists to catch."""
    from tendermint_tpu.parallel import pipeline

    def block_by_block(tasks, mesh=None, use_device=None):
        return [pipeline._verify_light_single(task) for task in tasks]

    monkeypatch.setattr(pipeline, "verify_commits_pipelined", block_by_block)
    with pytest.raises(chip_smoke.SmokeFailure, match="a block left the window's plan"):
        chip_smoke.library_phase(
            "cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=0, mixed_sync=(45, 4)
        )


def test_kernel_failure_fails_the_phase_instead_of_passing_on_the_oracle(
    monkeypatch,
):
    """With a dead kernel every verdict still comes out right — from
    the host oracle. That is exactly what the smoke must not accept."""

    def boom(kind, n, backend, mul_impl):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setattr(ed25519_batch, "_compiled_kernel", boom)
    with pytest.warns(UserWarning, match="CPU fallback"):
        with pytest.raises(chip_smoke.SmokeFailure, match="host oracle"):
            chip_smoke.library_phase("cpu", sizes=(), sync=None, sr_buckets=(), mixed=0, early_tail=0, mixed_sync=None)


def test_failing_implementation_is_counted_not_switched(monkeypatch):
    """``pallas`` asked for and failing: the chunk goes to the health
    machine and the host oracle like any device failure; the XLA graph
    is NOT tried behind the caller's back."""
    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.ops import pallas_verify

    def boom(n, block=256, interpret=False):
        raise RuntimeError("mosaic unavailable")

    def no_xla(*args, **kwargs):
        raise AssertionError("switched silently to the XLA graph")

    monkeypatch.setenv(ed25519_batch._IMPL_ENV, "pallas")
    monkeypatch.setattr(pallas_verify, "compiled_verify", boom)
    monkeypatch.setattr(ed25519_batch, "_compiled_kernel", no_xla)
    pks, msgs, sigs = [], [], []
    for i in range(8):
        priv, pub = ref.keypair_from_seed(bytes([i + 1]) * 32)
        pks.append(pub)
        msgs.append(b"vote %d" % i)
        sigs.append(ref.sign(priv, msgs[-1]))
    sigs[3] = sigs[3][:32] + bytes(32)
    want = [True] * 8
    want[3] = False
    with pytest.warns(UserWarning, match="mosaic unavailable"):
        assert ed25519_batch.verify_batch(pks, msgs, sigs) == want
    snap = device_policy.shared.snapshot()
    assert snap["failures"]["transient"] == 1
    assert snap["fallback_batches"] == 1
    assert snap["state"] == device_policy.DEGRADED


@pytest.mark.parametrize("kernel", ["verify", "verify_tables", "verify_resident"])
def test_compiles_holds_one_device_kernels_to_the_resolved_impl(kernel):
    """A one-device kernel that ran another implementation than ``auto``
    resolved to fails the smoke: the resident kernel too."""

    def span(engine):
        return {
            "name": "kernel_compile",
            "dur": 2.5e6,
            "args": {"engine": engine, "kernel": kernel, "lanes": 256},
        }

    assert chip_smoke._compiles([span("pallas")], "pallas") == [
        ["pallas", kernel, 256, 2.5]
    ]
    assert chip_smoke._compiles([span("ed25519")], "xla") == [
        ["xla", kernel, 256, 2.5]
    ]
    with pytest.raises(chip_smoke.SmokeFailure, match=kernel + " kernel at 256"):
        chip_smoke._compiles([span("ed25519")], "pallas")


def _sharded_compile_span(stored, kernel="verify_resident", engine="pallas"):
    return {
        "name": "kernel_compile",
        "dur": 3.25e6,
        "args": {"engine": engine, "kernel": kernel, "lanes": 4096, "devices": 4,
                 "stored": stored},
    }


@pytest.mark.parametrize("stored", ["hit", "miss"])
def test_compiles_reads_a_sharded_first_call(stored):
    """A mesh's Pallas kernel: the row carries the devices and what the
    kernel store did, and is held to the resolved implementation too."""
    one_device = {
        "name": "kernel_compile", "dur": 1e6,
        "args": {"engine": "pallas", "kernel": "verify", "lanes": 64},
    }
    rows = chip_smoke._compiles([one_device, _sharded_compile_span(stored)], "pallas")
    assert rows == [
        ["pallas", "verify", 64, 1.0],
        ["pallas", "verify_resident", 4096, 3.25, 4, stored],
    ]
    with pytest.raises(chip_smoke.SmokeFailure, match="verify_resident kernel at 4096"):
        chip_smoke._compiles([_sharded_compile_span(stored)], "xla")


def test_second_run_must_find_the_kernel_store_warm():
    """Run 1 may walk the kernel body (a cold store); run 2 may not."""

    def report(stored_edge, stored_big):
        rows = lambda stored, kernel: chip_smoke._compiles(
            [_sharded_compile_span(stored, kernel)], "pallas"
        )
        return {
            "edge": {"compiles": [["pallas", "verify", 64, 1.0]]},
            "sharded_edge": {"compiles": rows(stored_edge, "verify")},
            "sizes": [{"compiles": []}, {"compiles": rows(stored_big, "verify_resident")}],
        }

    cold = report("miss", "miss")
    assert [c[1:] for c in chip_smoke._sharded_first_calls(cold)] == [
        ["verify", 4096, 3.25, 4, "miss"],
        ["verify_resident", 4096, 3.25, 4, "miss"],
    ]
    chip_smoke._check_store_warm(2, report("hit", "hit"))
    with pytest.raises(chip_smoke.SmokeFailure, match="run 2 traced a kernel"):
        chip_smoke._check_store_warm(2, report("hit", "miss"))
    # one device: no sharded part in the report at all
    alone = {"edge": {"compiles": []}, "sizes": []}
    assert chip_smoke._sharded_first_calls(alone) == []
    chip_smoke._check_store_warm(2, alone)


def _one_device_compile_span(stored, kernel="verify_resident"):
    return {
        "name": "kernel_compile", "dur": 0.25e6,
        "args": {"engine": "pallas", "kernel": kernel, "lanes": 256, "impl": "pallas",
                 "stored": stored},
    }


@pytest.mark.parametrize("kernel", ["verify", "verify_tables", "verify_resident", "verify_sr"])
@pytest.mark.parametrize("stored", ["hit", "miss"])
def test_compiles_reads_a_one_device_first_call_from_the_store(stored, kernel):
    """PR 50: one device's Pallas programs come from the kernel store
    too. The row says one device and what the store did; it is no
    sharded first call."""
    rows = chip_smoke._compiles([_one_device_compile_span(stored, kernel)], "pallas")
    assert rows == [["pallas", kernel, 256, 0.25, 1, stored]]
    report = {"edge": {"compiles": rows}, "sizes": []}
    assert chip_smoke._stored_first_calls(report) == rows
    assert chip_smoke._sharded_first_calls(report) == []
    assert chip_smoke._stored_counts(report) == ((1, 0) if stored == "hit" else (0, 1))


@pytest.mark.parametrize(
    "part", ["edge", "sizes", "early_begin", "pipelined", "sr25519", "mixed_committee", "mixed_window"]
)
def test_second_run_must_find_the_one_device_programs_in_the_store(part):
    """Run 2 of the library phase may walk no kernel body on one device
    either, in whichever part of the report the first call lies."""

    def report(stored):
        rows = chip_smoke._compiles(
            [_one_device_compile_span(stored), _sharded_compile_span("hit")], "pallas"
        )
        rep = {"edge": {"compiles": []}, "sizes": [{"compiles": []}], "sr25519": [{"compiles": []}],
               "early_begin": {"compiles": []}, "pipelined": {"compiles": []},
               "mixed_committee": {"compiles": []}, "mixed_window": {"compiles": []}}
        holder = rep[part][0] if isinstance(rep[part], list) else rep[part]
        holder["compiles"] = rows
        return rep

    chip_smoke._check_store_warm(2, report("hit"))
    assert chip_smoke._stored_counts(report("hit")) == (2, 0)
    assert chip_smoke._stored_counts(report("miss")) == (1, 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="run 2 traced a kernel.*256, 0.25, 1, 'miss'"):
        chip_smoke._check_store_warm(2, report("miss"))


@pytest.mark.parametrize("ran,ok", [("pallas", True), ("xla", False), (None, False)])
def test_sharded_chunks_are_held_to_the_resolved_impl(ran, ok):
    span = {
        "name": "mesh_dispatch",
        "args": {"kind": "resident", "devices": 4, "lanes": 16384},
    }
    if ran is not None:
        span["args"]["impl"] = ran
    if ok:
        assert chip_smoke._sharded([span, span], "pallas") == [
            ("resident", 4, 16384, "pallas")
        ]
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="sharded resident chunk"):
            chip_smoke._sharded([span], "pallas")


def _chunk(kind, engine, at, lanes, sharded_over=None, padded=None):
    """A ``dispatch_chunk`` span and, where it went out over a mesh, the
    ``mesh_dispatch`` inside it."""
    out = [{"name": "dispatch_chunk", "ts": at, "dur": 100.0,
            "args": {"kind": kind, "engine": engine, "lanes": lanes}}]
    if sharded_over:
        out.append({"name": "mesh_dispatch", "ts": at + 10.0, "dur": 80.0,
                    "args": {"kind": kind, "engine": engine, "devices": sharded_over,
                             "lanes": padded or lanes, "impl": "pallas"}})
    return out


def test_a_mixed_commits_two_sub_batches_are_held_to_the_mesh():
    """PR 48: on two devices or more every chunk of the kinds named has
    a ``mesh_dispatch`` over all of them, and no chunk stayed behind."""
    both = _chunk("resident", "ed25519", 0.0, 280, 4, 1024) + _chunk("sr25519", "sr25519", 500.0, 280, 4, 1024)
    assert chip_smoke._check_sharded_kinds(both, "pallas", 4, {"sr25519"}, "mixed") == [
        ("resident", 4, 1024, "pallas"), ("sr25519", 4, 1024, "pallas"),
    ]
    with pytest.raises(chip_smoke.SmokeFailure, match="want kinds .'sr25519'. over 4 devices"):
        chip_smoke._check_sharded_kinds(both[:2], "pallas", 4, {"sr25519"}, "mixed")  # no sr25519 chunk sharded
    with pytest.raises(chip_smoke.SmokeFailure, match="over 4 devices"):  # a degraded mesh
        chip_smoke._check_sharded_kinds(
            both[:2] + _chunk("sr25519", "sr25519", 500.0, 280, 3, 1026), "pallas", 4, {"sr25519"}, "mixed"
        )
    local = both + _chunk("legacy", "ed25519", 900.0, 12)
    with pytest.raises(chip_smoke.SmokeFailure, match="stayed on one device"):
        chip_smoke._check_sharded_kinds(local, "pallas", 4, {"sr25519"}, "mixed")


def test_the_store_warm_check_reads_the_sr25519_and_the_mixed_parts_too():
    """A second run that walks the sr25519 shard program's body is caught
    like one that walks an ed25519 one (PR 48)."""
    rows = lambda stored: chip_smoke._compiles([_sharded_compile_span(stored, "verify_sr")], "pallas")
    report = lambda sr, mixed: {
        "edge": {"compiles": []}, "sizes": [],
        "sr25519": [{"compiles": [["pallas", "verify_sr", 64, 1.0]]}, {"compiles": rows(sr)}],
        "mixed_committee": {"compiles": rows(mixed)},
    }
    assert len(chip_smoke._sharded_first_calls(report("miss", "hit"))) == 2
    chip_smoke._check_store_warm(2, report("hit", "hit"))
    for cold in (report("miss", "hit"), report("hit", "miss")):
        with pytest.raises(chip_smoke.SmokeFailure, match="run 2 traced a kernel"):
            chip_smoke._check_store_warm(2, cold)
    chip_smoke._check_store_warm(2, {"edge": {"compiles": []}, "sizes": [], "sr25519": [], "mixed_committee": None})


# --- §5: a compile cache that can be placed from outside ----------------------


def _cache_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    ed25519_batch._enable_persistent_cache()
    return calls


def test_cache_dir_from_outside_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert _cache_updates(monkeypatch) == []


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert _cache_updates(monkeypatch) == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))
    ]


# --- the whole phases at 40 lanes (compile-heavy: outside tier-1) -------------


@pytest.fixture()
def _auto_paths_on(monkeypatch):
    """What ``auto`` turns on for tpu, turned on here, so that the
    device-hash and resident-store checks are live on the CPU."""
    from tendermint_tpu.ops import hash512, precompute, resident

    monkeypatch.setenv("TENDERMINT_TPU_DEVICE_HASH", "on")
    monkeypatch.setenv("TENDERMINT_TPU_RESIDENT", "on")
    precompute.reset()
    resident.reset()
    hash512.reset_stats()
    yield
    precompute.reset()
    resident.reset()
    hash512.reset_stats()


@pytest.mark.slow
def test_library_phase_at_40_validators(_auto_paths_on, monkeypatch):
    # two sizes and the windows, as on the chip: each is a node of its
    # own (chip_smoke._fresh_node), so each gets its tables at once
    monkeypatch.setattr(ed25519_batch, "job_lanes", lambda: 32)  # the chip's: 4,096
    report = chip_smoke.library_phase(
        "cpu", sizes=(24, 40), heights=2, sync=(20, 4), early_tail=20, mixed_sync=None
    )
    assert report["early_begin"]["refused"] == [31, 32, 51]
    small, size = report["sizes"]
    # two heights, the tampered commit twice, and one commit a block's edge
    assert (small["edges"], small["blocks"]) == ([0, 23], 1)
    assert small["counters"]["resident_hits"] == 24 * (4 + 2)
    assert small["counters"]["resident_uploads"] == 1
    assert (size["edges"], size["blocks"]) == ([0, 31, 32, 39], 2)
    c = size["counters"]
    assert c["hash_device_lanes"] == 40 * (5 + 4)
    assert c["resident_hits"] == 40 * (4 + 4) and c["resident_uploads"] == 1
    assert c["gathered_h2d_bytes"] == 0 and c["fallback_batches"] == 0
    p = report["pipelined"]
    assert p["lanes_per_window"] == 4 * 14
    assert p["counters"]["resident_hits"] == 2 * 4 * 14 and p["counters"]["fallback_batches"] == 0
    json.dumps(report)  # what the child writes for the parent


@pytest.mark.slow
def test_served_phase_at_40_validators(_auto_paths_on):
    report = chip_smoke.served_phase(
        "cpu", n_vals=40, clients=2, per_client=2, warmups=4
    )
    assert report["device"]["platform"] == "cpu"
    assert report["requests"] == 8 and report["lanes"] == 320
    assert report["stats"]["host_direct_lanes"] == 0
    assert report["device_health"]["fallback_batches"] == 0
    assert report["resident"]["hits"] > 0
    assert all(c["fallback_calls"] == 0 for c in report["clients"])

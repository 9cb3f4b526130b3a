"""Every rehearsal of the benchmark that tier-1 makes: ``chipbench.run
--rehearse`` on a cell's tiny twin, in a child, eighteen times, one
after another.

Every run of ``chipbench.run`` empties the checkout's one
``.chipbench_trace`` around its window, so a traced rehearsal has to
have the checkout to itself (``tests/helpers.trace_turn``). Until PR 46
these cases sat in the six files of the cells they rehearse, xdist gave
those files to six workers, and five of them waited on the lock while
one child ran: 590 s of waiting beside 381 s of children (PR 46's
timing, CHANGES.md). Here one worker runs them back to back and nobody
waits; ``tests/conftest.py`` starts this file first. Each case is still
its own, with the assertions it had; what it rehearses, and the readers
and generators behind it, are tested in the file of its cell
(``test_chipbench_<cell>.py``, ``test_early_begin.py``), which starts
no child. A new rehearsal goes here.
"""

from __future__ import annotations

import os

import pytest

from chipbench import spec
from tests import test_chipbench_callpath as callpath
from tests import test_chipbench_light as light
from tests import test_chipbench_mixed as mixed
from tests import test_chipbench_mixed_x4 as mixed_x4
from tests import test_chipbench_rotation as rotation
from tests import test_chipbench_syncmixed as syncmixed
from tests.helpers import over_limit, rehearse_cell, sound

# --- the call path's twin (tests/test_chipbench_callpath.py) -----------------------------


def test_tiny_twin_reports_every_new_metric():
    bench = callpath.BENCH
    value = sound(*rehearse_cell(bench, "tiny-hub-warm", 2**31 + 34, 1), (), bench, "tiny-hub-warm")
    got = {stem: value(stem, moves="commit_p50_ms") for stem in callpath.BASES}
    # what holds whatever the machine: a phase lies inside its span, the device cannot start what
    # has not been dispatched, and the parts of a call are each part of it
    assert got["h2d_put_ms"] + got["launch_ms"] <= got["dispatch_ms"]
    for stem in ("launch_lag_ms", "readback_lag_ms", "device_chain_gap_ms", "pre_dispatch_ms", "chain_ms",
                 "post_collect_ms", "d2h_ms"):
        assert got[stem] >= 0.0, (stem, got[stem])
    # a difference of two clocks less the waits by design: about 0 in a sound call, either side of
    # it by what the thread's clock rounds to and by the CPU the thread used inside a wait
    wall = got["pre_dispatch_ms"] + got["chain_ms"] + got["post_collect_ms"]
    assert abs(got["off_cpu_ms"]) < wall
    assert got["engine_proc_cpu_ms"] > 0.0


# --- the benchmark's own harness on a call made of blocks (tests/test_early_begin.py) ----

# the child's job is 16 lanes, so that the tiny twin's 24-lane commits are a block begun early and
# eight lanes at verify(); it leaves with 3 if no block was begun early
ENGAGED = """
import atexit, os
from tendermint_tpu.crypto import batch
from tendermint_tpu.ops import ed25519_batch
ed25519_batch.job_lanes = lambda: 16
begun_early, begin_on_device = [], batch.begin_on_device
def counting(key_type, lanes, begin_batch, early=False):
    begun_early.extend([lanes] * early)
    return begin_on_device(key_type, lanes, begin_batch, early)
batch.begin_on_device = counting
atexit.register(lambda: set(begun_early) == {16} or os._exit(3))
"""


def test_the_benchmarks_harness_reads_a_call_made_of_blocks():
    """``chipbench.run``, traced, on the call-path twin with the early
    begin engaged in every timed call: ``correct``, nothing ``failed``
    (the lanes dispatched and collected are the lanes sent), every
    per-layer metric of the cell a number, and the call's three parts
    each part of it."""
    bench = callpath.BENCH
    value = sound(
        *rehearse_cell(bench, "tiny-hub-warm", 2**31 + 44, 1, prelude=ENGAGED), (), bench, "tiny-hub-warm"
    )
    parts = [value(stem, moves="commit_p50_ms") for stem in ("pre_dispatch_ms", "chain_ms", "post_collect_ms")]
    assert all(p > 0 for p in parts)
    assert value("device_chain_gap_ms", moves="commit_p50_ms") >= 0


# --- ``light1k-chain``'s twin: the program against the reference, end to end -------------

LIGHT_COMPARED = ("verdict_cache_hits_in_window", "compilations_in_window", "timed_calls_refused",
                  "calls_with_a_wrong_walk", "fault_calls_with_a_wrong_verdict",
                  "lanes_where_reference_disagrees")


def test_tiny_twin_of_light1k_chain_rehearses_on_the_cpu():
    """The traced rehearsal is the comparison the chip run makes at full
    size: every timed call's block and store against the reference's
    walk, the four faults, the sampled lanes; and lanes dispatched =
    the reference's distinct checked signatures (``failed`` 0)."""
    bench, cell = light.BENCH, light.CELL
    value = sound(*rehearse_cell(bench, cell, light.SEED, 1, timeout=420), LIGHT_COMPARED, bench, cell)
    # pivots' keys are met twice at most and get no table, and neither does the anchor of the client
    # restarted where the cycle starts over: the window builds none and no lane finds one
    assert value("resident_hit_share") == 0.0 and value("table_build_ms") == 0.0
    assert value("light_unnamed_ms") < value("light_host_ms")


@pytest.mark.parametrize(
    "brk,over",
    [
        # one lane's verdict inverted where the engine returns it: a
        # timed call is refused, the walks after it start further back
        # (and may meet a kernel shape the warm-up did not)
        ("flip_verdict", ["compilations_in_window", "timed_calls_refused", "calls_with_a_wrong_walk",
                          "fault_calls_with_a_wrong_verdict", "lanes_where_reference_disagrees"]),
        # the engine's s < L check off: the trusting lane's s + L verifies
        ("no_canonical_s", ["fault_calls_with_a_wrong_verdict", "lanes_where_reference_disagrees"]),
    ],
)
def test_tiny_twin_of_light1k_chain_broken_on_purpose_comes_out_not_correct(brk, over):
    """The controls (``breaks.py``) have to show in the cell's own comparisons, not in the harness's two."""
    out, said = rehearse_cell(light.BENCH, light.CELL, light.SEED, 0, "--break", brk, timeout=420)
    assert out["correct"] is False
    got = over_limit(said)
    assert set(got) <= set(over) and got, got
    assert set(got) & {"fault_calls_with_a_wrong_verdict", "timed_calls_refused"}


# --- ``mixed10k``'s twin ------------------------------------------------------------------

MIXED_COMPARED = (
    "verdict_cache_hits_in_window", "compilations_in_window", "timed_commits_refused",
    "tampered_commits_not_blamed_on_their_lane", "lanes_where_reference_disagrees",
)


def test_tiny_twin_of_mixed10k_rehearses_on_the_cpu_traced():
    bench, cell = mixed.BENCH, mixed.CELL
    out, said = rehearse_cell(bench, cell, mixed.SEED, 1)
    value = sound(out, said, MIXED_COMPARED, bench, cell)  # every name printed
    assert value("resident_hit_share") == 50.0 and value("sr25519_lane_share") == 50.0
    assert value("mesh_lane_share") == 0.0 and value("device_hash_share") == 0.0
    assert value("host_lane_share") == pytest.approx(100.0 * 4 / 36)
    assert value("pad_lane_share") == 75.0  # two 64-lane kernels for 32 lanes
    assert 0 < value("merlin_ms") < value("prep_ms", **mixed.SR_PREP) < value("prep_ms", **mixed.ALL_PREP)
    assert 0 < value("kernel_ms", **mixed.SR_PROGRAMS) < value("kernel_ms", **mixed.ALL_PROGRAMS)
    assert 0 < value("sr25519_roofline") < 100
    assert value("host_lanes_ms") > 0 and value("device_chain_gap_ms") > 0
    assert "engine sr25519 kernel verify_sr lanes 64" in said


def test_tiny_twin_of_mixed10k_rehearses_on_the_cpu_untraced():
    out, said = rehearse_cell(mixed.BENCH, mixed.CELL, 4_000_000_007, 0)
    sound(out, said, MIXED_COMPARED)
    assert set(out["metrics"]) == {"commit_p50_ms", "setup_s"}


# planted after the warm-up calls, which have to stay sound
PLANT = """
import chipbench.generators.commits_mixed as g
warm = g.CommitsMixed.warm
def warm_then_break(self):
    warm(self)
%s
g.CommitsMixed.warm = warm_then_break
"""
FLIPPED_CHALLENGE = PLANT % """
    import tendermint_tpu.crypto.hashing as hashing
    sound = hashing.sr25519_challenges_mod_l
    def flipped(pubs, rs, msgs):
        out = sound(pubs, rs, msgs)
        out[len(out) // 2, 7] ^= 0x10
        return out
    hashing.sr25519_challenges_mod_l = flipped
"""
SECP_FORCED_TRUE = PLANT % """
    from tendermint_tpu.crypto.keys import Secp256k1PubKey
    # every secp256k1 verify is a verify_many call since PR 49, a lane alone too
    Secp256k1PubKey.verify_many = staticmethod(lambda keys, msgs, sigs: [True] * len(keys))
"""


@pytest.mark.parametrize(
    "prelude,over",
    [
        # one sr25519 lane's challenge off by a bit: that lane is refused
        (FLIPPED_CHALLENGE, ["timed_commits_refused", "lanes_where_reference_disagrees"]),
        # the host lanes answer true whatever they are asked
        (SECP_FORCED_TRUE, ["tampered_commits_not_blamed_on_their_lane"]),
    ],
    ids=["flipped_challenge_byte", "secp256k1_verdict_forced_true"],
)
def test_tiny_twin_of_mixed10k_with_a_planted_fault_comes_out_not_correct(prelude, over):
    out, said = rehearse_cell(mixed.BENCH, mixed.CELL, mixed.SEED, 0, prelude=prelude)
    assert out["correct"] is False and set(over) <= set(over_limit(said)), said[-1500:]


# --- ``mixed10k-x4``'s twin: the mixed committee on a mesh of four virtual devices ----------


@pytest.mark.limit(600)  # two sharded XLA kernels compile cold, then ~5 s a call on four devices that share the cores
def test_tiny_twin_of_mixed10k_x4_rehearses_on_four_virtual_devices_traced():
    """520 validators, 256 lanes of each type that batches: both
    sub-batches pass the mesh floor and go out sharded, a 64-lane slab a
    device, the host lanes between. Every definition the real cell is
    held to is printed but the one the XLA graph leaves silent, which
    the twin does not list (``tests/test_chipbench_mixed_x4.py``)."""
    bench, cell = mixed_x4.BENCH, mixed_x4.CELL
    out, said = rehearse_cell(
        bench, cell, mixed_x4.SEED, 1, timeout=580, seconds=mixed_x4.SECONDS, env=mixed_x4.ENV
    )
    assert "device_kind cpu, count 4" in said
    value = sound(out, said, MIXED_COMPARED, bench, cell)  # every name the twin lists printed
    assert value("mesh_lane_share") == 100.0 and value("slab_fill") == 100.0  # 4 x 64 lanes for 256
    assert value("resident_hit_share") == 50.0
    assert 0 < value("mesh_dispatch_ms") and value("h2d_bytes") > 0
    assert 0 < value("kernel_ms", **mixed_x4.SR_SHARD) < value("kernel_ms", patterns=mixed.ALL_PROGRAMS["patterns"])
    assert 0 < value("sr25519_shard_roofline") < 100
    assert "calls completed" in said and "(512 useful lanes each)" in said


# --- ``sync500-catchup``'s twin, and ``sync500-rotation``'s: this deployment with a set that changes

SYNC_BENCH = os.path.join(spec.HERE, "testdata", "tiny-sync-benchmark.json")
SYNC_CELL, SYNC_SEED = "tiny-sync-catchup", 2**31 + 26

BROKEN = [  # both cells are held to the same controls, over in the same comparisons
    # one lane's verdict inverted where the engine returns it
    ("flip_verdict", ["timed_blocks_refused", "windows_with_a_wrong_block_verdict", "lanes_where_reference_disagrees"]),
    # the engine's s < L check off: the included s + L lane verifies
    ("no_canonical_s", ["windows_with_a_wrong_block_verdict", "lanes_where_reference_disagrees"]),
]


def test_tiny_twin_of_sync500_catchup_rehearses_on_the_cpu():
    compared = ("verdict_cache_hits_in_window", "compilations_in_window", "timed_blocks_refused",
                "windows_with_a_wrong_block_verdict", "lanes_where_reference_disagrees")
    value = sound(*rehearse_cell(SYNC_BENCH, SYNC_CELL, SYNC_SEED, 1), compared, SYNC_BENCH, SYNC_CELL)
    assert value("resident_hit_share") == 100.0


@pytest.mark.parametrize("brk,over", BROKEN)
def test_tiny_twin_of_sync500_catchup_broken_on_purpose_comes_out_not_correct(brk, over):
    """The controls (``breaks.py``) have to show in the cell's own comparisons, not in the harness's two."""
    out, said = rehearse_cell(SYNC_BENCH, SYNC_CELL, SYNC_SEED, 0, "--break", brk)
    assert out["correct"] is False and over_limit(said) == over


def test_tiny_twin_of_sync500_rotation_rehearses_on_the_cpu():
    bench, cell = rotation.BENCH, rotation.CELL
    compared = ("verdict_cache_hits_in_window", "compilations_in_window", "timed_blocks_refused",
                "sets_registered_in_window", "windows_with_a_wrong_block_verdict", "lanes_where_reference_disagrees")
    value = sound(*rehearse_cell(bench, cell, rotation.SEED, 1), compared, bench, cell)
    # every call shows the mechanism: a table dropped with the retired set, the store dropped and sent
    # again, a newcomer's table built, the youngest keys' lanes on the legacy kernel
    assert value("tables_dropped") == 1.0 and 0 < value("legacy_lanes") <= 8 and 0 < value("resident_hit_share") < 100
    for stem in ("valset_hash_ms", "table_build_ms", "resident_upload_ms", "resident_drop_ms"):
        assert value(stem) > 0, stem
    assert value("valset_hash_ms") < value("note_set_ms")


@pytest.mark.parametrize("brk,over", BROKEN)
def test_tiny_twin_of_sync500_rotation_broken_on_purpose_comes_out_not_correct(brk, over):
    """The controls (``breaks.py``) have to show in the cell's own comparisons, not in the harness's two."""
    out, said = rehearse_cell(rotation.BENCH, rotation.CELL, rotation.SEED, 0, "--break", brk)
    assert out["correct"] is False and over_limit(said) == over


# --- ``syncmixed500-catchup``'s twin: a window over three key types, planned by key type -----------

SYNCMIXED_COMPARED = ("verdict_cache_hits_in_window", "compilations_in_window", "timed_blocks_refused",
                      "fault_window_blocks_with_a_wrong_verdict", "lanes_where_reference_disagrees")


def test_tiny_twin_of_syncmixed500_catchup_rehearses_on_the_cpu():
    """42 validators of three key types, windows of 4: each call two
    launches and one host call made while both sub-batches are in flight
    — a block alone would stay under the device's threshold, so the
    parent's road comes out ``failed`` here — and the check's fresh
    window with its five faults as the plain reference says."""
    bench, cell = syncmixed.BENCH, syncmixed.CELL
    out, said = rehearse_cell(bench, cell, syncmixed.SEED, 1)
    value = sound(out, said, SYNCMIXED_COMPARED, bench, cell)  # every name the twin lists printed
    assert value("device_launches") == 2.0
    lanes = int(said.split("lanes a call sent to the device")[0].rsplit(" ", 2)[-2])
    assert value("host_inflight_lanes") == lanes and "(%d useful lanes each)" % lanes in said
    assert 0 < value("group_lanes_ms") < value("pipeline_host_ms")
    assert value("kernel_ms") > 0 and value("h2d_bytes") > 0 and value("prep_ms") > 0


@pytest.mark.parametrize(
    "brk,over",
    [
        # one lane's verdict inverted where each engine returns its sub-batch
        ("flip_verdict", ["timed_blocks_refused", "fault_window_blocks_with_a_wrong_verdict", "lanes_where_reference_disagrees"]),
        # the ed25519 engine's s < L check off: the included ed25519 s + L lane verifies
        ("no_canonical_s", ["fault_window_blocks_with_a_wrong_verdict", "lanes_where_reference_disagrees"]),
    ],
)
def test_tiny_twin_of_syncmixed500_catchup_broken_on_purpose_comes_out_not_correct(brk, over):
    """The controls (``breaks.py``) have to show in the cell's own comparisons, not in the harness's two."""
    out, said = rehearse_cell(syncmixed.BENCH, syncmixed.CELL, syncmixed.SEED, 0, "--break", brk)
    assert out["correct"] is False and over_limit(said) == over

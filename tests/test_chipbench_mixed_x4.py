"""The benchmark's part of the ``mixed10k-x4`` deployment (PR 48),
without a chip: the configuration held to ``mixed10k``'s, the cell's
files and definitions, and the readers of what the cell brought on
hand-made evidence. The cell's tiny twin (520 validators on four
virtual devices) is rehearsed end to end in
``tests/test_chipbench_rehearsals.py``; the commit itself, on the
forced CPU mesh, in ``tests/test_mesh_mixed.py``."""

from __future__ import annotations

import os

import pytest

from chipbench import opcount_sr25519, selftest, spec
from chipbench.run import Evidence
from tests.helpers import REAL_BENCH, definitions, metric, read, span, stem_of
from tests.test_chipbench_mixed import as_counted

BENCH = os.path.join(spec.HERE, "testdata", "tiny-mixed-x4-twin.json")
CELL = "tiny-mixed-x4"
SEED = 2**31 + 48
REAL = (REAL_BENCH, "mixed10k-x4")
# four virtual devices, as selftest.py gives ``tiny-big-x4``; the suite pins the mesh to one device
# (conftest.py), which a child would inherit
ENV = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4", "TENDERMINT_TPU_MESH": "all"}
# a call is ~5 s on four virtual devices that share this host's cores, and ``run.py`` reads a window
# of three calls or more, the last of them profiled
SECONDS = 12

SR_SHARD = {"line": "modules", "patterns": ["jit_run_shard_sr25519*"]}
SPAN, TRACE = ("program_span", "commit_p50_ms"), ("device_trace", "commit_p50_ms")
# what the cell brought, as definitions (``DEFINITION_KEYS``), under whatever names
OWN = [
    ["trace_kernel_time", SR_SHARD, "Kernels", "ms", "lower", *TRACE],
    ["roofline_sr25519", SR_SHARD, "Kernels", "%", "higher", *TRACE],
    ["span_arg_share", {"span": "dispatch_chunk", "arg": "lanes", "of_span": "mesh_dispatch", "of_arg": "lanes"},
     "Mesh", "%", "higher", *SPAN],
    ["span_time_per_call", {"spans": ["mesh_dispatch"]}, "Mesh", "ms", "lower", *SPAN],
    ["setup_sharded_first_calls", {}, "Kernels", "s", "lower", "program_span", "setup_s"],
]
# the one definition of them that the XLA graph on a CPU mesh leaves silent, and the twin's file
# therefore leaves out: its sharded kernels' first calls record no ``kernel_compile`` span
# (``first_call_s``'s file says so of four chips); the reader is held to hand-made spans below
SILENT_ON_THE_XLA_GRAPH = "shard_first_call_s"


# --- the files -----------------------------------------------------------------------------


def test_the_configuration_is_mixed10k_in_every_key_but_five():
    real = spec.Spec(REAL_BENCH)
    config, control = real.config("mixed10k-x4"), real.config("mixed10k")
    differ = {"name", "source", "chips", "layout", "reduced"}
    assert set(config) == set(control)
    for key in control:
        assert (config[key] != control[key]) == (key in differ), key
    assert list(config) == list(control)  # and in the same order
    assert config["chips"] == 4 and list(config["reduced"]) == ["chips"]
    assert "pmapped over v5e-8" in config["source"] and "config 5" in config["source"]
    assert config["guarantees"] == control["guarantees"] and config["env"] == real.config("big10k-x4")["env"]


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    assert len(real.doc["per_layer"]) <= 128 and len(real.doc["workloads"]) <= 24
    cell = real.cell("mixed10k-x4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mixed10k-x4", "warm-mixed-commits", 4)
    assert len(cell["why"]) <= 200
    entry = [c for c in real.doc["configs"] if c["name"] == "mixed10k-x4"][0]
    config = real.config("mixed10k-x4")
    assert entry["reduced"] == list(config["reduced"]) == ["chips"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert [m["name"] for m in real.metrics_for("end_to_end", "mixed10k-x4")] == ["commit_p50_ms", "setup_s"]
    # at most half of the cells, rounded down, may ask for four chips
    four = [w["name"] for w in real.doc["workloads"] if w["chips"] == 4]
    assert four == ["big10k-x4", "mixed10k-x4"] and len(four) <= len(real.doc["workloads"]) // 2
    # the traffic file is mixed10k's, and both stand where PR 48 appended them: later ones after them
    assert real.cell("mixed10k")["traffic"] == cell["traffic"]
    assert real.doc["workloads"][8] is cell and real.doc["configs"][7] is entry


def test_the_cell_reports_what_it_brought_and_what_every_commit_cell_on_a_mesh_reports():
    """Its five definitions, and every definition ``big10k-x4`` reports
    from an entry that lists no cells (what a commit cell gets for
    nothing: ``mesh_lane_share`` among them)."""
    real = spec.Spec(REAL_BENCH)
    unlisted = {m["name"] for m in real.doc["per_layer"] if "workloads" not in m and m["moves"] == "commit_p50_ms"}
    mine = {m["name"] for m in real.metrics_for("per_layer", "mixed10k-x4")}
    assert unlisted and unlisted <= mine
    assert unlisted <= {m["name"] for m in real.metrics_for("per_layer", "big10k-x4")}
    assert metric(*REAL, "mesh_lane_share") in unlisted
    shared = definitions(REAL_BENCH, "big10k-x4", {stem_of(n) for n in unlisted})
    assert sum(shared.values()) == len(unlisted)  # no listed entry of big10k-x4 under one of these stems
    assert definitions(*REAL) == as_counted(OWN) + shared


@pytest.mark.parametrize("cell", [w["name"] for w in spec.Spec(REAL_BENCH).doc["workloads"] if w["name"] != "mixed10k-x4"])
def test_no_other_cell_reports_what_is_new_with_mixed10k_x4(cell):
    """Definitions, never copies: none of the five is another entry's."""
    assert not definitions(REAL_BENCH, cell) & as_counted(OWN), cell


def test_the_tiny_twin_lists_every_entry_the_real_cell_is_held_to_but_the_silent_one():
    real, tiny = spec.Spec(REAL_BENCH), spec.Spec(BENCH)
    want = [m["name"] for m in real.metrics_for("per_layer", "mixed10k-x4")]
    assert metric(*REAL, "shard_first_call_s") == SILENT_ON_THE_XLA_GRAPH and SILENT_ON_THE_XLA_GRAPH in want
    want.remove(SILENT_ON_THE_XLA_GRAPH)
    assert [m["name"] for m in tiny.metrics_for("per_layer", CELL)] == want
    config = tiny.config("mixed-x4")
    from tendermint_tpu.parallel import mesh

    assert min(config["key_types"]["ed25519"], config["key_types"]["sr25519"]) >= mesh.MIN_MESH_LANES
    assert config["chips"] == tiny.cell(CELL)["chips"] == 4


# --- the readers of what the cell brought, on hand-made evidence -------------------------------


def two_commits(devices=4, slab=4096, useful=4950):
    """Two timed calls (the second profiled) of a commit whose two
    sub-batches each go out as one sharded chunk of ``slab`` lanes a
    device; a device trace of the profiled call with both shard programs
    on every device; set-up's two sharded first calls."""
    ED, SR = {"engine": "ed25519", "kind": "resident"}, {"engine": "sr25519", "kind": "sr25519"}
    sent = slab * devices

    def call(at):
        return [
            span("dispatch_chunk", at + 100, 5000, lanes=useful, **ED),
            span("mesh_dispatch", at + 200, 4800, lanes=sent, devices=devices, impl="pallas", **ED),
            span("dispatch_chunk", at + 9000, 6000, lanes=useful, **SR),
            span("mesh_dispatch", at + 9100, 5600, lanes=sent, devices=devices, impl="pallas", **SR),
            span("host_lanes", at + 16000, 50000, key_type="secp256k1", lanes=100, device_lanes_inflight=2 * useful),
        ]

    ev = Evidence()
    ev.calls = [{"start_ns": 0, "end_ns": 120_000_000}]
    ev.spans = call(0)
    ev.profiled_calls = [{"start_ns": 200_000_000, "end_ns": 320_000_000}]
    ev.profiled_spans = call(200_000)
    ev.setup_spans = [
        span("kernel_compile", 0, 30e6, engine="pallas", kernel="verify_resident", lanes=slab, devices=devices, stored="miss"),
        span("kernel_compile", 40e6, 0.25e6, engine="pallas", kernel="verify_sr", lanes=slab, devices=devices, stored="hit"),
        span("kernel_compile", 50e6, 9e6, engine="pallas", kernel="verify_sr", lanes=1024),  # one device's: not a mesh's
    ]
    base = 1_000_000_000.0
    ev.trace = {
        "anchors": [[base, 120_000_000.0]],
        "devices": {
            "/device:TPU:%d" % d: {
                "ops": [],
                "modules": [
                    ["jit_run_shard(11)", base + 30e6, 15e6],
                    ["jit_run_shard_sr25519(12)", base + 50e6, 16e6],
                    ["jit_run_sr25519(13)", base + 70e6, 1e6],  # a one-chip program: another name
                ],
            }
            for d in range(devices)
        },
    }
    ev.peak = spec.load_json(os.path.join(spec.HERE, "peaks.json"))["TPU v5 lite"]
    return ev


def test_the_new_metrics_on_hand_made_evidence():
    ev = two_commits()
    assert read(ev, *REAL, "slab_fill") == pytest.approx(100.0 * 9900 / 32768)  # the issue's 30.2%
    assert read(ev, *REAL, "mesh_dispatch_ms") == pytest.approx(4.8 + 5.6)
    assert read(ev, *REAL, "mesh_lane_share") == 100.0
    # one device's share of the sr25519 shard program; the ed25519 one and a one-chip program aside
    assert read(ev, *REAL, "kernel_ms", **SR_SHARD) == pytest.approx(16.0)
    assert read(ev, *REAL, "kernel_ms", patterns=["jit_run*", "jit__lambda*"]) == pytest.approx(32.0)
    # both sides summed over the devices: the one-chip kernel's work for the useful lanes over 4 x 16 ms
    least = opcount_sr25519.least_seconds(4950, ev.peak)["seconds"]
    got = read(ev, *REAL, "sr25519_shard_roofline")
    assert got == pytest.approx(100.0 * least / (4 * 0.016)) and 0 < got < 100
    assert any("sr25519 roofline" in n for n in ev.notes)


def test_the_sharded_first_calls_are_summed_and_say_what_the_store_did():
    ev = two_commits()
    assert read(ev, *REAL, "shard_first_call_s") == pytest.approx(30.25)  # not the one-device kernel's 9 s
    said = [n for n in ev.notes if n.startswith("sharded first call")]
    assert len(said) == 2 and "verify_resident" in said[0] and "stored miss" in said[0]
    assert "verify_sr" in said[1] and "stored hit" in said[1] and "4096 lanes a device over 4 devices" in said[1]


def test_a_program_without_the_names_or_the_spans_gives_nothing_and_does_not_raise():
    """The parent of PR 48 under the new files: both sharded programs are
    ``jit_run``; a one-chip program records no ``mesh_dispatch``."""
    ev = two_commits()
    for dev in ev.trace["devices"].values():
        dev["modules"] = [["jit_run(%d)" % i, m[1], m[2]] for i, m in enumerate(dev["modules"])]
    assert read(ev, *REAL, "kernel_ms", **SR_SHARD) is None
    assert read(ev, *REAL, "sr25519_shard_roofline") is None
    assert read(ev, *REAL, "kernel_ms", patterns=["jit_run*", "jit__lambda*"]) == pytest.approx(32.0)
    ev.spans = [s for s in ev.spans if s["name"] != "mesh_dispatch"]
    ev.setup_spans = [s for s in ev.setup_spans if "devices" not in s["args"]]
    assert read(ev, *REAL, "slab_fill") is None
    assert read(ev, *REAL, "shard_first_call_s") is None
    assert read(ev, *REAL, "mesh_dispatch_ms") == 0.0
    ev.trace = None
    assert read(ev, *REAL, "kernel_ms", **SR_SHARD) is None and read(ev, *REAL, "sr25519_shard_roofline") is None

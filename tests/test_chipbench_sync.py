"""The benchmark's part of the ``sync500`` deployment, without a chip:
the plain light reference on hand-made blocks, the Pipeline metrics'
files reduced on hand-made spans and ``BENCHMARK.json`` against its
files. The cell's tiny twin is rehearsed end to end in
``tests/test_chipbench_rehearsals.py``.
"""

from __future__ import annotations

import pytest

from chipbench import reference_light, selftest, spec, workload
from tests.helpers import REAL_BENCH, Evidence, read, span

ABSENT, COMMIT, NIL = 1, 2, 3
REAL = (REAL_BENCH, "sync500-catchup")  # the cell a hand-made reading is named through


def block(n, flags, bad=(), powers=None):
    """(validators, signatures) signed by seeded keys; ``flags[i]`` is
    validator i's; signatures at the indices in ``bad`` are tampered."""
    signers = workload.make_signers(7, "ref-light", n)
    validators = [(s.pub, (powers or [10] * n)[i]) for i, s in enumerate(signers)]
    signatures = []
    for i, (s, flag) in enumerate(zip(signers, flags)):
        if flag == ABSENT:
            signatures.append((ABSENT, b"", b""))
            continue
        msg = b"vote %d flag %d" % (i, flag)
        sig = s.sign(msg)
        signatures.append((flag, msg, workload.tamper_signature(sig, "s") if i in bad else sig))
    return validators, signatures


@pytest.mark.parametrize(
    "flags,bad,powers,want",
    [
        # 6 equal votes: needed 40, the fifth passes it
        ([COMMIT] * 6, (), None, reference_light.OK),
        # the sixth is past the early exit: never looked at
        ([COMMIT] * 6, (5,), None, reference_light.OK),
        # an included one names its index in the commit
        ([COMMIT] * 6, (4,), None, ("wrong signature", 4)),
        # ... which an absent and a nil vote before it move off its lane
        ([ABSENT, COMMIT, NIL, COMMIT, COMMIT, COMMIT, COMMIT, COMMIT], (5,), None, ("wrong signature", 5)),
        # the first of two
        ([COMMIT] * 6, (1, 3), None, ("wrong signature", 1)),
        # exactly 2/3 is not more than 2/3, bad signature or not
        ([COMMIT] * 4 + [NIL, ABSENT], (), None, reference_light.INSUFFICIENT),
        ([COMMIT] * 4 + [NIL, ABSENT], (0,), None, reference_light.INSUFFICIENT),
        # unequal powers: 50 of 60 pass 2/3 at the first vote
        ([COMMIT] * 3, (1, 2), [50, 5, 5], reference_light.OK),
    ],
)
def test_reference_light_verify_block(flags, bad, powers, want):
    assert reference_light.verify_block(*block(len(flags), flags, bad, powers)) == want


def test_reference_light_blocks_do_not_look_at_their_neighbours():
    good = block(6, [COMMIT] * 6)
    bad = block(6, [COMMIT] * 6, bad=(2,))
    short = block(6, [COMMIT] * 3 + [ABSENT] * 3)
    assert reference_light.verify_window([good, bad, short, good]) == [
        reference_light.OK, ("wrong signature", 2), reference_light.INSUFFICIENT, reference_light.OK,
    ]


def test_benchmark_files_agree():
    selftest.test_files()
    real = spec.Spec(REAL_BENCH)
    cell = real.cell("sync500-catchup")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sync500", "catchup-windows", 1)
    config = real.config("sync500")
    assert config["validators"] == 500 and config["verify_window"] == 16
    assert list(config["reduced"]) == ["blocks"]
    quorum = config["validators"] * 2 // 3 + 1
    assert config["lanes_per_call"] == config["verify_window"] * quorum == 5344
    from chipbench.generators import cycle_length

    windows = cycle_length(real.traffic("catchup-windows"), 5344, 65536)
    assert windows >= 14 and config["blocks"] == windows * 16
    assert [m["name"] for m in real.metrics_for("end_to_end", "sync500-catchup")] == ["sigs_per_s", "setup_s"]


def one_window():
    """One call: verify_commits_pipelined 0..1000 holding build_lanes
    10..500 (phases 300 + 20; two note_validator_set spans of 40 inside
    it), verify_batch 510..900, merge_verdicts 910..950."""
    return [
        span("verify_commits_pipelined", 0, 1000, tasks=2, lanes=8),
        span("build_lanes", 10, 490, lanes=8, sign_bytes_us=300.0, sign_bytes_n=8, basic_checks_us=20.0, basic_checks_n=2),
        span("note_validator_set", 20, 40), span("note_validator_set", 260, 40),
        span("verify_batch", 510, 390), span("merge_verdicts", 910, 40),
    ]


# gaps 10 + 10 + 10 + 50 = 80 outside the children; the loop's 490 less its phases 320 and the 80 of the spans inside it = 90
PIPELINE = [("pipeline_host_ms", 0.610), ("sign_bytes_ms", 0.300), ("note_set_ms", 0.080), ("pipeline_unnamed_ms", 0.170)]


@pytest.mark.parametrize("stem,want", PIPELINE)
def test_pipeline_metric_on_nested_spans(stem, want):
    assert read(Evidence(one_window()), *REAL, stem) == pytest.approx(want)


def test_pipeline_metrics_add_up_on_nested_spans():
    got = {stem: read(Evidence(one_window()), *REAL, stem) for stem, _ in PIPELINE}
    named = 0.300 + 0.020 + 0.080 + 0.040  # sign-bytes, basic checks, note, merge
    assert got["pipeline_unnamed_ms"] + named == pytest.approx(got["pipeline_host_ms"])
    # the parent's program records none of these spans: nothing to read
    ev = Evidence([span("verify_batch", 510, 390)])
    for stem in ("pipeline_host_ms", "sign_bytes_ms", "pipeline_unnamed_ms"):
        assert read(ev, *REAL, stem) is None

#!/usr/bin/env bash
# Repo CI gate: byte-compile, static analysis, sanitizer-enabled
# concurrency tests, metrics audit, tier-1 tests.
#
# The tier-1 line is the ROADMAP.md "Tier-1 verify" command verbatim —
# keep the two in sync. DOTS_PASSED is the per-test pass count the
# driver compares against the seed.
set -u

rc_total=0

echo "== compileall =="
python -m compileall -q tendermint_tpu tests scripts bench bench.py || rc_total=1

echo "== analysis (tpulint) =="
# project-specific static analysis: lock discipline, JAX purity,
# wire compat, hygiene, metrics. New findings (not in the committed
# baseline) fail the gate.
python -m scripts.analysis || rc_total=1

echo "== tpuflow: taint analysis + deterministic wire fuzz =="
# The TPT family rides the tpulint run above against the committed
# baseline; this stage additionally requires the taint family to be
# clean WITHOUT the baseline — no TPT finding is ever grandfathered,
# every wire-tainted bound must carry a real guard (or an audited
# `# tpuflow: sanitized=` annotation).
python -m scripts.analysis --no-baseline --enable taint || {
    echo "tpuflow: unbaselined TPT findings (see above)" >&2
    rc_total=1
}
# The runtime half: 10 fixed seeds of structured mutations over the
# checked-in corpus, all four decode surfaces. Any hang, uncaught
# struct.error/IndexError/MemoryError, or silent wrong decode fails
# the stage; the failing seed replays byte-identically.
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 60 env JAX_PLATFORMS=cpu \
        python tests/fuzz_wire.py --seed $seed --smoke || {
        echo "tpuflow fuzz: FAILED under seed $seed — replay with" \
             "python tests/fuzz_wire.py --seed $seed" >&2
        rc_total=1
    }
done

echo "== sanitizer-enabled concurrency tests =="
# the lock-order sanitizer records the acquisition-order graph while
# the concurrency-heavy modules run their tests; an AB/BA inversion
# prints a LOCK-ORDER CYCLE marker even when no run deadlocks.
rm -f /tmp/_sanitize.log
timeout -k 10 600 env TENDERMINT_TPU_SANITIZE=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_scheduler.py tests/test_verifyd.py \
    tests/test_device_policy.py -q -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_sanitize.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "LOCK-ORDER CYCLE" /tmp/_sanitize.log; then
    echo "sanitizer: lock-order cycle detected (potential deadlock)" >&2
    rc_total=1
fi
# IO-UNDER-LOCK lines in the log are report-only: the grpc client
# deliberately holds its connection mutex across a unary call.

echo "== check_metrics =="
python scripts/check_metrics.py || rc_total=1

echo "== mesh engine tests (virtual 8-device mesh) =="
# The sharded verify engine (parallel/mesh + parallel/sharding) under
# the same virtual 8-mesh tests/conftest.py forces; run as its own
# stage so a mesh regression is visible even when tier-1 passes.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/test_mesh.py tests/test_parallel.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc_total=1

echo "== bench smoke (multichip scaling section) =="
# The multichip section must produce its scaling curve on the virtual
# mesh and land status=ok in both the merged and partial JSON. Tiny
# lanes/rounds keep the stage inside the wall budget; the DEFAULT
# heartbeat window stays (sharded compiles legitimately exceed 5s).
rm -rf /tmp/_bench_mesh && mkdir -p /tmp/_bench_mesh
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=multichip BENCH_MULTICHIP_LANES=512 \
    BENCH_MULTICHIP_DEVICES=1,2 BENCH_MULTICHIP_ROUNDS=1 \
    BENCH_SECTION_TIMEOUT=360 BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_mesh/partial.json \
    python bench.py > /tmp/_bench_mesh/out.json 2>/tmp/_bench_mesh/err.log
if [ "$?" -ne 0 ]; then
    echo "bench multichip smoke: non-zero rc" >&2
    tail -5 /tmp/_bench_mesh/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_mesh/out.json"))
assert merged["sections"]["multichip"]["status"] == "ok", merged["sections"]
mc = merged["multichip"]
assert mc["ok"] is True, mc
assert set(mc["sigs_per_s"]) == {"1", "2"}, mc
partial = json.load(open("/tmp/_bench_mesh/partial.json"))
assert partial["sections"]["multichip"]["status"] == "ok", partial["sections"]
print("bench multichip smoke ok: %s" % mc["sigs_per_s"])
EOF

echo "== bench smoke (section runner vs a hanging section) =="
# The per-section isolation contract: one deliberately-hanging
# section must NOT zero the round. Tiny no-jax sections keep this
# stage fast; the injected hang must die by heartbeat watchdog (well
# under the 60s wall budget), the run must not end in a whole-run
# rc=124, and the partial JSON must carry the healthy section's number.
rm -rf /tmp/_bench_smoke && mkdir -p /tmp/_bench_smoke
timeout -k 10 120 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=host_ref,_chaos BENCH_CHAOS=hang \
    BENCH_HEARTBEAT_TIMEOUT=5 BENCH_SECTION_TIMEOUT=60 \
    BENCH_SECTION_ATTEMPTS=1 BENCH_HOST_REF_SIGS=4 \
    BENCH_PARTIAL=/tmp/_bench_smoke/partial.json \
    TENDERMINT_TPU_FLIGHTREC_DIR=/tmp/_bench_smoke/flightrec \
    python bench.py > /tmp/_bench_smoke/out.json 2>/tmp/_bench_smoke/err.log
bench_rc=$?
if [ "$bench_rc" -eq 124 ]; then
    echo "bench smoke: whole-run timeout (rc=124) — section isolation broken" >&2
    rc_total=1
elif [ "$bench_rc" -ne 3 ]; then
    # 3 = partial evidence (healthy sections ok, the injected hang honest)
    echo "bench smoke: expected partial-evidence rc=3, got rc=$bench_rc" >&2
    tail -5 /tmp/_bench_smoke/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_smoke/out.json"))
secs = merged["sections"]
assert secs["host_ref"]["status"] == "ok", secs
assert merged["host_ref"]["sigs_per_s"] > 0, merged
assert secs["_chaos"]["status"] == "timeout", secs
assert "heartbeat silence" in (secs["_chaos"]["note"] or ""), secs
# killed by the heartbeat watchdog inside its window, not the wall budget
assert secs["_chaos"]["duration_s"] < 30, secs
partial = json.load(open("/tmp/_bench_smoke/partial.json"))  # schema-valid
# flight recorder (ISSUE 15): the watchdog kill must leave a parseable
# post-mortem dump referenced from the partial JSON — the child dies by
# SIGKILL, so the PARENT's ring (which emits the kill instant) is the
# dump under test
dumps = [
    d for d in partial.get("flightrec_dumps", [])
    if d.get("reason") == "watchdog_kill"
]
assert dumps, partial.get("flightrec_dumps")
rec = json.load(open(dumps[0]["path"]))
assert rec["schema"].startswith("tendermint-tpu-flightrec/"), rec["schema"]
assert any(
    r["name"] == "bench_watchdog_kill" for r in rec["records"]
), [r["name"] for r in rec["records"]][:20]
assert merged.get("flightrec_dumps") == partial["flightrec_dumps"], (
    "merged doc lost the dump references"
)
print(
    "bench smoke ok: hang killed by watchdog in %.1fs, healthy section "
    "kept, flight recorder dumped %d records"
    % (secs["_chaos"]["duration_s"], len(rec["records"]))
)
EOF

echo "== kernel campaign (resident tables + device hash + autotuner) =="
# ISSUE 8 stage: the device-resident table store, fused SHA-512
# challenge hashing, and the field-mul autotuner forced ON on the CPU
# backend (their auto modes keep CPU off, so tier-1 alone would never
# execute these paths), plus the hashing parity battery. Both forced
# TENDERMINT_TPU_FIELD_MUL values pin verify parity under each impl.
rm -rf /tmp/_kcamp && mkdir -p /tmp/_kcamp
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    TENDERMINT_TPU_RESIDENT=on TENDERMINT_TPU_DEVICE_HASH=1 \
    TENDERMINT_TPU_AUTOTUNE=on \
    TENDERMINT_TPU_AUTOTUNE_CACHE=/tmp/_kcamp/autotune.json \
    python -m pytest tests/test_resident.py tests/test_device_hash.py \
    tests/test_autotune.py -q -p no:cacheprovider -p no:xdist \
    -p no:randomly || rc_total=1
for mul in vpu mxu; do
    timeout -k 10 300 env JAX_PLATFORMS=cpu TENDERMINT_TPU_FIELD_MUL=$mul \
        python -m pytest tests/test_ops_ed25519.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly || {
        echo "kernel campaign: parity failed under FIELD_MUL=$mul" >&2
        rc_total=1
    }
done

echo "== lightd serving tier (evloop suites + light_serve smoke) =="
# PR 9 stage: the selector event loop must keep both wire protocols
# byte-identical (grpc + verifyd regression suites and the evloop
# regressions proper), and a 200-client light_serve smoke on CPU must
# land status=ok with a nonzero warm-phase cache hit rate.
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_grpc.py tests/test_verifyd.py \
    tests/test_evloop.py tests/test_lightd.py -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly || rc_total=1
rm -rf /tmp/_bench_light && mkdir -p /tmp/_bench_light
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=light_serve BENCH_LIGHT_SERVE_CLIENTS=200 \
    BENCH_LIGHT_SERVE_HEIGHTS=24 BENCH_LIGHT_SERVE_REQUESTS=1000 \
    BENCH_SECTION_TIMEOUT=360 BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_light/partial.json \
    python bench.py > /tmp/_bench_light/out.json 2>/tmp/_bench_light/err.log
if [ "$?" -ne 0 ]; then
    echo "bench light_serve smoke: non-zero rc" >&2
    tail -5 /tmp/_bench_light/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_light/out.json"))
assert merged["sections"]["light_serve"]["status"] == "ok", merged["sections"]
ls = merged["light_serve"]
assert ls["errors"] == 0, ls
assert ls["cache_hit_rate"] > 0, ls
assert ls["warm_headers_per_s"] > 0, ls
print(
    "bench light_serve smoke ok: %s clients, %.0f headers/s warm, "
    "hit rate %.2f" % (ls["clients"], ls["warm_headers_per_s"],
                       ls["cache_hit_rate"])
)
EOF

echo "== verifyd chaos battery (sanitized) + tenant bench smoke =="
# ISSUE 11 stage: the serving-tier chaos battery (device faults
# mid-dispatch, torn frames, slow readers, tenant floods, kill/restart)
# runs with the lock-order sanitizer ON — the continuous-batching
# dispatch workers share the scheduler mutex with the accumulator, so
# an inversion here is exactly the regression this stage exists to
# catch. Then the verifyd_tenants bench section must show explicit
# sheds under flood and continuous batching no worse than the barrier
# path on victim p99 (observed ~0.96x; 1.25x margin absorbs CI noise).
rm -f /tmp/_chaos.log
timeout -k 10 600 env TENDERMINT_TPU_SANITIZE=1 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_verifyd_chaos.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_chaos.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "LOCK-ORDER CYCLE" /tmp/_chaos.log; then
    echo "verifyd chaos: lock-order cycle detected (potential deadlock)" >&2
    rc_total=1
fi
rm -rf /tmp/_bench_tenants && mkdir -p /tmp/_bench_tenants
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=verifyd_tenants BENCH_SECTION_TIMEOUT=240 \
    BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_tenants/partial.json \
    python bench.py > /tmp/_bench_tenants/out.json \
    2>/tmp/_bench_tenants/err.log
if [ "$?" -ne 0 ]; then
    echo "bench verifyd_tenants smoke: non-zero rc" >&2
    tail -5 /tmp/_bench_tenants/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_tenants/out.json"))
assert merged["sections"]["verifyd_tenants"]["status"] == "ok", \
    merged["sections"]
vt = merged["verifyd_tenants"]
cont, barrier = vt["continuous"], vt["barrier"]
# the flood tenant hit its budget and was shed EXPLICITLY (the barrier
# mode sheds too, but its count sits near zero at this load — the
# budget mechanism itself is mode-independent and chaos-tested)
assert cont["flood_sheds"] > 0, vt
assert cont["tenants"]["flood"]["sheds"] == cont["flood_sheds"], cont
# continuous batching actually pipelined (hand-offs only exist there)
assert cont["dispatch_handoffs"] > 0, cont
assert barrier["dispatch_handoffs"] == 0, barrier
# mixed-load victim p99: continuous must not lose to the barrier path
assert cont["victim_p99_ms"] <= barrier["victim_p99_ms"] * 1.25, vt
print(
    "bench verifyd_tenants smoke ok: victim p99 %.1fms continuous vs "
    "%.1fms barrier, flood sheds %d/%d"
    % (cont["victim_p99_ms"], barrier["victim_p99_ms"],
       cont["flood_sheds"], barrier["flood_sheds"])
)
EOF

echo "== tpusan: happens-before race detection =="
# PR 12 stage: vector-clock happens-before detection over the
# concurrent serving stack (scheduler hand-off, verifyd brownout/chaos,
# evloop lifecycle). Any DATA RACE marker is a gate failure — the
# report carries both access stacks and the lock sets held.
rm -f /tmp/_tpusan_hb.log
timeout -k 10 850 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_scheduler.py tests/test_verifyd_chaos.py \
    tests/test_evloop.py -q -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_tpusan_hb.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_hb.log; then
    echo "tpusan: data race detected (stacks above)" >&2
    rc_total=1
fi
if grep -q "LOCK-ORDER CYCLE" /tmp/_tpusan_hb.log; then
    echo "tpusan: lock-order cycle detected" >&2
    rc_total=1
fi

echo "== tpusan: deterministic schedule exploration (10 seeds) =="
# The continuous-batching scheduler under 10 seeded interleavings.
# Same seed -> same schedule, byte-stable report: a failure here
# reproduces exactly with TENDERMINT_TPU_SANITIZE=explore:<seed>.
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 180 env TENDERMINT_TPU_SANITIZE=explore:$seed \
        JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_scheduler.py::TestContinuousBatching" -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > /tmp/_tpusan_explore.log 2>&1 || {
        echo "tpusan explore: FAILED under seed $seed — replay with" \
             "TENDERMINT_TPU_SANITIZE=explore:$seed" >&2
        tail -20 /tmp/_tpusan_explore.log >&2
        rc_total=1
    }
done

echo "== shm ingress: sanitized slab-ring tests + seeded explore =="
# PR 13 stage (mirrors the PR 12 contract): the zero-copy slab-ring
# state machine runs under happens-before race detection — any DATA
# RACE or LOCK-ORDER CYCLE marker fails the gate — and then the ring
# state machine explores 10 seeded interleavings (acquire/fill/commit
# vs drain/retire/free is the exact cursor hand-off a bad schedule
# would tear).
rm -f /tmp/_tpusan_shm.log
timeout -k 10 850 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_verifyd_shm.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_tpusan_shm.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_shm.log; then
    echo "shm ingress: data race detected (stacks above)" >&2
    rc_total=1
fi
if grep -q "LOCK-ORDER CYCLE" /tmp/_tpusan_shm.log; then
    echo "shm ingress: lock-order cycle detected" >&2
    rc_total=1
fi
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 180 env TENDERMINT_TPU_SANITIZE=explore:$seed \
        JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_verifyd_shm.py::TestRingStateMachine" -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > /tmp/_tpusan_shm_explore.log 2>&1 || {
        echo "shm explore: FAILED under seed $seed — replay with" \
             "TENDERMINT_TPU_SANITIZE=explore:$seed" >&2
        tail -20 /tmp/_tpusan_shm_explore.log >&2
        rc_total=1
    }
done

echo "== bench smoke (verifyd_shm A/B) =="
# The zero-copy acceptance: at 8192 lanes the slab path must beat the
# TCP codec on p50 outright and report the codec bytes it skipped.
# The noop verifier is declared in the JSON (verify=noop) — the A/B
# isolates transport + codec cost, which is the claim under test.
rm -rf /tmp/_bench_shm && mkdir -p /tmp/_bench_shm
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=verifyd_shm BENCH_SHM_ROUNDS=8 \
    BENCH_SECTION_TIMEOUT=240 BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_shm/partial.json \
    python bench.py > /tmp/_bench_shm/out.json 2>/tmp/_bench_shm/err.log
if [ "$?" -ne 0 ]; then
    echo "bench verifyd_shm smoke: non-zero rc" >&2
    tail -5 /tmp/_bench_shm/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_shm/out.json"))
assert merged["sections"]["verifyd_shm"]["status"] == "ok", merged["sections"]
vs = merged["verifyd_shm"]
assert vs["verify"] == "noop", vs  # the knob is declared, not hidden
big = vs["sizes"]["8192"]
assert big["shm"]["transport"] == "shm", big
assert big["shm"]["p50_ms"] < big["tcp"]["p50_ms"], big
assert big["shm"]["codec_bytes_avoided"] > 0, big
assert vs["server"]["shm_torn_slabs"] == 0, vs["server"]
print(
    "bench verifyd_shm smoke ok: p50 %.2fms shm vs %.2fms tcp at 8192 "
    "lanes, %d codec bytes avoided"
    % (big["shm"]["p50_ms"], big["tcp"]["p50_ms"],
       big["shm"]["codec_bytes_avoided"])
)
EOF

echo "== flight recorder: sanitized ring tests + seeded explore =="
# ISSUE 15 stage: the always-on flight recorder records from every
# tracer span, metric increment, and fault hook concurrently — its
# byte-accounting ring runs under happens-before race detection, then
# the producer/reader/dumper hand-off explores 10 seeded
# interleavings (TestRingConcurrency is the designated target class).
rm -f /tmp/_tpusan_flightrec.log
timeout -k 10 300 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_flightrec.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_tpusan_flightrec.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_flightrec.log; then
    echo "flightrec: data race detected (stacks above)" >&2
    rc_total=1
fi
if grep -q "LOCK-ORDER CYCLE" /tmp/_tpusan_flightrec.log; then
    echo "flightrec: lock-order cycle detected" >&2
    rc_total=1
fi
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 180 env TENDERMINT_TPU_SANITIZE=explore:$seed \
        JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_flightrec.py::TestRingConcurrency" -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > /tmp/_tpusan_flightrec_explore.log 2>&1 || {
        echo "flightrec explore: FAILED under seed $seed — replay with" \
             "TENDERMINT_TPU_SANITIZE=explore:$seed" >&2
        tail -20 /tmp/_tpusan_flightrec_explore.log >&2
        rc_total=1
    }
done

echo "== adaptive serving: sanitized controller tests + seeded explore =="
# ISSUE 17 stage: the dyn-batch controller and per-tenant SLO budget
# machinery under happens-before race detection — the controller's
# observe_flush/limits sites run on dispatch workers while stats()
# snapshots from serving threads, so a missing lock here is a real
# race, not a theoretical one. Then the tenant-SLO suite (breach ->
# scoped shed -> hysteresis recovery, end to end) explores 10 seeded
# interleavings.
rm -f /tmp/_tpusan_adaptive.log
timeout -k 10 600 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_adaptive.py -q -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_tpusan_adaptive.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_adaptive.log; then
    echo "adaptive: data race detected (stacks above)" >&2
    rc_total=1
fi
if grep -q "LOCK-ORDER CYCLE" /tmp/_tpusan_adaptive.log; then
    echo "adaptive: lock-order cycle detected" >&2
    rc_total=1
fi
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 180 env TENDERMINT_TPU_SANITIZE=explore:$seed \
        JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_adaptive.py::TestTenantSlo" -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > /tmp/_tpusan_adaptive_explore.log 2>&1 || {
        echo "adaptive explore: FAILED under seed $seed — replay with" \
             "TENDERMINT_TPU_SANITIZE=explore:$seed" >&2
        tail -20 /tmp/_tpusan_adaptive_explore.log >&2
        rc_total=1
    }
done

echo "== bench smoke (slo_replay: adaptive holds budget at 2x static) =="
# The adaptive-serving acceptance on the checked-in diurnal trace: the
# static ladder is cut to ONE rung (SAT_STEPS=1 — the x1 run anchors
# the saturation point either way), never the trace itself: a trace
# shorter than the controller's ramp window would score cold-start
# and fail for the wrong reason. The section self-asserts p99-within-
# budget and served>=70%; the heredoc re-checks both from the JSON so
# a silently-weakened section assert still fails the gate.
rm -rf /tmp/_bench_slo && mkdir -p /tmp/_bench_slo
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=slo_replay BENCH_SLO_SAT_STEPS=1 \
    BENCH_SECTION_TIMEOUT=240 BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_slo/partial.json \
    python bench.py > /tmp/_bench_slo/out.json 2>/tmp/_bench_slo/err.log
if [ "$?" -ne 0 ]; then
    echo "bench slo_replay smoke: non-zero rc" >&2
    tail -5 /tmp/_bench_slo/err.log >&2
    rc_total=1
fi
python - <<'EOF' || rc_total=1
import json
merged = json.load(open("/tmp/_bench_slo/out.json"))
assert merged["sections"]["slo_replay"]["status"] == "ok", merged["sections"]
sr = merged["slo_replay"]
tip = sr["adaptive"]["tip"]
budget = sr["trace"]["tip_slo_ms"]
assert sr["adaptive"]["dyn_batch"] is True, sr["adaptive"]
assert tip["p99_ms"] is not None and tip["p99_ms"] <= budget, tip
assert tip["served"] >= 0.7 * max(1, tip["scored"]), tip
# the adaptive run records the scheduler knobs it actually converged
# to (ISSUE 17 satellite: resolved knobs in every artifact)
assert sr["adaptive"]["knobs"], sr["adaptive"]
assert "dyn_batch" in sr["adaptive"]["knobs"], sr["adaptive"]["knobs"]
print(
    "bench slo_replay smoke ok: adaptive tip p99 %.1fms <= %dms budget "
    "at x%g (2x static saturation), served %d/%d"
    % (tip["p99_ms"], budget, sr["adaptive_mult"], tip["served"],
       tip["scored"])
)
EOF

echo "== introspection: sanitized suites + sentinel + profiler overhead =="
# ISSUE 18 stage. (a) The device-byte accountant and profiler digests
# run under happens-before race detection — the ledger is written from
# resident-store refresh, shm register/unregister, and compile paths
# concurrently, so a missing lock is a real race. (b) The bench_diff
# sentinel's acceptance pair (synthetic fixtures under tests/fixtures):
# base -> regressed shows a headline collapse and MUST exit 4
# (regression); the identity diff MUST exit 0. (c) Profiler overhead: the host_ref throughput section
# with the profiler on must land within 5% of a profiler-off run, and
# the merged JSON must carry the profile fragment.
rm -f /tmp/_tpusan_introspect.log
timeout -k 10 600 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_introspect.py tests/test_bench_diff.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
    | tee /tmp/_tpusan_introspect.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_introspect.log; then
    echo "introspect: data race detected (stacks above)" >&2
    rc_total=1
fi
python -m scripts.bench_diff tests/fixtures/bench_diff_base.json \
    tests/fixtures/bench_diff_regressed.json \
    > /tmp/_bench_diff_accept.log 2>&1
if [ "$?" -ne 4 ]; then
    echo "bench_diff acceptance: base -> regressed must exit 4 (regression)" >&2
    rc_total=1
fi
python -m scripts.bench_diff tests/fixtures/bench_diff_regressed.json \
    tests/fixtures/bench_diff_regressed.json >/dev/null \
    || { echo "bench_diff acceptance: identity diff must exit 0" >&2; \
         rc_total=1; }
rm -rf /tmp/_bench_prof && mkdir -p /tmp/_bench_prof
for prof in on off; do
    timeout -k 10 180 env JAX_PLATFORMS=cpu TENDERMINT_TPU_PROFILE=$prof \
        BENCH_SECTIONS=host_ref BENCH_HOST_REF_SIGS=64 \
        BENCH_SECTION_TIMEOUT=150 BENCH_SECTION_ATTEMPTS=1 \
        BENCH_PARTIAL=/tmp/_bench_prof/partial_$prof.json \
        python bench.py > /tmp/_bench_prof/out_$prof.json \
        2>/tmp/_bench_prof/err_$prof.log || {
        echo "bench profiler smoke ($prof): non-zero rc" >&2
        tail -5 /tmp/_bench_prof/err_$prof.log >&2
        rc_total=1
    }
done
python - <<'EOF' || rc_total=1
import json
on = json.load(open("/tmp/_bench_prof/out_on.json"))
off = json.load(open("/tmp/_bench_prof/out_off.json"))
# the profile fragment rides in every merged doc; its enabled flag
# reflects the knob
assert on["profile"]["enabled"] is True, on.get("profile")
assert off["profile"]["enabled"] is False, off.get("profile")
t_on = on["host_ref"]["sigs_per_s"]
t_off = off["host_ref"]["sigs_per_s"]
overhead = (t_off - t_on) / t_off * 100.0
assert overhead <= 5.0, (
    "profiler overhead %.1f%% exceeds the 5%% budget "
    "(%.1f sigs/s on vs %.1f off)" % (overhead, t_on, t_off)
)
print(
    "profiler overhead ok: %.1f sigs/s on vs %.1f off (%.1f%%)"
    % (t_on, t_off, overhead)
)
EOF
# smoke diff against the checked-in CPU fingerprint: the generous
# tolerance absorbs hardware variance; what it still catches is an
# order-of-magnitude collapse or a section/metric falling out of the
# merged doc entirely (--strict-missing)
python -m scripts.bench_diff --tolerance 75 --strict-missing \
    BENCH_cpu_smoke_baseline.json /tmp/_bench_prof/out_on.json \
    || { echo "introspect: smoke diff vs checked-in fingerprint failed" >&2; \
         rc_total=1; }

echo "== verifyd federation: sanitized suites + seeded failover explore =="
# ISSUE 19 stage: the digest-routed shard federation. The routing and
# failover suites (plus the shard-kill chaos test) run under
# happens-before race detection — the FederationClient's membership
# state (_dead/_owner/route_epoch) is @instrument_attrs-instrumented,
# so a racy ladder walk surfaces as a DATA RACE marker, not a flake.
rm -f /tmp/_tpusan_fed.log
timeout -k 10 850 env TENDERMINT_TPU_SANITIZE=hb JAX_PLATFORMS=cpu \
    python -m pytest tests/test_federation.py tests/test_verifyd_chaos.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
    | tee /tmp/_tpusan_fed.log
[ "${PIPESTATUS[0]}" -ne 0 ] && rc_total=1
if grep -q "DATA RACE" /tmp/_tpusan_fed.log; then
    echo "federation: data race detected (stacks above)" >&2
    rc_total=1
fi
if grep -q "LOCK-ORDER CYCLE" /tmp/_tpusan_fed.log; then
    echo "federation: lock-order cycle detected" >&2
    rc_total=1
fi
# the failover ladder under 10 seeded interleavings: mark-dead vs
# revive vs concurrent group dispatch is the exact hand-off a bad
# schedule would tear (same seed -> same schedule, exact replay)
for seed in 0 1 2 3 4 5 6 7 8 9; do
    timeout -k 10 180 env TENDERMINT_TPU_SANITIZE=explore:$seed \
        JAX_PLATFORMS=cpu python -m pytest \
        "tests/test_federation.py::TestFailover" -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > /tmp/_tpusan_fed_explore.log 2>&1 || {
        echo "federation explore: FAILED under seed $seed — replay with" \
             "TENDERMINT_TPU_SANITIZE=explore:$seed" >&2
        tail -20 /tmp/_tpusan_fed_explore.log >&2
        rc_total=1
    }
done

echo "== bench smoke (verifyd_fleet, 2 shards) =="
# The federation acceptance, over the wire: 2 spawned shard processes
# must pin strictly disjoint resident-table slices (the section fails
# itself on any overlap or coverage gap), aggregate modeled sigs/s
# must scale >= 1.5x over one shard, and the mid-load SIGKILL round
# must finish with zero silent drops.
rm -rf /tmp/_bench_fleet && mkdir -p /tmp/_bench_fleet
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    BENCH_SECTIONS=verifyd_fleet BENCH_FLEET_MAX_SHARDS=2 \
    BENCH_FLEET_ROUNDS=4 \
    BENCH_SECTION_TIMEOUT=240 BENCH_SECTION_ATTEMPTS=1 \
    BENCH_PARTIAL=/tmp/_bench_fleet/partial.json \
    python bench.py > /tmp/_bench_fleet/out.json || {
    echo "bench verifyd_fleet smoke: non-zero rc" >&2
    rc_total=1
}
python - <<'EOF' || rc_total=1
import json
doc = json.load(open("/tmp/_bench_fleet/out.json"))
sec = doc["sections"]["verifyd_fleet"]
assert sec["status"] == "ok", "verifyd_fleet section: %s" % sec
fleet = doc["verifyd_fleet"]
assert fleet["verify"] == "modeled", fleet  # honesty declared
two = fleet["shards"]["2"]
assert two["disjoint"] is True, two
pinned = two["pinned_keys"]
assert len(pinned) == 2 and all(n > 0 for n in pinned.values()), pinned
assert sum(pinned.values()) == fleet["committees"] * 4, pinned
assert two["max_shard_bytes_vs_single"] < 1.0, two
assert fleet["scaling_2x_over_1x"] >= 1.5, fleet["scaling_2x_over_1x"]
fo = fleet["failover"]
assert fo["zero_silent_drops"] is True, fo
assert fo["unexplained_false_lanes"] == 0, fo
print(
    "verifyd_fleet smoke ok: %.2fx scaling, pinned split %s, "
    "%d lanes rerouted on shard kill"
    % (fleet["scaling_2x_over_1x"], pinned, fo["rerouted_lanes"])
)
EOF

echo "== tier-1 pytest =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
[ "$rc" -ne 0 ] && rc_total=1

exit $rc_total

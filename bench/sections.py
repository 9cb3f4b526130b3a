"""The bench section registry: every measurement the harness knows how
to run, each as an isolated unit (ISSUE 6 tentpole).

A section body takes a heartbeat callable and returns the *fragment*
of the headline BENCH JSON it contributes (bench/results.py merges the
fragments in registry order). Bodies run inside a dedicated child
process (bench/child.py) under the parent watchdog, so they must beat
at every unit of real progress — a body that goes silent longer than
the heartbeat window is presumed wedged and killed.

Degradation ladder: ``degrade`` lists the env knobs the retry ladder
halves on each re-attempt (floor included), so a section that died at
full size gets progressively cheaper before the runner gives up
(bench/runner.py ladder_env).

The ``_chaos`` section is the fault-injection hook for the chaos tests
and the CI smoke stage: registered only when ``BENCH_CHAOS`` is set,
its behavior (ok / crash / sigkill / hang / slow / err:<msg>) is the
env value — a deliberately-misbehaving section the watchdog must
contain without poisoning its neighbors.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, Tuple

from bench.workload import (
    build_header_chain,
    env_int,
    load_helpers,
    make_workload,
    mixed_key_factory,
)

GO_CPU_BATCH_SIGS_PER_SEC = 30_000.0  # curve25519-voi batch verify, 1 core

CHAOS_ENV = "BENCH_CHAOS"


@dataclasses.dataclass(frozen=True)
class Section:
    """One registry entry. ``degrade`` = ((env_knob, default, floor), ...);
    ``skip_env`` = legacy BENCH_SKIP_* vars that drop the section;
    ``extra_env`` = env the parent must add to this section's child."""

    name: str
    fn: Callable[[Callable[[str], None]], dict]
    needs_jax: bool = True
    degrade: Tuple[Tuple[str, int, int], ...] = ()
    skip_env: Tuple[str, ...] = ()
    extra_env: Tuple[Tuple[str, str], ...] = ()


# --------------------------------------------------------------------------
# Section bodies
# --------------------------------------------------------------------------


def run_throughput(beat) -> dict:
    """Headline metric: batched ZIP-215 verification throughput, best of
    BENCH_ROUNDS rounds at BENCH_BATCH (crypto/ed25519/bench_test.go)."""
    import jax
    import numpy as np

    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import ed25519_batch

    batch = env_int("BENCH_BATCH", 8192)
    rounds = env_int("BENCH_ROUNDS", 5)
    backend = jax.default_backend()
    beat("workload batch=%d" % batch)
    rng = np.random.default_rng(1234)
    pks, msgs, sigs = make_workload(rng, batch)

    beat("warmup/compile batch=%d backend=%s" % (batch, backend))
    oks = ed25519_batch.verify_batch(pks, msgs, sigs)
    assert all(oks), "benchmark signatures must verify"

    best = 0.0
    tracing.tracer.clear()  # summarize the measured rounds, not warmup
    for i in range(rounds):
        beat("round %d/%d" % (i + 1, rounds))
        t0 = time.perf_counter()
        ed25519_batch.verify_batch(pks, msgs, sigs)
        dt = time.perf_counter() - t0
        best = max(best, batch / dt)
    return {
        "metric": "ed25519_batch_verify_throughput_b%d" % batch,
        "value": round(best, 1),
        "unit": "sigs/s",
        "vs_baseline": round(best / GO_CPU_BATCH_SIGS_PER_SEC, 3),
        "backend": backend,
        "impl": ed25519_batch.active_impl(),
        "trace_summary": tracing.tracer.summary() or None,
    }


def run_stages(beat) -> dict:
    """One instrumented pass: prep / H2D / kernel / D2H wall times, with
    prep further split into challenge hashing (hash_ms — on-device when
    ops/hash512 is active; the ``hash_us`` the tracer puts on a
    ``prep_chunk`` span) and host packing (pack_ms), plus a two-pass
    table-H2D probe over a pinned validator set (per-batch table upload
    bytes; flat-at-zero on pass 2 when the resident store holds them)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tendermint_tpu.libs import tracing
    from tendermint_tpu.ops import ed25519_batch, precompute, resident

    batch = env_int("BENCH_BATCH", 8192)
    backend = jax.default_backend()
    beat("workload batch=%d" % batch)
    rng = np.random.default_rng(1234)
    pks, msgs, sigs = make_workload(rng, batch)

    beat("prep")
    # the split is the tracer's: record for the length of the prep
    was_off = not tracing.tracer.enabled
    if was_off:
        tracing.configure("ring")
    try:
        t0 = time.perf_counter()
        with tracing.span("prep_chunk", lanes=len(pks)) as psp:
            inputs, host_ok = ed25519_batch.prepare_batch(
                pks, msgs, sigs, pad_to=ed25519_batch._bucket(len(pks)), backend=backend
            )
        t_prep = time.perf_counter() - t0
    finally:
        if was_off:
            tracing.configure("off")
    t_hash = psp.args.get("hash_us", 0.0) / 1e6

    m = inputs["pk"].shape[0]
    chunk = ed25519_batch.CHUNK
    impl = ed25519_batch.active_impl()

    beat("h2d lanes=%d" % m)
    t0 = time.perf_counter()
    dev = []
    for lo in range(0, m, chunk):
        hi = min(lo + chunk, m)
        dev.append(
            tuple(
                jax.device_put(jnp.asarray(inputs[k][lo:hi]))
                for k in ("pk", "r", "s", "k")
            )
        )
    for args in dev:
        for a in args:
            a.block_until_ready()
    t_h2d = time.perf_counter() - t0

    fns = []
    for ci, args in enumerate(dev):
        n_chunk = args[0].shape[0]
        beat("kernel compile chunk %d/%d n=%d impl=%s" % (ci + 1, len(dev), n_chunk, impl))
        if impl == "pallas":
            from tendermint_tpu.ops import pallas_verify

            fns.append(pallas_verify.compiled_verify(n_chunk))
        else:
            from tendermint_tpu.ops import field32

            mul_impl = "mxu" if impl == "mxu" else field32.get_mul_impl()
            fns.append(
                ed25519_batch._compiled_kernel(
                    ed25519_batch.KINDS["legacy"], n_chunk, None, mul_impl
                )
            )
    beat("kernel warmup")
    outs = [fn(*args) for fn, args in zip(fns, dev)]  # warmup/compile
    for o in outs:
        o.block_until_ready()

    beat("kernel measured pass")
    t0 = time.perf_counter()
    outs = [fn(*args) for fn, args in zip(fns, dev)]
    for o in outs:
        o.block_until_ready()
    t_kernel = time.perf_counter() - t0

    t0 = time.perf_counter()
    _ = np.concatenate([np.asarray(o) for o in outs])
    t_d2h = time.perf_counter() - t0

    # Two verify passes over a pinned validator set: pass 1 pays the
    # table uploads, pass 2 shows the steady-state per-batch table-H2D
    # cost (zero when the resident store serves the gathers).
    table_lanes = min(batch, env_int("BENCH_STAGES_TABLE_LANES", 256))
    beat("table-h2d probe lanes=%d" % table_lanes)
    t_pks, t_msgs, t_sigs = pks[:table_lanes], msgs[:table_lanes], sigs[:table_lanes]
    precompute.pin_pubkeys(t_pks)

    def _table_bytes() -> int:
        s = resident.stats()
        return int(s["h2d_bytes"]) + int(s["gathered_h2d_bytes"])

    b0 = _table_bytes()
    ed25519_batch.verify_batch(t_pks, t_msgs, t_sigs)
    b1 = _table_bytes()
    beat("table-h2d probe pass 2")
    ed25519_batch.verify_batch(t_pks, t_msgs, t_sigs)
    b2 = _table_bytes()

    return {
        "impl": impl,
        "backend": jax.default_backend(),
        "stages_ms": {
            "prep_ms": round(t_prep * 1e3, 2),
            "hash_ms": round(t_hash * 1e3, 2),
            "pack_ms": round(max(t_prep - t_hash, 0.0) * 1e3, 2),
            "h2d_ms": round(t_h2d * 1e3, 2),
            "kernel_ms": round(t_kernel * 1e3, 2),
            "d2h_ms": round(t_d2h * 1e3, 2),
        },
        "hash_device": psp.args.get("hash") == "device",
        "table_h2d": {
            "lanes": table_lanes,
            "pass1_bytes": b1 - b0,
            "pass2_bytes": b2 - b1,
            "resident": resident.enabled(backend),
        },
    }


def run_verify_commit(beat) -> dict:
    """p50 end-to-end VerifyCommit latency at BENCH_COMMIT_VALS
    validators (types/validation.go:27-54 semantics; BASELINE.md
    tracked metric). BENCH_COMMIT_MIX=mixed makes the set half
    ed25519 / half sr25519."""
    from tendermint_tpu.types import validation

    n_vals = env_int("BENCH_COMMIT_VALS", 10_000)
    iters = 7
    helpers = load_helpers()
    beat("fixture vals=%d" % n_vals)
    if os.environ.get("BENCH_COMMIT_MIX", "ed") == "mixed":
        privs, vset = helpers.make_validators(n_vals, key_factory=mixed_key_factory)
    else:
        privs, vset = helpers.make_validators(n_vals)
    block_id = helpers.make_block_id()
    commit = helpers.make_commit(block_id, 5, 0, vset, privs)
    beat("warmup/compile vals=%d" % n_vals)
    validation.verify_commit(helpers.CHAIN_ID, vset, block_id, 5, commit)
    times = []
    for i in range(iters):
        beat("iter %d/%d" % (i + 1, iters))
        t0 = time.perf_counter()
        validation.verify_commit(helpers.CHAIN_ID, vset, block_id, 5, commit)
        times.append(time.perf_counter() - t0)
    times.sort()
    p50 = round(times[len(times) // 2] * 1e3, 2)
    return {"verify_commit_p50_ms_v%d" % n_vals: p50}


def run_light_client(beat) -> dict:
    """BASELINE config 3: light-client sequential chain walk — each step
    a VerifyAdjacent (valhash link + 2/3 commit verify on the device
    batch path). Match: light/client_benchmark_test.go,
    light/verifier.go:106-152."""
    from tendermint_tpu.encoding.canonical import Timestamp
    from tendermint_tpu.light.verifier import verify_adjacent

    n_headers = env_int("BENCH_LIGHT_HEADERS", 16)
    n_vals = env_int("BENCH_LIGHT_VALS", 1000)
    beat("chain fixture headers=%d vals=%d" % (n_headers, n_vals))
    chain, vset, _ = build_header_chain(n_headers, n_vals)
    now = Timestamp.from_unix_ns(
        1_700_000_000_000_000_000 + (n_headers + 2) * 1_000_000_000
    )

    def walk():
        for i in range(1, len(chain)):
            verify_adjacent(chain[i - 1], chain[i], vset, 86400.0, now, 10.0)

    beat("warmup walk")
    walk()
    beat("measured walk")
    t0 = time.perf_counter()
    walk()
    dt = time.perf_counter() - t0
    return {
        "light_client_headers_per_s_v%d" % n_vals: round((len(chain) - 1) / dt, 2)
    }


def run_blocksync(beat) -> dict:
    """BASELINE config 4: a blocksync catch-up window's commits
    flattened into one pipelined device batch. Match:
    internal/blocksync/reactor.go:538-650, parallel/pipeline.py."""
    from tendermint_tpu.parallel.pipeline import CommitTask, verify_commits_pipelined

    n_blocks = env_int("BENCH_SYNC_BLOCKS", 32)
    n_vals = env_int("BENCH_SYNC_VALS", 500)
    beat("chain fixture blocks=%d vals=%d" % (n_blocks, n_vals))
    chain, vset, chain_id = build_header_chain(n_blocks, n_vals)
    tasks = [
        CommitTask(chain_id, vset, sh.commit.block_id, sh.header.height, sh.commit)
        for sh in chain
    ]
    beat("warmup pipeline")
    verdicts = verify_commits_pipelined(tasks)
    assert all(v.ok for v in verdicts), "benchmark commits must verify"
    beat("measured pipeline")
    t0 = time.perf_counter()
    verdicts = verify_commits_pipelined(tasks)
    dt = time.perf_counter() - t0
    assert all(v.ok for v in verdicts)
    return {"blocksync_blocks_per_s_v%d" % n_vals: round(n_blocks / dt, 2)}


def run_cache(beat) -> dict:
    """Second-commit amortization at BENCH_CACHE_VALS validators: pass 1
    pays the host-side precompute builds, pass 2 gathers every table
    from the validator-set cache; passes 3/4 show the digest-keyed
    result-cache short-circuit."""
    from tendermint_tpu.ops import precompute
    from tendermint_tpu.types import validation

    cache_vals = env_int("BENCH_CACHE_VALS", 100)
    helpers = load_helpers()
    beat("fixture vals=%d" % cache_vals)
    privs, vset = helpers.make_validators(cache_vals)
    block_id = helpers.make_block_id()
    commit = helpers.make_commit(block_id, 7, 0, vset, privs)
    precompute.reset()

    def one_pass():
        t0 = time.perf_counter()
        validation.verify_commit(helpers.CHAIN_ID, vset, block_id, 7, commit)
        return time.perf_counter() - t0

    beat("cold pass (compiles + builds tables)")
    cold = one_pass()
    s1 = dict(precompute.stats()["precompute"])
    beat("warm pass (cache gather)")
    warm = one_pass()
    s2 = dict(precompute.stats()["precompute"])
    prev = os.environ.get("TENDERMINT_TPU_RESULT_CACHE")
    os.environ["TENDERMINT_TPU_RESULT_CACHE"] = "1"
    try:
        beat("result-cache passes")
        one_pass()  # populates the result cache
        cached = one_pass()  # answered from it
    finally:
        if prev is None:
            os.environ.pop("TENDERMINT_TPU_RESULT_CACHE", None)
        else:
            os.environ["TENDERMINT_TPU_RESULT_CACHE"] = prev
    rc = precompute.stats()["result_cache"]
    warm_lookups = s2["hits"] + s2["misses"] - s1["hits"] - s1["misses"]
    warm_hits = s2["hits"] - s1["hits"]
    return {
        "cache": {
            "vals": cache_vals,
            "cold_ms": round(cold * 1e3, 2),
            "warm_ms": round(warm * 1e3, 2),
            "result_cached_ms": round(cached * 1e3, 2),
            "builds_cold": s1["builds"],
            "builds_warm": s2["builds"] - s1["builds"],
            "table_hit_rate_warm": round(warm_hits / warm_lookups, 4)
            if warm_lookups
            else None,
            "table_build_ms_total": round(s2["build_seconds"] * 1e3, 2),
            "result_cache_hits": rc["hits"],
            "result_cache_misses": rc["misses"],
        }
    }


def run_verifyd(beat) -> dict:
    """Verification-as-a-service cost: an in-process verifyd daemon
    serves BENCH_VERIFYD_CLIENTS concurrent clients over the localhost
    wire; the identical batch runs through the tiered dispatch directly
    for the wire-overhead comparison."""
    import threading

    import numpy as np

    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.client import VerifydClient
    from tendermint_tpu.verifyd.server import VerifydServer

    n_clients = env_int("BENCH_VERIFYD_CLIENTS", 4)
    n_lanes = env_int("BENCH_VERIFYD_LANES", 64)
    n_rounds = env_int("BENCH_VERIFYD_ROUNDS", 8)

    beat("workload lanes=%d" % n_lanes)
    rng = np.random.default_rng(99)
    pks, msgs, sigs = make_workload(rng, n_lanes)

    beat("in-process warmup/compile")
    crypto_batch.tiered_verify_ed25519(pks, msgs, sigs)
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        crypto_batch.tiered_verify_ed25519(pks, msgs, sigs)
    inproc_s = (time.perf_counter() - t0) / n_rounds

    srv = VerifydServer(max_batch=n_lanes * n_clients, max_delay=0.002)
    srv.start()
    host, port = srv.address
    lat = []
    lat_mtx = threading.Lock()
    errors = []

    def run_client(i):
        try:
            c = VerifydClient(f"{host}:{port}", fallback=False)
            for _ in range(n_rounds):
                t = time.perf_counter()
                oks = c.verify(pks, msgs, sigs, klass=protocol.CLASS_CONSENSUS)
                dt = time.perf_counter() - t
                if not all(oks):
                    raise AssertionError("verifyd rejected valid lanes")
                with lat_mtx:
                    lat.append(dt)
            c.close()
        except Exception as exc:
            errors.append(repr(exc))

    try:
        beat("daemon warmup")
        warm = VerifydClient(f"{host}:{port}")
        warm.verify(pks, msgs, sigs)
        warm.close()
        threads = [
            threading.Thread(target=run_client, args=(i,))
            for i in range(n_clients)
        ]
        beat("wire rounds clients=%d rounds=%d" % (n_clients, n_rounds))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors or not lat:
            return {"verifyd": {"error": errors[:3] or ["no samples"]}}
        sched_stats = srv.scheduler.stats()
        lat.sort()
        total_lanes = len(lat) * n_lanes
        return {
            "verifyd": {
                "clients": n_clients,
                "lanes_per_call": n_lanes,
                "wire_sigs_per_s": round(total_lanes / wall, 1),
                "wire_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
                "wire_p95_ms": round(lat[int(len(lat) * 0.95)] * 1e3, 2),
                "inproc_batch_ms": round(inproc_s * 1e3, 2),
                "wire_overhead_x": round((sum(lat) / len(lat)) / inproc_s, 2)
                if inproc_s > 0
                else None,
                "flushes": sched_stats["flushes"],
                "mean_batch_occupancy": round(
                    sched_stats["entries_verified"]
                    / max(1, sched_stats["flushes"]),
                    1,
                ),
                "cross_client_flushes": srv.stats()["cross_client_flushes"],
            }
        }
    finally:
        srv.stop()


def run_verifyd_tenants(beat) -> dict:
    """Two-tenant mixed-load A/B: a victim tenant's consensus latency
    while an aggressor tenant floods rpc, measured with continuous
    batching ON vs the flush-barrier path (TENDERMINT_TPU_CONT_BATCH=off
    equivalent). The device is MODELED (a fixed sleep per lane) so the
    comparison isolates scheduling behavior from kernel speed — and the
    section runs without jax."""
    import threading

    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.client import (
        VerifydClient,
        VerifydRejectedError,
    )
    from tendermint_tpu.verifyd.server import VerifydServer

    n_rounds = env_int("BENCH_TENANTS_ROUNDS", 30)
    n_floods = env_int("BENCH_TENANTS_FLOODS", 4)
    lane_us = env_int("BENCH_TENANTS_LANE_US", 300)

    # the modeled verifier never reads the bytes: synthetic lanes keep
    # the section free of pure-python key arithmetic
    victim_lanes = (
        [b"\x01" * 32] * 4,
        [b"victim-%d" % i for i in range(4)],
        [b"\x02" * 64] * 4,
    )
    flood_lanes = (
        [b"\x03" * 32] * 16,
        [b"flood-%d" % i for i in range(16)],
        [b"\x04" * 64] * 16,
    )

    def modeled(pks, msgs, sigs):
        time.sleep(lane_us * 1e-6 * len(pks))
        return [True] * len(pks)

    def one_mode(continuous):
        srv = VerifydServer(
            verify_fn=modeled, max_batch=64, max_delay=0.002,
            admission_cap=256, tenant_cap=48, continuous=continuous,
        )
        srv.start()
        host, port = srv.address
        addr = f"{host}:{port}"
        stop = threading.Event()
        mtx = threading.Lock()
        flood_served = [0]
        flood_sheds = [0]

        def aggressor():
            c = VerifydClient(
                addr, tenant="flood", fallback=False, shed_retries=0
            )
            while not stop.is_set():
                try:
                    c.verify(*flood_lanes, klass=protocol.CLASS_RPC)
                    with mtx:
                        flood_served[0] += 1
                except VerifydRejectedError:
                    with mtx:
                        flood_sheds[0] += 1
                    time.sleep(0.002)  # a real client would back off
            c.close()

        lat = []
        try:
            victim = VerifydClient(addr, tenant="victim", fallback=False)
            victim.verify(*victim_lanes, klass=protocol.CLASS_CONSENSUS)
            floods = [
                threading.Thread(target=aggressor) for _ in range(n_floods)
            ]
            for t in floods:
                t.start()
            time.sleep(0.1)  # flood established
            for i in range(n_rounds):
                if i % 10 == 0:
                    beat("victim round %d/%d" % (i, n_rounds))
                t0 = time.perf_counter()
                oks = victim.verify(
                    *victim_lanes, klass=protocol.CLASS_CONSENSUS
                )
                lat.append(time.perf_counter() - t0)
                if not all(oks):
                    raise AssertionError("modeled verify must pass")
            stop.set()
            for t in floods:
                t.join(timeout=10)
            victim.close()
            tenants = {
                label: {"lanes": s["lanes"], "sheds": s["sheds"]}
                for label, s in srv.tenant_stats().items()
            }
            occupancy = srv.scheduler.stats()["dispatch_handoffs"]
        finally:
            stop.set()
            srv.stop()
        lat.sort()
        return {
            "victim_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "victim_p99_ms": round(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 2
            ),
            "flood_served": flood_served[0],
            "flood_sheds": flood_sheds[0],
            "dispatch_handoffs": occupancy,
            "tenants": tenants,
        }

    beat("continuous mode rounds=%d floods=%d" % (n_rounds, n_floods))
    cont = one_mode(True)
    beat("barrier mode (CONT_BATCH=off)")
    barrier = one_mode(False)
    ratio = (
        round(barrier["victim_p99_ms"] / cont["victim_p99_ms"], 2)
        if cont["victim_p99_ms"]
        else None
    )
    return {
        "verifyd_tenants": {
            "lane_us": lane_us,
            "continuous": cont,
            "barrier": barrier,
            "barrier_over_continuous_p99_x": ratio,
        }
    }


def run_verifyd_shm(beat) -> dict:
    """Zero-copy ingress A/B (verifyd/shm.py): the identical batch rides
    the shared-memory slab ring vs the TCP proto3 codec against the same
    in-process verifyd, at 1k and BENCH_SHM_LANES (default 8k) lanes.
    The verifier is a noop over synthetic lanes — declared as
    ``verify: noop`` in the fragment — so the deltas isolate transport +
    codec cost from kernel speed, and the section runs without jax."""
    import threading  # noqa: F401  (keeps import style with siblings)

    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.client import VerifydClient
    from tendermint_tpu.verifyd.server import VerifydServer

    rounds = env_int("BENCH_SHM_ROUNDS", 12)
    big = env_int("BENCH_SHM_LANES", 8192)
    sizes = sorted({min(1024, big), big})

    def make_lanes(n):
        # the noop verifier never reads the bytes; distinct msgs keep
        # the scheduler's coalescing keys distinct
        return (
            [i.to_bytes(4, "little") * 8 for i in range(n)],
            [b"shm-lane-%08d" % i for i in range(n)],
            [b"\x05" * 64] * n,
        )

    def noop(pks, msgs, sigs):
        return [True] * len(pks)

    srv = VerifydServer(
        verify_fn=noop,
        max_batch=big,
        max_delay=0.0005,
        admission_cap=4 * big,
        max_pending=4 * big,
        shm="on",
    )
    srv.start()
    host, port = srv.address
    addr = f"{host}:{port}"
    out = {"verify": "noop", "rounds": rounds, "sizes": {}}
    try:
        for n in sizes:
            pks, msgs, sigs = make_lanes(n)
            per_mode = {}
            for mode in ("shm", "tcp"):
                beat("mode=%s lanes=%d rounds=%d" % (mode, n, rounds))
                c = VerifydClient(
                    addr,
                    shm="on" if mode == "shm" else "off",
                    fallback=False,
                )
                try:
                    oks = c.verify(
                        pks, msgs, sigs, klass=protocol.CLASS_CONSENSUS
                    )
                    if not all(oks):
                        raise AssertionError("noop verify must pass")
                    if mode == "shm" and c.transport != "shm":
                        per_mode[mode] = {
                            "error": "shm negotiation failed (rode %s)"
                            % c.transport
                        }
                        continue
                    lat = []
                    for _ in range(rounds):
                        t0 = time.perf_counter()
                        c.verify(
                            pks, msgs, sigs, klass=protocol.CLASS_CONSENSUS
                        )
                        lat.append(time.perf_counter() - t0)
                    lat.sort()
                    stats = c.stats()
                    per_mode[mode] = {
                        "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                        "p99_ms": round(
                            lat[min(len(lat) - 1, int(len(lat) * 0.99))]
                            * 1e3,
                            3,
                        ),
                        "sigs_per_s": round(rounds * n / sum(lat), 1),
                        "transport": stats["transport"],
                        "shm_fallbacks": stats["shm_fallbacks"],
                    }
                    if mode == "shm":
                        per_mode[mode]["codec_bytes_avoided"] = stats[
                            "shm_bytes_avoided"
                        ]
                finally:
                    c.close()
            entry = dict(per_mode)
            if "p50_ms" in per_mode.get("shm", {}) and "p50_ms" in per_mode.get(
                "tcp", {}
            ):
                entry["p50_delta_ms"] = round(
                    per_mode["tcp"]["p50_ms"] - per_mode["shm"]["p50_ms"], 3
                )
            out["sizes"][str(n)] = entry
        out["server"] = {
            k: srv.stats()[k]
            for k in ("shm_lanes", "shm_torn_slabs", "shm_fallbacks")
        }
    finally:
        srv.stop()
    return {"verifyd_shm": out}


def run_verifyd_fleet(beat) -> dict:
    """Verifyd federation scaling (ISSUE 19): 1/2/4 spawned shard
    processes under the same two-tenant mixed-committee load, one
    FederationClient per tenant routing by validator-set digest. The
    section PROVES three claims over the wire, not by bookkeeping:
    tables are partitioned (per-shard pinned slices from STATS_PATH are
    pairwise disjoint and each shard stages a fraction of the
    single-shard bytes), aggregate sigs/s scales with shard count
    (2 shards >= 1.5x one shard), and a mid-load SIGKILL of a shard
    finishes the round with ZERO silent drops (every lane verdicted;
    every False lane explained by the host-oracle counter). Shards are
    real processes (bench/fleet.py) because the GIL and the
    process-singleton resident store would fake both scaling and
    disjointness in-process; the verifier is MODELED (fixed sleep per
    lane, declared ``verify: modeled``) so the scaling measured is the
    federation's, not the kernel's."""
    import hashlib
    import threading

    from bench.fleet import ShardFleet
    from tendermint_tpu.ops.resident import TABLE_BYTES_PER_KEY
    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.federation import FederationClient

    rounds = env_int("BENCH_FLEET_ROUNDS", 6)
    kill_rounds = env_int("BENCH_FLEET_KILL_ROUNDS", 3)
    n_committees = env_int("BENCH_FLEET_COMMITTEES", 8)
    lanes_per = env_int("BENCH_FLEET_LANES", 16)
    lane_us = env_int("BENCH_FLEET_LANE_US", 200)
    max_shards = env_int("BENCH_FLEET_MAX_SHARDS", 4)
    shard_counts = [n for n in (1, 2, 4) if n <= max_shards] or [1]

    # deterministic synthetic committees (4 keys each): the modeled
    # verifier never reads the bytes, and FIXED keys make the ring
    # split — hence the disjointness assertion — reproducible, not
    # a coin flip per run
    committees = [
        [
            hashlib.sha256(b"fleet-committee-%d-key-%d" % (c, k)).digest()
            for k in range(4)
        ]
        for c in range(n_committees)
    ]
    batch_pks, batch_msgs, batch_sigs = [], [], []
    for c, keys in enumerate(committees):
        for i in range(lanes_per):
            batch_pks.append(keys[i % len(keys)])
            batch_msgs.append(b"fleet-c%02d-lane-%04d" % (c, i))
            batch_sigs.append(b"\x06" * 64)
    lanes_per_call = len(batch_pks)

    tenant_specs = (("consensus", 500), ("rpc", 0))

    def drive(fed, klass, n_rounds, errs, false_lanes):
        """One tenant's load: n_rounds mixed batches spanning every
        committee. Records verdict-count mismatches (silent drops) and
        False verdicts (host-oracle lanes — modeled sigs are garbage)."""
        for _ in range(n_rounds):
            try:
                oks = fed.verify(
                    batch_pks, batch_msgs, batch_sigs, klass=klass
                )
            except Exception as exc:  # the ladder must never raise
                errs.append(repr(exc))
                continue
            if len(oks) != lanes_per_call:
                errs.append(
                    "verdict count %d != %d" % (len(oks), lanes_per_call)
                )
            false_lanes[0] += sum(1 for ok in oks if not ok)

    out = {
        "verify": "modeled",
        "lane_us": lane_us,
        "committees": n_committees,
        "lanes_per_call": lanes_per_call,
        "tenants": [t for t, _ in tenant_specs],
        "rounds": rounds,
        "shards": {},
    }
    single_bytes = None
    for n_shards in shard_counts:
        beat("launching %d shard(s)" % n_shards)
        fleet = ShardFleet(lane_us=lane_us)
        feds = []
        try:
            addrs = fleet.launch(n_shards)
            feds = [
                FederationClient(addrs, tenant=t, slo_ms=slo, timeout=30.0)
                for t, slo in tenant_specs
            ]
            for fed in feds:
                for keys in committees:
                    fed.note_validator_set(keys)
            # warm round: establishes connections and trips the
            # server-side hot-key pin threshold on every committee
            for fed, (t, _) in zip(feds, tenant_specs):
                klass = (
                    protocol.CLASS_CONSENSUS
                    if t == "consensus"
                    else protocol.CLASS_RPC
                )
                oks = fed.verify(batch_pks, batch_msgs, batch_sigs, klass=klass)
                if not all(oks):
                    raise AssertionError("modeled verify must pass warm round")
            beat("measuring %d shard(s) rounds=%d" % (n_shards, rounds))
            errs: list = []
            false_counts = [[0] for _ in feds]
            threads = [
                threading.Thread(
                    target=drive,
                    args=(
                        fed,
                        protocol.CLASS_CONSENSUS
                        if t == "consensus"
                        else protocol.CLASS_RPC,
                        rounds,
                        errs,
                        fc,
                    ),
                )
                for fed, (t, _), fc in zip(feds, tenant_specs, false_counts)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if errs:
                raise AssertionError("healthy rounds errored: %s" % errs[:3])
            if any(fc[0] for fc in false_counts):
                raise AssertionError(
                    "healthy rounds hit host fallback (shards overloaded?)"
                )
            sigs_per_s = len(feds) * rounds * lanes_per_call / wall
            # partitioning proof, over the wire: each shard's pinned
            # slice from STATS_PATH, pairwise disjoint, full coverage
            gossip = feds[0].refresh(timeout=5.0)
            pinned = {
                sid: set(snap.get("pinned_keys") or [])
                for sid, snap in gossip.items()
            }
            staged = {
                sid: int((snap.get("resident") or {}).get(
                    "host_staged_bytes", 0
                ))
                for sid, snap in gossip.items()
            }
            all_keys: set = set()
            for sid, keys in pinned.items():
                overlap = all_keys & keys
                if overlap:
                    raise AssertionError(
                        "shards replicate %d key(s) — partition violated"
                        % len(overlap)
                    )
                all_keys |= keys
            want_keys = {pk.hex() for pk in batch_pks}
            if all_keys != want_keys:
                raise AssertionError(
                    "pinned union %d keys != workload %d"
                    % (len(all_keys), len(want_keys))
                )
            entry = {
                "sigs_per_s": round(sigs_per_s, 1),
                "wall_s": round(wall, 3),
                "pinned_keys": {
                    "shard%d" % s: len(k) for s, k in pinned.items()
                },
                "host_staged_bytes": {
                    "shard%d" % s: b for s, b in staged.items()
                },
                "disjoint": True,
            }
            if n_shards == 1:
                single_bytes = sum(staged.values())
                if single_bytes != len(want_keys) * TABLE_BYTES_PER_KEY:
                    raise AssertionError(
                        "single-shard staged bytes %d != %d keys x %d"
                        % (single_bytes, len(want_keys), TABLE_BYTES_PER_KEY)
                    )
            elif single_bytes:
                worst = max(staged.values())
                entry["max_shard_bytes_vs_single"] = round(
                    worst / single_bytes, 3
                )
                if worst >= single_bytes:
                    raise AssertionError(
                        "a shard staged the full table set (%d >= %d): "
                        "replicated, not partitioned" % (worst, single_bytes)
                    )
            out["shards"][str(n_shards)] = entry

            if n_shards == 2 and kill_rounds > 0:
                # failover: SIGKILL a shard that owns committees while
                # both tenants are mid-load; the round must finish with
                # every lane verdicted and every False lane explained
                victim = feds[0].shard_for(committees[0][0])
                base_fallback = [
                    fed.stats()["host_fallback_lanes"] for fed in feds
                ]
                beat("killing shard %d mid-load" % victim)
                errs2: list = []
                false2 = [[0] for _ in feds]
                threads = [
                    threading.Thread(
                        target=drive,
                        args=(
                            fed,
                            protocol.CLASS_CONSENSUS
                            if t == "consensus"
                            else protocol.CLASS_RPC,
                            kill_rounds,
                            errs2,
                            fc,
                        ),
                    )
                    for fed, (t, _), fc in zip(feds, tenant_specs, false2)
                ]
                for t in threads:
                    t.start()
                # land the kill inside the first round, not between them
                time.sleep(lanes_per_call * lane_us * 1e-6 * 0.5)
                fleet.kill(victim)
                for t in threads:
                    t.join()
                if errs2:
                    raise AssertionError(
                        "failover rounds errored: %s" % errs2[:3]
                    )
                explained = sum(
                    fed.stats()["host_fallback_lanes"] - b
                    for fed, b in zip(feds, base_fallback)
                )
                unexplained = sum(fc[0] for fc in false2) - explained
                if unexplained:
                    raise AssertionError(
                        "%d False lane(s) not explained by the host-"
                        "oracle counter: silent corruption" % unexplained
                    )
                moved = sum(
                    fed.stats()["failovers"] + fed.stats()["host_fallback_lanes"]
                    for fed in feds
                )
                if moved <= 0:
                    raise AssertionError(
                        "shard kill produced no failovers — ladder inert"
                    )
                out["failover"] = {
                    "killed_shard": victim,
                    "rounds_after_kill": kill_rounds,
                    "failovers": sum(f.stats()["failovers"] for f in feds),
                    "rerouted_lanes": sum(
                        f.stats()["rerouted_lanes"] for f in feds
                    ),
                    "host_fallback_lanes": explained,
                    "unexplained_false_lanes": 0,
                    "zero_silent_drops": True,
                }
        finally:
            for fed in feds:
                fed.close()
            fleet.stop_all()

    one = out["shards"].get("1", {}).get("sigs_per_s")
    two = out["shards"].get("2", {}).get("sigs_per_s")
    if one and two:
        out["scaling_2x_over_1x"] = round(two / one, 2)
        if two < 1.5 * one:
            raise AssertionError(
                "2-shard aggregate %.1f sigs/s < 1.5x single-shard %.1f"
                % (two, one)
            )
    return {"verifyd_fleet": out}


def run_latency_attrib(beat) -> dict:
    """End-to-end latency attribution (ISSUE 15): the stage-time vector
    every verifyd response carries must actually EXPLAIN the latency the
    client observes, not merely decorate it. A modeled sleep verifier
    makes the device stage dominant and deterministic (no jax), the
    connection is warmed before measuring so channel setup does not
    pollute the vector, and the section asserts the attributed stages
    sum to >=90% of the client-observed p50 — if attribution ever drifts
    (a stage boundary moves, a wait stops being counted), the bench
    fails rather than silently reporting a vector nobody can trust."""
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.client import VerifydClient
    from tendermint_tpu.verifyd.server import VerifydServer

    rounds = env_int("BENCH_ATTRIB_ROUNDS", 24)
    n_lanes = env_int("BENCH_ATTRIB_LANES", 32)
    lane_us = env_int("BENCH_ATTRIB_LANE_US", 400)

    lanes = (
        [b"\x05" * 32] * n_lanes,
        [b"attrib-%d" % i for i in range(n_lanes)],
        [b"\x06" * 64] * n_lanes,
    )

    def modeled(pks, msgs, sigs):
        time.sleep(lane_us * 1e-6 * len(pks))
        return [True] * len(pks)

    prev_mode = tracing.tracer.mode
    tracing.configure(tracing.RING)  # exemplars need a recording tracer
    # static batching: the claim under test is the stage vector tiling a
    # KNOWN config's wall — the dyn controller legitimately shortens
    # residency, which deflates the wall the fixed transport overhead is
    # measured against (slo_replay owns the adaptive numbers)
    srv = VerifydServer(
        verify_fn=modeled, max_batch=n_lanes, max_delay=0.001,
        dyn_batch=False,
    )
    srv.start()
    host, port = srv.address
    samples = []  # (wall_s, attributed_s) per measured call
    try:
        c = VerifydClient(f"{host}:{port}", fallback=False)
        beat("connection warmup lanes=%d lane_us=%d" % (n_lanes, lane_us))
        for _ in range(3):
            c.verify(*lanes, klass=protocol.CLASS_CONSENSUS)
        prev_totals = dict(c.stage_totals)
        for i in range(rounds):
            if i % 8 == 0:
                beat("attrib round %d/%d" % (i, rounds))
            t0 = time.perf_counter()
            oks = c.verify(*lanes, klass=protocol.CLASS_CONSENSUS)
            wall = time.perf_counter() - t0
            if not all(oks):
                raise AssertionError("modeled verify must pass")
            attributed = sum(
                v - prev_totals.get(k, 0.0)
                for k, v in c.stage_totals.items()
                if k != "transport"
            )
            prev_totals = dict(c.stage_totals)
            samples.append((wall, attributed))
        stage_totals = dict(c.stage_totals)
        c.close()
    finally:
        srv.stop()
        tracing.configure(prev_mode)

    samples.sort(key=lambda s: s[0])
    p50_wall, p50_attr = samples[len(samples) // 2]
    p50_frac = p50_attr / p50_wall if p50_wall > 0 else 0.0
    attributed_sum = sum(
        v for k, v in stage_totals.items() if k != "transport"
    )
    frag = {
        "rounds": rounds,
        "lanes": n_lanes,
        "lane_us": lane_us,
        "p50_ms": round(p50_wall * 1e3, 3),
        "p50_attributed_ms": round(p50_attr * 1e3, 3),
        "p50_attributed_frac": round(p50_frac, 4),
        "stage_ms": {
            k: round(v * 1e3, 3) for k, v in sorted(stage_totals.items())
        },
        "transport_frac": round(
            stage_totals.get("transport", 0.0)
            / max(1e-12, attributed_sum + stage_totals.get("transport", 0.0)),
            4,
        ),
    }
    # the section's whole point: the vector explains the latency
    if p50_frac < 0.9:
        raise AssertionError(
            "stage vector explains only %.1f%% of observed p50 "
            "(need >=90%%): %r" % (p50_frac * 100.0, frag)
        )
    return {"latency_attrib": frag}


def run_slo_replay(beat) -> dict:
    """SLO replay (ISSUE 17 tentpole): replay the checked-in diurnal
    trace (bench/slo_trace.json — tip-follower Zipf rpc + consensus
    bursts) against the SAME verifyd twice. Static config first, at a
    doubling rate ladder, until its tip-tenant p99 breaches the
    declared budget (or it starts shedding/blowing deadlines) — that
    multiplier is the static saturation point. Then the adaptive config
    (dyn-batch controller + per-tenant SLO budget) replays at 2x that
    point and the section ASSERTS it holds the tip p99 within budget
    while still serving >=70% of the offered requests — held-by-
    shedding-everything is a failure, not a pass.

    The device is MODELED (launch-dominated: a large fixed sleep plus a
    small per-lane slope) so the section isolates the control loop from
    kernel speed and runs without jax. That cost curve is exactly the
    regime the controller exists for: bigger batches amortize the
    launch cost, so the static config's ceiling is set by its small
    max_batch while the adaptive config earns headroom by growing it."""
    import json
    import threading

    import numpy as np

    from tendermint_tpu.verifyd import protocol
    from tendermint_tpu.verifyd.client import (
        VerifydClient,
        VerifydRejectedError,
    )
    from tendermint_tpu.verifyd.server import VerifydServer

    trace_path = os.path.join(os.path.dirname(__file__), "slo_trace.json")
    with open(trace_path) as f:
        trace = json.load(f)
    if trace.get("schema") != "tendermint-tpu-slo-trace/1":
        raise ValueError("bad slo trace schema: %r" % trace.get("schema"))

    n_slots = env_int("BENCH_SLO_SLOTS", len(trace["slots"]))
    sat_steps = env_int("BENCH_SLO_SAT_STEPS", 4)
    base_us = env_int("BENCH_SLO_BASE_US", 10_000)
    lane_us = env_int("BENCH_SLO_LANE_US", 40)
    static_mb = env_int("BENCH_SLO_STATIC_BATCH", 4)
    static_delay_ms = env_int("BENCH_SLO_STATIC_DELAY_MS", 2)
    n_senders = env_int("BENCH_SLO_SENDERS", 12)
    warmup_pct = env_int("BENCH_SLO_WARMUP_PCT", 30)

    slot_s = float(trace["slot_s"])
    slots = [tuple(s) for s in trace["slots"][:n_slots]]
    # measurement warmup: the whole trace is SENT (the load is real from
    # t=0) but the scoreboard only starts once the controller has had
    # its ramp window — steady-state p99, the quantity the budget is
    # declared against, not cold-start transients
    warmup_s = len(slots) * slot_s * warmup_pct / 100.0
    tip_cfg = trace["tenants"]["tip"]
    cons_cfg = trace["tenants"]["consensus"]
    slo_ms = int(tip_cfg["slo_ms"])

    def modeled(pks, msgs, sigs):
        time.sleep(base_us * 1e-6 + lane_us * 1e-6 * len(pks))
        return [True] * len(pks)

    def make_events(mult):
        """The full arrival schedule for one replay, deterministic from
        the checked-in seed: [(t_offset_s, tenant, lanes, klass,
        deadline_s), ...] sorted by time."""
        rng = np.random.default_rng(int(trace["seed"]))
        events = []
        for i, (tip_rps, cons_rps) in enumerate(slots):
            t_slot = i * slot_s
            n_tip = int(round(tip_rps * mult * slot_s))
            for k in range(n_tip):
                lanes = int(
                    min(tip_cfg["max_lanes"], rng.zipf(tip_cfg["zipf_a"]))
                )
                events.append((
                    t_slot + (k + rng.random()) * slot_s / max(1, n_tip),
                    "tip", lanes, protocol.CLASS_RPC,
                    tip_cfg["deadline_ms"] / 1e3,
                ))
            n_cons = int(round(cons_rps * mult * slot_s))
            for k in range(n_cons):
                events.append((
                    t_slot + (k + rng.random()) * slot_s / max(1, n_cons),
                    "consensus", int(cons_cfg["lanes"]),
                    protocol.CLASS_CONSENSUS,
                    cons_cfg["deadline_ms"] / 1e3,
                ))
        events.sort(key=lambda e: e[0])
        return events

    def play(mult, dyn, tenant_slos):
        """One replay of the trace at rate multiplier ``mult``."""
        srv = VerifydServer(
            verify_fn=modeled,
            max_batch=static_mb,
            max_delay=static_delay_ms / 1e3,
            admission_cap=4096,
            dyn_batch=dyn,
            tenant_slos=tenant_slos,
        )
        srv.start()
        host, port = srv.address
        addr = f"{host}:{port}"
        queues = {"tip": [], "consensus": []}
        for ev in make_events(mult):
            queues[ev[1]].append(ev)
        offered = {t: len(q) for t, q in queues.items()}
        mtx = threading.Lock()
        out = {
            t: {"lat": [], "sheds": 0, "deadline": 0, "late": 0, "sent": 0}
            for t in queues
        }

        def sender(tenant, q):
            c = VerifydClient(
                addr, tenant=tenant, fallback=False, shed_retries=0
            )
            stats = out[tenant]
            try:
                while True:
                    with mtx:
                        if not q:
                            return
                        t_ev, _, lanes, klass, dl = q.pop(0)
                    wait = t_start + t_ev - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    elif wait < -slot_s:
                        # the pool fell a full slot behind schedule:
                        # offered load has gone closed-loop, record it
                        with mtx:
                            stats["late"] += 1
                    scored = t_ev >= warmup_s
                    t_req = time.perf_counter()
                    try:
                        c.verify(
                            [b"\x07" * 32] * lanes,
                            [b"replay-%d" % lanes] * lanes,
                            [b"\x08" * 64] * lanes,
                            klass=klass, deadline=dl,
                        )
                        if scored:
                            with mtx:
                                stats["sent"] += 1
                                stats["lat"].append(
                                    time.perf_counter() - t_req
                                )
                    except VerifydRejectedError as exc:
                        if not scored:
                            continue
                        with mtx:
                            stats["sent"] += 1
                            if (
                                exc.status
                                == protocol.STATUS_DEADLINE_EXCEEDED
                            ):
                                # a blown deadline IS a latency sample:
                                # score it at the full deadline so the
                                # percentile cannot hide it
                                stats["deadline"] += 1
                                stats["lat"].append(dl)
                            else:
                                stats["sheds"] += 1
            finally:
                c.close()

        try:
            warm = VerifydClient(addr, fallback=False)
            warm.verify([b"\x07" * 32], [b"warm"], [b"\x08" * 64])
            warm.close()
            pools = [
                threading.Thread(target=sender, args=("tip", queues["tip"]))
                for _ in range(n_senders)
            ] + [
                threading.Thread(
                    target=sender, args=("consensus", queues["consensus"])
                )
                for _ in range(max(2, n_senders // 3))
            ]
            t_start = time.perf_counter() + 0.05
            for t in pools:
                t.start()
            while any(t.is_alive() for t in pools):
                beat(
                    "replay x%g dyn=%s pending=%d"
                    % (mult, dyn, sum(len(q) for q in queues.values()))
                )
                for t in pools:
                    t.join(timeout=2.0)
            knobs = srv.stats().get("scheduler")
            tenants = srv.tenant_stats()
        finally:
            srv.stop()

        run = {"mult": mult, "dyn_batch": dyn, "knobs": knobs}
        for tenant, stats in out.items():
            lat = sorted(stats["lat"])
            n = len(lat)
            run[tenant] = {
                "offered": offered[tenant],
                "scored": stats["sent"],
                "served": n - stats["deadline"],
                "sheds": stats["sheds"],
                "deadline_exceeded": stats["deadline"],
                "late": stats["late"],
                "p50_ms": round(lat[n // 2] * 1e3, 2) if n else None,
                "p99_ms": round(lat[int(0.99 * (n - 1))] * 1e3, 2)
                if n
                else None,
                "slo": (tenants.get(tenant) or {}).get("slo_ms", 0),
                "slo_sheds": (tenants.get(tenant) or {}).get("slo_sheds", 0),
            }
        return run

    def breached(run):
        """A static run is saturated when the tip p99 blew the budget —
        or when it only held the budget by rejecting work."""
        tip = run["tip"]
        failures = tip["sheds"] + tip["deadline_exceeded"]
        return (
            (tip["p99_ms"] is not None and tip["p99_ms"] > slo_ms)
            or failures > 0.05 * max(1, tip["scored"])
        )

    static_runs = []
    mult = 1.0
    m_sat = None
    for _ in range(max(1, sat_steps)):
        beat("static ladder x%g" % mult)
        run = play(mult, dyn=False, tenant_slos=None)
        static_runs.append(run)
        if breached(run):
            m_sat = mult
            break
        mult *= 2.0
    saturated = m_sat is not None
    if m_sat is None:
        # ladder exhausted without a breach: anchor on the last rate we
        # actually proved the static config holds
        m_sat = static_runs[-1]["mult"]

    adaptive_mult = 2.0 * m_sat
    beat("adaptive replay x%g (2x static saturation)" % adaptive_mult)
    adaptive = play(adaptive_mult, dyn=True, tenant_slos={"tip": slo_ms})

    frag = {
        "slo_replay": {
            "trace": {
                "slots": len(slots),
                "slot_s": slot_s,
                "seed": trace["seed"],
                "tip_slo_ms": slo_ms,
                "warmup_s": round(warmup_s, 3),
            },
            "model": {"base_us": base_us, "lane_us": lane_us},
            "static": static_runs,
            "static_saturation_mult": m_sat,
            "static_saturated": saturated,
            "adaptive_mult": adaptive_mult,
            "adaptive": adaptive,
        }
    }

    # the section's whole point: at double the load that saturates the
    # static config, the controller still holds the declared budget —
    # and not by shedding the tenant into the floor
    tip = adaptive["tip"]
    served_frac = tip["served"] / max(1, tip["scored"])
    if tip["p99_ms"] is None or tip["p99_ms"] > slo_ms:
        raise AssertionError(
            "adaptive config failed to hold tip p99 within %dms at x%g "
            "(2x static saturation): %r" % (slo_ms, adaptive_mult, frag)
        )
    if served_frac < 0.7:
        raise AssertionError(
            "adaptive config held p99 only by shedding (served %.0f%% "
            "< 70%%): %r" % (served_frac * 100.0, frag)
        )
    return frag


def run_light_serve(beat) -> dict:
    """PR 9 serving-tier benchmark: an in-process lightd (selector event
    loop + verified-header cache) under BENCH_LIGHT_SERVE_CLIENTS
    concurrent simulated light clients.

    Cold phase: one ascending sweep over the chain — every height is a
    cache miss paying a real skipping verification (one scheduler
    super-batch per bisection round). Warm phase: the selector load
    generator (bench/light_loadgen.py) replays Zipf-distributed heights
    over the now-populated cache. The headline is the warm/cold
    headers/s ratio (acceptance: >= 20x) plus warm p50/p99 and the
    cache hit rate."""
    import json
    import random
    import urllib.request

    from bench.light_loadgen import run_load, zipf_heights
    from bench.workload import build_light_block_chain
    from tendermint_tpu.encoding.canonical import Timestamp
    from tendermint_tpu.libs.metrics import (
        EvloopMetrics,
        LightMetrics,
        Registry,
    )
    from tendermint_tpu.light.client import LightClient, TrustOptions
    from tendermint_tpu.light.lightd import LightServer
    from tendermint_tpu.light.provider import MemoryProvider

    n_clients = env_int("BENCH_LIGHT_SERVE_CLIENTS", 1000)
    n_heights = env_int("BENCH_LIGHT_SERVE_HEIGHTS", 64)
    n_vals = env_int("BENCH_LIGHT_SERVE_VALS", 8)
    n_requests = env_int("BENCH_LIGHT_SERVE_REQUESTS", 5000)

    beat("chain fixture heights=%d vals=%d" % (n_heights, n_vals))
    blocks, chain_id = build_light_block_chain(n_heights, n_vals)
    now = lambda: Timestamp.from_unix_ns(  # noqa: E731
        1_700_000_000_000_000_000 + (n_heights + 60) * 1_000_000_000
    )
    client = LightClient(
        chain_id,
        TrustOptions(period=86400.0, height=1, hash=blocks[0].hash()),
        MemoryProvider(chain_id, blocks),
        [],
        now=now,
    )
    reg = Registry()
    metrics = LightMetrics(reg)
    srv = LightServer(
        client, metrics=metrics, registry=reg,
        evloop_metrics=EvloopMetrics(reg),
    )
    srv.start()
    host, port = srv.address
    try:
        def rpc(method, params):
            req = json.dumps(
                {"jsonrpc": "2.0", "id": 1, "method": method,
                 "params": params}
            ).encode()
            with urllib.request.urlopen(
                urllib.request.Request(
                    srv.url, data=req,
                    headers={"Content-Type": "application/json"},
                ),
                timeout=60,
            ) as resp:
                return json.loads(resp.read())

        beat("warmup (first verification compiles)")
        out = rpc("light_header", {"height": 2})
        assert "result" in out, out

        beat("cold sweep heights=3..%d" % n_heights)
        t0 = time.perf_counter()
        for h in range(3, n_heights + 1):
            out = rpc("light_header", {"height": h})
            assert "result" in out, out
            if h % 16 == 0:
                beat("cold sweep at height %d" % h)
        cold_s = time.perf_counter() - t0
        cold_rate = (n_heights - 2) / cold_s if cold_s > 0 else 0.0

        beat("warm loadgen clients=%d requests=%d" % (n_clients, n_requests))
        rng = random.Random(4242)
        per_client = max(1, n_requests // n_clients)
        sequences = [
            zipf_heights(rng, range(1, n_heights + 1), per_client)
            for _ in range(n_clients)
        ]
        t0 = time.perf_counter()
        load = run_load(host, port, sequences, beat=beat)
        warm_s = time.perf_counter() - t0
        lat = load["latencies"]
        warm_rate = load["completed"] / warm_s if warm_s > 0 else 0.0
        stats = srv.cache.stats()
        return {
            "light_serve": {
                "clients": load["clients"],
                "heights": n_heights,
                "vals": n_vals,
                "cold_headers_per_s": round(cold_rate, 2),
                "warm_headers_per_s": round(warm_rate, 1),
                "warm_vs_cold_x": round(warm_rate / cold_rate, 1)
                if cold_rate > 0
                else None,
                "warm_requests": load["completed"],
                "errors": load["errors"],
                "warm_p50_ms": round(lat[len(lat) // 2] * 1e3, 3)
                if lat
                else None,
                "warm_p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 3)
                if lat
                else None,
                "cache_hit_rate": round(stats["hit_rate"], 4),
                "cache_entries": stats["entries"],
            }
        }
    finally:
        srv.stop()


def run_multichip(beat) -> dict:
    """Lane-axis sharded verification scaling curve (parallel/sharding):
    ROADMAP item 1's scaling axis, measured as its own section so a sick
    mesh cannot take the single-chip evidence down with it. Verifies the
    SAME workload on 1/2/4/8-device meshes (clipped to what the backend
    exposes) and reports per-count throughput plus aggregate speedup and
    scaling efficiency at the widest mesh. On a CPU backend the parent
    injects ``--xla_force_host_platform_device_count`` so the virtual
    8-mesh is exercised — that proves the sharding machinery end to end,
    but all 8 virtual devices share the host cores, so CPU "speedup" is
    a correctness signal, not a performance one."""
    import jax
    import numpy as np

    from tendermint_tpu.parallel import sharding

    backend = jax.default_backend()
    # 8192 lanes saturate an 8-chip mesh (1024/chip, the second-largest
    # bucket); the CPU default stays small so the virtual mesh's
    # 4 compiles fit the smoke budget.
    lanes = env_int(
        "BENCH_MULTICHIP_LANES", 1024 if backend == "cpu" else 8192
    )
    rounds = env_int("BENCH_MULTICHIP_ROUNDS", 2)
    beat("mesh discovery")
    avail = jax.device_count()
    wanted = [
        int(tok)
        for tok in os.environ.get(
            "BENCH_MULTICHIP_DEVICES", "1,2,4,8"
        ).split(",")
        if tok.strip()
    ]
    counts = sorted({k for k in wanted if 1 <= k <= avail})
    if not counts:
        counts = [1]
    beat("workload lanes=%d devices_available=%d" % (lanes, avail))
    rng = np.random.default_rng(7)
    pks, msgs, sigs = make_workload(rng, lanes)
    sigs[3] = b"\x01" * 64  # one injected bad lane: verdicts must be real

    sigs_per_s = {}
    ok_all = True
    for k in counts:
        mesh = sharding.make_mesh(k)
        beat("warmup/compile devices=%d" % k)
        # min_lanes=0: measure the sharded path at every count,
        # including k=1 and small CPU workloads under the bypass floor.
        oks = sharding.verify_batch_sharded(
            pks, msgs, sigs, mesh=mesh, min_lanes=0
        )
        ok_all = ok_all and (
            (not oks[3]) and all(oks[:3]) and all(oks[4:])
        )
        best = float("inf")
        for r in range(rounds):
            beat("measured pass devices=%d round=%d" % (k, r + 1))
            t0 = time.perf_counter()
            sharding.verify_batch_sharded(
                pks, msgs, sigs, mesh=mesh, min_lanes=0
            )
            best = min(best, time.perf_counter() - t0)
        sigs_per_s[str(k)] = round(lanes / best, 1)
    k_max = counts[-1]
    base = sigs_per_s[str(counts[0])]
    speedup = (
        round(sigs_per_s[str(k_max)] / base, 2) if base > 0 else None
    )
    efficiency = (
        round(speedup / k_max, 3)
        if speedup is not None and counts[0] == 1
        else None
    )
    return {
        "multichip": {
            "backend": backend,
            "lanes": lanes,
            "devices_available": avail,
            "devices_measured": counts,
            "sigs_per_s": sigs_per_s,
            "speedup_max_devices": speedup,
            "scaling_efficiency": efficiency,
            "ok": bool(ok_all),
        }
    }


def run_host_ref(beat) -> dict:
    """Pure-python ZIP-215 reference throughput (crypto/ed25519_ref) —
    the no-jax floor every device number is compared against, and the
    section the chaos tests / CI smoke lean on because it cannot be
    taken down by the accelerator stack."""
    from tendermint_tpu.crypto import ed25519_ref

    n = env_int("BENCH_HOST_REF_SIGS", 12)
    beat("keygen n=%d" % n)
    triples = []
    for i in range(n):
        sk, pk = ed25519_ref.generate_keypair()
        msg = b"bench-host-ref-%d" % i
        triples.append((pk, msg, ed25519_ref.sign(sk, msg)))
    beat("verify n=%d" % n)
    t0 = time.perf_counter()
    oks = [ed25519_ref.verify_zip215(pk, m, s) for pk, m, s in triples]
    dt = time.perf_counter() - t0
    assert all(oks), "host reference verification must pass"
    return {"host_ref": {"sigs": n, "sigs_per_s": round(n / dt, 1)}}


def run_chaos(beat) -> dict:
    """Fault injection (BENCH_CHAOS): the section that misbehaves on
    purpose so tests and the CI smoke stage can prove the runner
    contains it. Modes:

    - ``ok``         complete normally
    - ``crash``      raise (child exits non-zero)
    - ``err:<msg>``  raise RuntimeError(msg) — classification tests
    - ``sigkill``    SIGKILL self mid-run (torn child, no traceback)
    - ``hang``       beat once, then go silent — heartbeat-watchdog prey
    - ``slow:<s>``   beat dutifully for <s> seconds — wall-timeout prey
    """
    import signal

    mode = os.environ.get(CHAOS_ENV, "ok")
    beat("chaos mode=%s" % mode)
    if mode == "ok":
        return {"chaos": {"mode": "ok"}}
    if mode == "crash":
        raise RuntimeError("injected chaos crash")
    if mode.startswith("err:"):
        raise RuntimeError(mode[4:])
    if mode == "sigkill":
        os.kill(os.getpid(), signal.SIGKILL)
    if mode == "hang":
        # Deliberate heartbeat silence: the watchdog, not this sleep,
        # decides when this section dies.
        time.sleep(3600)
        return {"chaos": {"mode": "hang-survived"}}
    if mode.startswith("slow:"):
        deadline = time.monotonic() + float(mode[5:])
        i = 0
        while time.monotonic() < deadline:
            i += 1
            beat("slow tick %d" % i)
            time.sleep(0.1)
        return {"chaos": {"mode": mode, "ticks": i}}
    raise ValueError("unknown BENCH_CHAOS mode %r" % mode)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_ALL = (
    Section(
        "throughput",
        run_throughput,
        degrade=(("BENCH_BATCH", 8192, 256), ("BENCH_ROUNDS", 5, 2)),
    ),
    Section("stages", run_stages, degrade=(("BENCH_BATCH", 8192, 256),)),
    Section(
        "verify_commit",
        run_verify_commit,
        degrade=(("BENCH_COMMIT_VALS", 10_000, 100),),
        skip_env=("BENCH_SKIP_COMMIT",),
    ),
    Section(
        "light_client",
        run_light_client,
        degrade=(
            ("BENCH_LIGHT_HEADERS", 16, 4),
            ("BENCH_LIGHT_VALS", 1000, 50),
        ),
        skip_env=("BENCH_SKIP_EXTRAS",),
    ),
    Section(
        "blocksync",
        run_blocksync,
        degrade=(("BENCH_SYNC_BLOCKS", 32, 4), ("BENCH_SYNC_VALS", 500, 50)),
        skip_env=("BENCH_SKIP_EXTRAS",),
    ),
    Section(
        "cache",
        run_cache,
        degrade=(("BENCH_CACHE_VALS", 100, 25),),
        skip_env=("BENCH_SKIP_CACHE",),
    ),
    Section(
        "verifyd",
        run_verifyd,
        degrade=(
            ("BENCH_VERIFYD_LANES", 64, 16),
            ("BENCH_VERIFYD_ROUNDS", 8, 2),
        ),
        skip_env=("BENCH_SKIP_VERIFYD",),
    ),
    Section(
        "verifyd_tenants",
        run_verifyd_tenants,
        needs_jax=False,
        degrade=(
            ("BENCH_TENANTS_ROUNDS", 30, 10),
            ("BENCH_TENANTS_FLOODS", 4, 1),
        ),
        skip_env=("BENCH_SKIP_VERIFYD_TENANTS",),
    ),
    Section(
        "verifyd_shm",
        run_verifyd_shm,
        needs_jax=False,
        degrade=(
            ("BENCH_SHM_LANES", 8192, 1024),
            ("BENCH_SHM_ROUNDS", 12, 4),
        ),
        skip_env=("BENCH_SKIP_VERIFYD_SHM",),
    ),
    Section(
        "verifyd_fleet",
        run_verifyd_fleet,
        # the disjointness proof rides the server's REAL hot-key pin
        # path (ops/resident), so the shard children need the ops
        # engine importable even though the verifier is modeled
        degrade=(
            ("BENCH_FLEET_MAX_SHARDS", 4, 2),
            ("BENCH_FLEET_ROUNDS", 6, 2),
            ("BENCH_FLEET_LANES", 16, 8),
        ),
        skip_env=("BENCH_SKIP_VERIFYD_FLEET",),
    ),
    Section(
        "latency_attrib",
        run_latency_attrib,
        needs_jax=False,
        degrade=(
            ("BENCH_ATTRIB_ROUNDS", 24, 8),
            ("BENCH_ATTRIB_LANES", 32, 8),
        ),
        skip_env=("BENCH_SKIP_LATENCY_ATTRIB",),
    ),
    Section(
        "slo_replay",
        run_slo_replay,
        needs_jax=False,
        # cheapen by shortening the rate LADDER, never the trace: a
        # trace shorter than the controller's ramp window measures
        # cold-start, and the section's own assertion would fail it
        degrade=(("BENCH_SLO_SAT_STEPS", 4, 1),),
        skip_env=("BENCH_SKIP_SLO_REPLAY",),
    ),
    Section(
        "light_serve",
        run_light_serve,
        degrade=(
            ("BENCH_LIGHT_SERVE_CLIENTS", 1000, 100),
            ("BENCH_LIGHT_SERVE_HEIGHTS", 64, 16),
            ("BENCH_LIGHT_SERVE_REQUESTS", 5000, 500),
        ),
        skip_env=("BENCH_SKIP_LIGHT_SERVE",),
    ),
    Section(
        "multichip",
        run_multichip,
        degrade=(
            ("BENCH_MULTICHIP_LANES", 8192, 512),
            ("BENCH_MULTICHIP_ROUNDS", 2, 1),
        ),
        skip_env=("BENCH_SKIP_MULTICHIP",),
        # Virtual 8-mesh on the host platform; inert on a real device
        # backend (the flag only shapes the CPU platform).
        extra_env=(
            (
                "XLA_FLAGS",
                "--xla_force_host_platform_device_count=8",
            ),
        ),
    ),
    Section("host_ref", run_host_ref, needs_jax=False),
    Section("_chaos", run_chaos, needs_jax=False),
)

REGISTRY: Dict[str, Section] = {s.name: s for s in _ALL}

# Registry order is merge order (bench/results.py) and run order.
ORDER = tuple(s.name for s in _ALL)


def default_plan() -> Tuple[str, ...]:
    """The sections a plain ``python bench.py`` runs: everything except
    the chaos hook (present only when BENCH_CHAOS asks for it), minus
    legacy BENCH_SKIP_* opt-outs, or exactly BENCH_SECTIONS when set."""
    explicit = os.environ.get("BENCH_SECTIONS", "").strip()
    if explicit:
        names = [n.strip() for n in explicit.split(",") if n.strip()]
        unknown = [n for n in names if n not in REGISTRY]
        if unknown:
            raise KeyError("unknown bench section(s): %s" % ", ".join(unknown))
        return tuple(names)
    plan = []
    for s in _ALL:
        if s.name == "_chaos" and not os.environ.get(CHAOS_ENV):
            continue
        if any(os.environ.get(e) == "1" for e in s.skip_env):
            continue
        plan.append(s.name)
    return tuple(plan)


def get(name: str) -> Section:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            "unknown bench section %r (have: %s)" % (name, ", ".join(ORDER))
        ) from None

"""Verifyd federation (tendermint_tpu/verifyd/federation.py, ISSUE 19).

Pins the routing subsystem's load-bearing properties: the consistent-
hash ring is deterministic (same key, same shard, forever) and
minimal-remap (losing a shard moves ONLY that shard's keys, each to
its next preference rung); committee digests are order-independent;
a FederationClient keeps whole committees on one shard, walks the
failover ladder on sheds and dead shards (host oracle last — never a
silent drop, never an unexplained verdict), bumps ``route_epoch`` on
every membership flip, and merges per-shard tenant SLO views into one
fleet view. The new wire fields (request 9/10, response 6, slab header
v4) round-trip and stay absent for pre-federation peers.
"""

import json
import threading
import time

import pytest

from tests.test_verifyd import host_verify, make_lanes
from tendermint_tpu.verifyd import federation, protocol
from tendermint_tpu.verifyd.client import (
    VerifydClient,
    VerifydRejectedError,
)
from tendermint_tpu.verifyd.federation import (
    FederationClient,
    HashRing,
    digest_validator_set,
)
from tendermint_tpu.verifyd.server import VerifydServer


def make_keys(n, tag=b"fed"):
    """n distinct synthetic 32-byte pubkeys (ring tests never verify)."""
    import hashlib

    return [
        hashlib.sha256(b"%s-%d" % (tag, i)).digest() for i in range(n)
    ]


def start_shards(n, verify_fns=None, **kw):
    """n in-process shard servers; returns (servers, addrs)."""
    servers, addrs = [], []
    for sid in range(n):
        fn = verify_fns[sid] if verify_fns else host_verify
        srv = VerifydServer(
            verify_fn=fn, max_batch=64, max_delay=0.002, shard_id=sid, **kw
        )
        srv.start()
        h, p = srv.address
        servers.append(srv)
        addrs.append(f"{h}:{p}")
    return servers, addrs


# --- consistent-hash ring ---------------------------------------------------


class TestHashRing:
    def test_same_key_always_same_shard(self):
        ring = HashRing(range(4))
        again = HashRing(range(4))
        for key in make_keys(64):
            assert ring.route(key) == again.route(key)
            assert ring.preference(key) == again.preference(key)

    def test_preference_is_a_permutation_of_shards(self):
        ring = HashRing(range(4))
        for key in make_keys(32):
            pref = ring.preference(key)
            assert sorted(pref) == [0, 1, 2, 3]

    def test_split_is_near_even(self):
        ring = HashRing(range(4))
        counts = {s: 0 for s in range(4)}
        for key in make_keys(1000):
            counts[ring.route(key)] += 1
        # 64 vnodes/shard: no shard should starve or hog
        assert min(counts.values()) >= 100
        assert max(counts.values()) <= 450

    def test_minimal_remap_on_shard_loss(self):
        """Killing shard d moves ONLY d's keys, each to its next
        preference rung — the property that makes failover cheap."""
        ring = HashRing(range(4))
        keys = make_keys(200)
        for dead in range(4):
            for key in keys:
                pref = ring.preference(key)
                routed = ring.route(key, dead={dead})
                if pref[0] != dead:
                    assert routed == pref[0]  # unaffected key: no remap
                else:
                    assert routed == pref[1]  # victim key: next rung

    def test_all_dead_returns_primary(self):
        ring = HashRing(range(2))
        key = make_keys(1)[0]
        assert ring.route(key, dead={0, 1}) == ring.preference(key)[0]


def test_digest_validator_set_order_independent():
    keys = make_keys(4)
    d = digest_validator_set(keys)
    assert digest_validator_set(list(reversed(keys))) == d
    assert digest_validator_set(keys[2:] + keys[:2]) == d
    assert digest_validator_set(keys[:3]) != d


# --- client-side routing ----------------------------------------------------


class TestRouting:
    def test_committee_rides_one_shard(self):
        """Every lane of a noted committee lands on the SAME shard, and
        repeat calls land on the same shard again."""
        seen = [set(), set()]

        def recorder(sid):
            def fn(pks, msgs, sigs):
                seen[sid].update(bytes(p) for p in pks)
                return [True] * len(pks)

            return fn

        servers, addrs = start_shards(2, verify_fns=[recorder(0), recorder(1)])
        fed = FederationClient(addrs)
        try:
            committees = [make_keys(4, tag=b"c%d" % c) for c in range(6)]
            for keys in committees:
                fed.note_validator_set(keys)
            pks = [pk for keys in committees for pk in keys]
            msgs = [b"m%d" % i for i in range(len(pks))]
            sigs = [b"\x07" * 64] * len(pks)
            assert fed.verify(pks, msgs, sigs) == [True] * len(pks)
            first = [set(s) for s in seen]
            assert fed.verify(pks, msgs, sigs) == [True] * len(pks)
            assert [set(s) for s in seen] == first  # stable placement
            for keys in committees:
                owners = {
                    sid for sid in range(2) if set(keys) & seen[sid]
                }
                assert len(owners) == 1  # never split across shards
            # both shards carry traffic and their slices are disjoint
            assert seen[0] and seen[1]
            assert not (seen[0] & seen[1])
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_unknown_key_routes_by_its_own_digest(self):
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs)
        try:
            pk = make_keys(1)[0]
            assert fed.routing_key(pk) == pk
            digest = fed.note_validator_set([pk])
            assert fed.routing_key(pk) == digest
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_requests_stamp_shard_and_epoch_on_the_wire(self):
        """The server sees the routed shard id (misroutes stay 0) and
        the router's epoch; a deliberately mis-stamped request is
        counted but still served — routing is placement advice."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs)
        try:
            pks, msgs, sigs = make_lanes(3)
            assert fed.verify(pks, msgs, sigs) == [True] * 3
            sid = fed.shard_for(pks[0])
            stats = servers[sid].stats()
            assert stats["misroutes"] == 0
            assert stats["route_epoch_seen"] == fed.route_epoch
            # cross-wire a request to the OTHER shard
            other = 1 - sid
            c = VerifydClient(addrs[other], fallback=False, shard_id=sid)
            assert c.verify(pks, msgs, sigs) == [True] * 3
            c.close()
            assert servers[other].stats()["misroutes"] == 1
        finally:
            fed.close()
            for s in servers:
                s.stop()


# --- failover ladder (CI explore target: TestFailover) ----------------------


class TestFailover:
    def test_dead_shard_reroutes_to_next_rung(self):
        """SIGKILL-equivalent (stopped server): the dead shard's keys
        re-route to the survivor, the dead shard is quarantined, and
        the route epoch bumps so servers can spot stale maps."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, dead_retry_s=60.0)
        try:
            pks, msgs, sigs = make_lanes(4)
            committee = list(dict.fromkeys(pks))
            fed.note_validator_set(committee)
            victim = fed.shard_for(pks[0])
            epoch0 = fed.route_epoch
            servers[victim].stop()
            assert fed.verify(pks, msgs, sigs) == [True] * 4
            st = fed.stats()
            assert st["failovers"] >= 1
            assert st["rerouted_lanes"] >= 4
            assert st["host_fallback_lanes"] == 0
            assert fed.alive_shards() == [1 - victim]
            assert fed.route_epoch > epoch0
            # every shard client carries the bumped epoch on field 10
            for c in fed._clients:
                assert c.route_epoch == fed.route_epoch
            # survivor now owns the victim's keys
            assert fed.shard_for(pks[0]) == 1 - victim
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_dead_shard_revives_after_quarantine(self):
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, dead_retry_s=0.05)
        try:
            pks, msgs, sigs = make_lanes(4, seed=1)
            fed.note_validator_set(list(dict.fromkeys(pks)))
            victim = fed.shard_for(pks[0])
            h, p = servers[victim].address
            servers[victim].stop()
            assert fed.verify(pks, msgs, sigs) == [True] * 4
            # quarantined until a successful probe revives it (the
            # _dead entry outlives its expiry time, so this holds no
            # matter how slowly the sanitizer schedules us)
            assert victim in fed._dead
            # restart on the same port; the expired quarantine lets the
            # next call probe it, and success revives the shard
            servers[victim] = VerifydServer(
                verify_fn=host_verify, host=h, port=p,
                max_batch=64, max_delay=0.002, shard_id=victim,
            )
            servers[victim].start()
            time.sleep(0.1)  # quarantine expires
            epoch_dead = fed.route_epoch
            assert fed.verify(pks, msgs, sigs) == [True] * 4
            assert victim not in fed._dead
            assert victim in fed.alive_shards()
            assert fed.route_epoch > epoch_dead
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_shed_walks_the_ladder(self):
        """A shard that sheds (RESOURCE_EXHAUSTED) keeps its quarantine
        clean — it is browning out, not dead — but the group's lanes
        re-route to the next rung and still verify."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, failover_backoff_s=0.001)
        try:
            pks, msgs, sigs = make_lanes(4, seed=2)
            fed.note_validator_set(list(dict.fromkeys(pks)))
            victim = fed.shard_for(pks[0])

            def always_shed(*a, **kw):
                raise VerifydRejectedError(
                    protocol.STATUS_RESOURCE_EXHAUSTED, "brownout"
                )

            fed._clients[victim].verify = always_shed
            assert fed.verify(pks, msgs, sigs) == [True] * 4
            st = fed.stats()
            assert st["failovers"] >= 1
            assert st["host_fallback_lanes"] == 0
            # shed != dead: the shard stays in the alive set
            assert victim in fed.alive_shards()
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_host_oracle_is_the_last_rung(self):
        """With every shard dead the verdicts still arrive — REAL
        host-oracle verdicts, positionally correct for a bad lane —
        and the fallback is accounted, never silent."""
        servers, addrs = start_shards(2)
        for s in servers:
            s.stop()
        fed = FederationClient(addrs, failover_backoff_s=0.001, timeout=5.0)
        try:
            pks, msgs, sigs = make_lanes(5, seed=3, bad={2})
            got = fed.verify(pks, msgs, sigs)
            assert got == [True, True, False, True, True]
            assert fed.stats()["host_fallback_lanes"] == 5
            assert fed.alive_shards() == []
        finally:
            fed.close()

    def test_mixed_batch_verdicts_merge_positionally(self):
        """Two committees on different shards, interleaved lanes, one
        bad signature: the verdict vector maps back lane-for-lane."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs)
        try:
            a_pks, a_msgs, a_sigs = make_lanes(3, seed=4, bad={1})
            b_pks, b_msgs, b_sigs = make_lanes(3, seed=5)
            fed.note_validator_set([a_pks[0]])
            fed.note_validator_set([b_pks[0]])
            pks = [a_pks[0], b_pks[0], a_pks[1], b_pks[1], a_pks[2]]
            msgs = [a_msgs[0], b_msgs[0], a_msgs[1], b_msgs[1], a_msgs[2]]
            sigs = [a_sigs[0], b_sigs[0], a_sigs[1], b_sigs[1], a_sigs[2]]
            assert fed.verify(pks, msgs, sigs) == [
                True, True, False, True, True,
            ]
        finally:
            fed.close()
            for s in servers:
                s.stop()


# --- gossip / fleet stats ---------------------------------------------------


class TestFleetStats:
    def test_server_stats_snapshot_over_the_wire(self):
        servers, addrs = start_shards(1)
        c = VerifydClient(addrs[0], fallback=False)
        try:
            pks, msgs, sigs = make_lanes(2, seed=6)
            assert c.verify(pks, msgs, sigs) == [True] * 2
            snap = c.server_stats()
            assert snap["shard_id"] == 0
            assert snap["stats"]["requests_served"] >= 1
            assert isinstance(snap["pinned_keys"], list)
            assert "brownout" in snap and "tenants" in snap
        finally:
            c.close()
            servers[0].stop()

    def test_refresh_marks_unreachable_shards_dead(self):
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, dead_retry_s=60.0)
        try:
            servers[1].stop()
            snaps = fed.refresh(timeout=1.0)
            assert 0 in snaps and 1 not in snaps
            assert fed.alive_shards() == [0]
        finally:
            fed.close()
            servers[0].stop()

    def test_fleet_tenants_merges_shard_views(self):
        """The fleet view a tenant reasons about: p99 is the fleet MAX,
        slo the tightest bound, counters fleet SUMS, shedding an OR —
        the closed rung of ROADMAP item 5."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs)
        try:
            with fed._mtx:
                fed._gossip = {
                    0: {
                        "tenants": {
                            "chain-a": {
                                "p99_ms": 12.0, "slo_ms": 250,
                                "slo_sheds": 3, "sheds": 4,
                                "lanes": 100, "host_direct": 1,
                                "slo_shedding": 0,
                            }
                        }
                    },
                    1: {
                        "tenants": {
                            "chain-a": {
                                "p99_ms": 40.0, "slo_ms": 100,
                                "slo_sheds": 2, "sheds": 1,
                                "lanes": 50, "host_direct": 0,
                                "slo_shedding": 1,
                            }
                        }
                    },
                }
            view = fed.fleet_tenants()["chain-a"]
            assert view["p99_ms"] == 40.0
            assert view["slo_ms"] == 100
            assert view["slo_sheds"] == 5
            assert view["sheds"] == 5
            assert view["lanes"] == 150
            assert view["host_direct"] == 1
            assert view["slo_shedding"] == 1
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_refresh_drops_and_counts_inflated_gossip_snapshot(self):
        """A misbehaving shard's oversized STATS snapshot must not
        balloon the fleet view: before the gossip caps, refresh() stored
        whatever JSON the shard returned. Now the snapshot is dropped
        whole and counted in gossip_rejects, while the shard itself
        stays alive (it answered; only its gossip is rejected)."""
        from tendermint_tpu.verifyd import federation as fedmod

        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, dead_retry_s=60.0)
        try:
            inflated = {
                "tenants": {
                    f"t{i}": {"p99_ms": 1.0}
                    for i in range(fedmod.MAX_GOSSIP_TENANTS + 1)
                }
            }
            fed._clients[1].server_stats = (
                lambda timeout=2.0, _s=inflated: _s
            )
            snaps = fed.refresh(timeout=2.0)
            assert 0 in snaps and 1 not in snaps
            assert fed.gossip_rejects == 1
            assert fed.alive_shards() == [0, 1]
            # the rejected snapshot's tenants never reach the fleet view
            assert "t0" not in fed.fleet_tenants()
            assert fed.stats()["gossip_rejects"] == 1
        finally:
            fed.close()
            for s in servers:
                s.stop()

    def test_sanitize_snapshot_caps(self):
        from tendermint_tpu.verifyd import federation as fedmod

        sanitize = FederationClient._sanitize_snapshot
        ok = {"tenants": {"a": {"p99_ms": 1.0}}, "brownout": {}}
        assert sanitize(ok) is ok
        with pytest.raises(ValueError, match="tenants"):
            sanitize({
                "tenants": {
                    f"t{i}": {} for i in range(fedmod.MAX_GOSSIP_TENANTS + 1)
                }
            })
        with pytest.raises(ValueError, match="B$"):
            sanitize({"pad": "x" * fedmod.MAX_GOSSIP_SNAPSHOT_BYTES})
        with pytest.raises(ValueError, match="not a dict"):
            sanitize(["not", "a", "dict"])

    def test_slo_propagates_to_every_shard(self):
        """Satellite 1: one ``--tenant-slo`` reaches ALL shards
        identically (wire field 8), so the merged fleet view carries
        the same budget each shard enforced locally."""
        servers, addrs = start_shards(2)
        fed = FederationClient(addrs, tenant="chain-slo", slo_ms=250)
        try:
            committees = [make_keys(4, tag=b"s%d" % c) for c in range(6)]
            for keys in committees:
                fed.note_validator_set(keys)
            pks = [pk for keys in committees for pk in keys]
            msgs = [b"slo-%d" % i for i in range(len(pks))]
            sigs = [b"\x08" * 64] * len(pks)

            # noop verifiers: the synthetic lanes aren't real signatures
            for s in servers:
                s.stop()
            servers, addrs2 = start_shards(
                2, verify_fns=[lambda *a: [True] * len(a[0])] * 2
            )
            fed.close()
            fed = FederationClient(addrs2, tenant="chain-slo", slo_ms=250)
            for keys in committees:
                fed.note_validator_set(keys)
            assert fed.verify(pks, msgs, sigs) == [True] * len(pks)
            served = [
                s for s in servers
                if s.tenant_stats().get("chain-slo", {}).get("lanes", 0) > 0
            ]
            assert len(served) == 2  # both shards saw the tenant...
            for s in served:  # ...with the SAME budget
                assert s.tenant_stats()["chain-slo"]["slo_ms"] == 250
        finally:
            fed.close()
            for s in servers:
                s.stop()


# --- wire fields ------------------------------------------------------------


class TestWireFields:
    def test_request_shard_and_epoch_roundtrip(self):
        req = protocol.VerifyRequest(
            kind=protocol.KIND_RAW,
            pks=[b"\x01" * 32],
            msgs=[b"m"],
            sigs=[b"\x02" * 64],
            shard_id=3,
            route_epoch=17,
        )
        got = protocol.decode_request(
            protocol.encode_request(req)
        )
        assert got.shard_id == 3
        assert got.route_epoch == 17

    def test_unrouted_request_omits_the_fields(self):
        """shard_id=-1 / epoch=0 must be wire-IDENTICAL to a
        pre-federation client: absent, not zero-valued."""
        req = protocol.VerifyRequest(
            kind=protocol.KIND_RAW,
            pks=[b"\x01" * 32],
            msgs=[b"m"],
            sigs=[b"\x02" * 64],
        )
        wire = protocol.encode_request(req)
        routed = protocol.encode_request(
            protocol.VerifyRequest(
                kind=protocol.KIND_RAW,
                pks=[b"\x01" * 32],
                msgs=[b"m"],
                sigs=[b"\x02" * 64],
                shard_id=0,
                route_epoch=1,
            )
        )
        assert len(routed) > len(wire)
        got = protocol.decode_request(wire)
        assert got.shard_id == -1
        assert got.route_epoch == 0

    def test_response_shard_id_roundtrip_and_omission(self):
        resp = protocol.VerifyResponse(
            status=protocol.STATUS_OK, verdicts=[True], shard_id=2
        )
        got = protocol.decode_response(
            protocol.encode_response(resp)
        )
        assert got.shard_id == 2
        bare = protocol.decode_response(
            protocol.encode_response(
                protocol.VerifyResponse(
                    status=protocol.STATUS_OK, verdicts=[True]
                )
            )
        )
        assert bare.shard_id == -1

    def test_shard_id_zero_survives_the_shift(self):
        """Shard 0 is a VALID identity: the +1 wire shift must not
        collapse it into 'absent'."""
        req = protocol.VerifyRequest(
            kind=protocol.KIND_RAW,
            pks=[b"\x01" * 32],
            msgs=[b"m"],
            sigs=[b"\x02" * 64],
            shard_id=0,
        )
        got = protocol.decode_request(
            protocol.encode_request(req)
        )
        assert got.shard_id == 0


# --- process-wide backend wiring --------------------------------------------


class TestBackendWiring:
    def test_single_address_is_not_a_federation(self, monkeypatch):
        monkeypatch.setenv(federation.SHARDS_ENV, "127.0.0.1:1")
        federation.reset_federation()
        try:
            assert federation.federation_client() is None
            assert federation.federation_backend() is None
        finally:
            federation.reset_federation()

    def test_env_configures_and_caches_the_client(self, monkeypatch):
        servers, addrs = start_shards(2)
        monkeypatch.setenv(federation.SHARDS_ENV, ",".join(addrs))
        federation.reset_federation()
        try:
            fed = federation.federation_client()
            assert fed is not None
            assert federation.federation_client() is fed  # cached
            pks, msgs, sigs = make_lanes(3, seed=7)
            backend = federation.federation_backend()
            assert backend(pks, msgs, sigs) == [True] * 3
        finally:
            federation.reset_federation()
            for s in servers:
                s.stop()

    def test_federation_outranks_single_remote(self, monkeypatch):
        from tendermint_tpu.crypto import batch as crypto_batch

        servers, addrs = start_shards(2)
        monkeypatch.setenv(federation.SHARDS_ENV, ",".join(addrs))
        monkeypatch.setenv(
            "TENDERMINT_TPU_VERIFY_REMOTE", "127.0.0.1:1"
        )
        federation.reset_federation()
        try:
            backend = crypto_batch.remote_verify_backend()
            assert backend is not None
            pks, msgs, sigs = make_lanes(3, seed=8)
            # the dead single-remote address would fail; the federation
            # serves — proof the digest router owns placement
            assert backend(pks, msgs, sigs) == [True] * 3
        finally:
            federation.reset_federation()
            for s in servers:
                s.stop()

    @pytest.mark.parametrize("live_before", [True, False], ids=["recognised_set", "new_set"])
    @pytest.mark.parametrize("federated", [False, True], ids=["unfederated", "federated"])
    def test_set_hook_asks_for_a_client_before_it_builds_the_keys(
        self, monkeypatch, federated, live_before
    ):
        """``crypto.batch.note_validator_set``: with no federation the
        routing hook builds no key list (the only one built is the
        registry's own, for a set it had to register); with a client
        it hands over the sorted keys on every call, whether the set
        was recognised as live or not."""
        from tendermint_tpu.crypto import batch as crypto_batch
        from tendermint_tpu.ops import precompute
        from tests.helpers import make_validators

        _, vset = make_validators(5)
        want = sorted(v.pub_key.bytes() for v in vset.validators)
        servers = []
        monkeypatch.delenv(federation.SHARDS_ENV, raising=False)
        if federated:
            servers, addrs = start_shards(2)
            monkeypatch.setenv(federation.SHARDS_ENV, ",".join(addrs))
        federation.reset_federation()
        precompute.reset()
        built, handed = [], []
        real_keys = precompute._vset_ed25519_keys
        monkeypatch.setattr(
            precompute, "_vset_ed25519_keys", lambda v: built.append(1) or real_keys(v)
        )
        monkeypatch.setattr(
            FederationClient,
            "note_validator_set",
            lambda self, pubkeys: handed.append(list(pubkeys)),
        )
        try:
            if live_before:
                assert precompute.activate_validator_set(vset) == (True, False)
                del built[:]
            noted = crypto_batch.note_validator_set(vset.copy())
            assert noted == ((False, True) if live_before else (True, False))
            registry = 0 if live_before else 1
            assert len(built) == registry + federated
            assert handed == ([want] if federated else [])
            # every later call: nothing built unfederated, the same call federated
            assert crypto_batch.note_validator_set(vset) == (False, True)
            assert len(built) == registry + 2 * federated
            assert handed == ([want, want] if federated else [])
        finally:
            federation.reset_federation()
            precompute.reset()
            for s in servers:
                s.stop()

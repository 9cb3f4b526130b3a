"""A commit over a committee of mixed key types on a mesh (PR 48): what
``mixed10k-x4`` does 10,000 signatures at a time on four chips, here at
520 validators on four of conftest.py's forced host devices, on the XLA
graph (the chip runs the Pallas kernels per shard; ``chip_smoke.py``
holds those, at the real widths).

One ``verify_commit`` whose two device sub-batches each pass the mesh
floor (``parallel/mesh.MIN_MESH_LANES``) sends two sharded chunks, the
ed25519 one and then the sr25519 one, verifies the secp256k1 lanes on
the host while both are in flight, and collects; its verdicts are the
plain reference's (``chipbench/reference_mixed.py``), lane for lane, and
a tampered lane of each key type is blamed at its index.

Shape discipline: every device run is 256 lanes of a kind over four
devices, a 64-lane slab each — the shapes the benchmark's tiny twin
(``chipbench/testdata/tiny-mixed-x4.json``) compiles too.
"""

from __future__ import annotations

import pytest

from chipbench import reference_mixed
from tendermint_tpu.crypto import hashing
from tendermint_tpu.ops.device_policy import shared as shared_health
from tendermint_tpu.parallel import mesh
from tendermint_tpu.types import validation
from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_mixed_validators, traced
from tests.test_validation_mixed import commit_lanes, multi_of

N_ED = N_SR = mesh.MIN_MESH_LANES
N_SECP = 8
DEVICES = 4
HEIGHT = 48


@pytest.fixture(autouse=True)
def _mesh_of_four(monkeypatch):
    """Opt back into sharding (conftest pins TENDERMINT_TPU_MESH=1 for
    the general suite), over four of the eight devices."""
    monkeypatch.setenv(mesh.MESH_ENV, str(DEVICES))
    mesh.manager.reset()
    shared_health.reset()
    yield
    mesh.manager.reset()
    shared_health.reset()


@pytest.fixture(scope="module")
def committee():
    privs, vset = make_mixed_validators(N_ED, N_SR, N_SECP)
    block_id = make_block_id(b"mesh-mixed")
    return privs, vset, block_id, make_commit(block_id, HEIGHT, 0, vset, privs)


def tampered(commit, idx):
    """``commit`` with one bit of the signature at ``idx`` flipped (a
    bit of s for every key type)."""
    import copy

    out = copy.deepcopy(commit)
    sig = bytearray(out.signatures[idx].signature)
    sig[40] ^= 0x01
    out.signatures[idx].signature = bytes(sig)
    return out


def named(events, name):
    return [e for e in events if e["name"] == name]


def test_a_mixed_commit_sends_two_sharded_sub_batches_and_verifies_the_host_lanes_between(committee):
    _, vset, block_id, commit = committee
    raised, events = traced(lambda: validation.verify_commit(CHAIN_ID, vset, block_id, HEIGHT, commit))
    assert raised is None
    assert not named(events, "host_fallback") and not named(events, "single_verify")
    # two sharded dispatches, one a kind: the ed25519 sub-batch's, then sr25519's
    sent = named(events, "mesh_dispatch")
    assert [(e["args"]["engine"], e["args"]["devices"], e["args"]["lanes"]) for e in sent] == [
        ("ed25519", DEVICES, N_ED), ("sr25519", DEVICES, N_SR),
    ]
    assert sent[1]["args"]["kind"] == "sr25519" and sent[0]["args"]["kind"] != "sr25519"
    # each inside its chunk's dispatch_chunk, which carries the useful lanes
    chunks = named(events, "dispatch_chunk")
    assert [c["args"]["lanes"] for c in chunks] == [N_ED, N_SR]
    assert all(c["ts"] <= m["ts"] <= c["ts"] + c["dur"] for c, m in zip(chunks, sent))
    # the host's lanes between the last dispatch and the first collect, both sub-batches in flight
    (host,) = named(events, "host_lanes")
    assert (host["args"]["key_type"], host["args"]["lanes"]) == ("secp256k1", N_SECP)
    assert host["args"]["device_lanes_inflight"] == N_ED + N_SR
    assert host["args"]["impl"] == hashing.host_secp256k1_impl()  # whose ECDSA (PR 49)
    got = named(events, "collect_chunk")
    assert all(c["ts"] + c["dur"] <= host["ts"] for c in chunks)
    assert all(host["ts"] + host["dur"] <= c["ts"] for c in got)
    # every device gave its slab back, for both engines
    per_device = named(events, "collect_device")
    assert sorted((e["args"]["engine"], e["args"]["lanes"]) for e in per_device) == (
        [("ed25519", N_ED // DEVICES)] * DEVICES + [("sr25519", N_SR // DEVICES)] * DEVICES
    )
    assert mesh.manager.snapshot()["dispatches"] == 2
    assert mesh.manager.snapshot()["exclusions"] == 0


@pytest.mark.parametrize("key_type", ["ed25519", "sr25519", "secp256k1"])
def test_a_tampered_lane_of_each_key_type_is_blamed_at_its_index_on_the_mesh(committee, key_type):
    _, vset, block_id, commit = committee
    types = [v.pub_key.type for v in vset.validators]
    idx = [i for i, kt in enumerate(types) if kt == key_type][7]  # not its type's first lane
    with pytest.raises(validation.InvalidCommitError) as exc:
        validation.verify_commit(CHAIN_ID, vset, block_id, HEIGHT, tampered(commit, idx))
    assert "(#%d)" % idx in str(exc.value)
    assert mesh.manager.snapshot()["dispatches"] == 2  # refused by the mesh's verdicts, not beside them


def test_the_meshs_verdicts_are_the_plain_references_lane_for_lane(committee):
    """One lane of each key type tampered in one commit: every lane's
    verdict, through the phased call on the mesh, is what
    ``reference_mixed.py`` (big integers, no import of the program) says
    of that key, message and signature."""
    _, vset, _, commit = committee
    types = [v.pub_key.type for v in vset.validators]
    bad = sorted([i for i, kt in enumerate(types) if kt == key_type][3] for key_type in set(types))
    for idx in bad:
        commit = tampered(commit, idx)
    lanes = commit_lanes(vset, commit)
    ok, verdicts = multi_of(lanes).verify()
    assert not ok and [i for i, v in enumerate(verdicts) if not v] == bad
    want = [reference_mixed.verify(pk.type, pk.bytes(), msg, sig) for pk, msg, sig in lanes]
    assert verdicts == want
    assert mesh.manager.snapshot()["dispatches"] == 2

"""Multi-commit pipelined batch verification.

The reference's blocksync loop verifies one commit per block serially
(internal/blocksync/reactor.go:538-650, VerifyCommitLight at :582). Here
whole RANGES of commits are flattened into one device batch: every
included signature of every block in the window goes to the engine in
one ``verify_batch`` call (chunked there at 4,096 lanes a kernel launch,
optionally sharded over a mesh), and per-block verdicts are sliced back
out. This is the pipeline-parallel analog from SURVEY.md §2.4 — fetch,
device-batch, apply.

Semantics per block match verify_commit_light exactly: ignore non-commit
sigs, stop adding once tallied power exceeds 2/3, all included sigs must
verify, tally must exceed 2/3.

A window over validator sets of ed25519 keys alone is one flat batch of
the ed25519 engine's. A window that meets a set holding another key
type (types/params.go PubKeyTypes: ed25519, sr25519, secp256k1) is
planned by key type across its blocks: the same task loop gathers the
same lanes, one ``crypto.batch.MultiBatchVerifier`` takes the window's
lanes in one ``add_many`` — one sub-batch a key type a window, not one
a block — and verifies them in its three phases (the device sub-batches
begun in the order of their type's name, the lanes of a type that
cannot batch verified on the host while those kernels run, then the
collects); its merged verdicts are sliced per block like the flat
batch's. Only a block holding an entry its sub-verifier refuses to take
(a malformed signature) gets verify_commit_light's own verdict, as
``types.validation._verify_commit_batch`` sends such a commit to single
verification.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import ED25519_KEY_TYPE
from tendermint_tpu.libs import tracing
from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT, BlockID, Commit
from tendermint_tpu.types.validation import (
    InvalidCommitError,
    NotEnoughVotingPowerError,
    _verify_basic_vals_and_commit,
    verify_commit_light,
)
from tendermint_tpu.types.validator_set import ValidatorSet


@dataclass
class CommitTask:
    """One block's commit to verify: (chain_id, vals, block_id, height, commit)."""

    chain_id: str
    vals: ValidatorSet
    block_id: BlockID
    height: int
    commit: Commit


@dataclass
class CommitVerdict:
    ok: bool
    error: Optional[Exception] = None


def _window_seats(tasks: Sequence[CommitTask]) -> Tuple[dict, bool]:
    """``(id(set) -> what each of its seats hands the verifier, mixed)``:
    every ValidatorSet object of the window looked at once — its seats'
    key types read here and not once a lane or a block. ``mixed`` says
    that some set holds a key that is not ed25519: the window's lanes
    then carry the key objects, for the sub-batches to be planned by
    their types; else the raw key bytes the ed25519 engine takes."""
    sets = {id(task.vals): task.vals for task in tasks if task.vals is not None}
    keys = {i: [val.pub_key for val in vals.validators] for i, vals in sets.items()}
    mixed = not all(crypto_batch.key_types(ks) <= {ED25519_KEY_TYPE} for ks in keys.values())
    if not mixed:
        keys = {i: [key.bytes() for key in ks] for i, ks in keys.items()}
    return keys, mixed


def _plan_by_key_type(spans: list, lanes: Tuple[list, list, list], use_device: Optional[bool]):
    """``(verifier, refused)``: the window's lanes in one
    MultiBatchVerifier, grouped there by key type in one pass — one
    sub-batch a type a window. A window holding an entry a sub-verifier
    refuses to take is offered block by block to find whose it is: those
    blocks (``refused``, task indices) leave the plan, ``spans`` says
    where the others' lanes now start."""
    bv = crypto_batch.MultiBatchVerifier(use_device)
    try:
        bv.add_many(*lanes)
    except ValueError:
        pass  # it took part of the window: a new one takes the blocks it can
    else:
        return bv, []
    refused: List[int] = []
    kept: Tuple[list, list, list] = ([], [], [])
    for t_i, span in enumerate(spans):
        if span is None:
            continue
        start, sig_idxs = span
        block = [column[start : start + len(sig_idxs)] for column in lanes]
        try:
            crypto_batch.MultiBatchVerifier(use_device).add_many(*block)
        except ValueError:
            refused.append(t_i)
            spans[t_i] = None
            continue
        spans[t_i] = (len(kept[0]), sig_idxs)
        for column, part in zip(kept, block):
            column += part
    bv = crypto_batch.MultiBatchVerifier(use_device)
    bv.add_many(*kept)
    return bv, refused


def _verify_light_single(task: CommitTask) -> CommitVerdict:
    """verify_commit_light's own verdict, for a block the plan refused."""
    try:
        verify_commit_light(
            task.chain_id, task.vals, task.block_id, task.height, task.commit
        )
    except (InvalidCommitError, NotEnoughVotingPowerError) as e:
        return CommitVerdict(False, e)
    return CommitVerdict(True)


def verify_commits_pipelined(
    tasks: Sequence[CommitTask],
    mesh=None,
    use_device: Optional[bool] = None,
) -> List[CommitVerdict]:
    """Batch-verify many commits in one device batch, or in one
    sub-batch a key type where a set of the window holds several.

    Returns one verdict per task; a failed batch attributes the first bad
    signature per block (validation.go:244-251 semantics, per block),
    by its index in the commit whatever its key's type. ``mesh`` shards
    a window of ed25519 keys; a mixed window's device sub-batches go
    where MultiBatchVerifier's go, to the engines' own mesh policy.
    """
    verdicts: List[Optional[CommitVerdict]] = [None] * len(tasks)
    # the window's lanes: the key as the verifier takes it (_window_seats)
    flat_keys: list = []
    flat_msgs: List[bytes] = []
    flat_sigs: List[bytes] = []
    # per-task: (start of its lanes in the flat batch, [commit idx of each lane])
    spans: List[Optional[Tuple[int, List[int]]]] = [None] * len(tasks)
    bv = None  # the mixed window's verifier
    refused: List[int] = []  # tasks holding an entry its sub-verifier refuses to take

    with tracing.span("verify_commits_pipelined", tasks=len(tasks)) as osp:
        refused_early = 0
        skipped = 0
        # One span for the whole task loop; its per-task and per-lane
        # steps are phase totals in the span's arguments (wrapped once
        # here: the loop itself holds no tracing call, and on the no-op
        # span these are the callables themselves).
        with tracing.span("build_lanes") as lsp:
            basic_checks = lsp.timed("basic_checks", _verify_basic_vals_and_commit)
            # one phase over every task's encoder (tracer on only)
            timed_lane = lsp.timed("sign_bytes", lambda lane, idx: lane(idx))
            prefixes = 0
            note_set = (
                crypto_batch.note_validator_set_traced
                if lsp.live
                else crypto_batch.note_validator_set
            )
            seats, mixed = _window_seats(tasks)
            for t_i, task in enumerate(tasks):
                try:
                    basic_checks(task.vals, task.commit, task.height, task.block_id)
                except InvalidCommitError as e:
                    verdicts[t_i] = CommitVerdict(False, e)
                    refused_early += 1
                    continue
                # Eligibility for the device precompute cache, once a task:
                # a blocksync window reuses one validator set across most
                # of its blocks, and a live set is recognised by its key
                # objects (precompute.activate_validator_set), not hashed.
                note_set(task.vals)
                needed = task.vals.total_voting_power() * 2 // 3
                validators = task.vals.validators
                keys = seats[id(task.vals)]
                commit = task.commit
                signatures = commit.signatures
                encoder = commit.sign_bytes_encoder(task.chain_id)
                sign_bytes = (
                    partial(timed_lane, encoder.lane) if lsp.live else encoder.lane
                )
                start = len(flat_keys)
                sig_idxs: List[int] = []
                tallied = 0
                for idx, cs in enumerate(signatures):
                    if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
                        continue  # light: ignore everything not for the block
                    flat_keys.append(keys[idx])
                    flat_msgs.append(sign_bytes(idx))
                    flat_sigs.append(cs.signature)
                    sig_idxs.append(idx)
                    tallied += validators[idx].voting_power
                    if tallied > needed:
                        break
                prefixes += encoder.prefixes
                if tallied > needed:
                    spans[t_i] = (start, sig_idxs)
                    if osp.live:
                        skipped += sum(
                            1
                            for cs in signatures[sig_idxs[-1] + 1 :]
                            if cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
                        )
                    continue
                # this task sends no lane: drop what it added, which
                # keeps every later task's slice where its start says
                del flat_keys[start:], flat_msgs[start:], flat_sigs[start:]
                verdicts[t_i] = CommitVerdict(
                    False,
                    NotEnoughVotingPowerError(got=tallied, needed=needed),
                )
                refused_early += 1
            if mixed and flat_keys:
                # the grouping, a phase of this span like the two above
                plan = lsp.timed("group_lanes", _plan_by_key_type)
                bv, refused = plan(spans, (flat_keys, flat_msgs, flat_sigs), use_device)
            lanes = len(bv) if bv is not None else len(flat_keys)
            lsp.set(lanes=lanes, sign_bytes_prefixes=prefixes)
        osp.set(lanes=lanes, skipped=skipped, refused_early=refused_early)

        if bv is not None:
            # the window's lanes by route, and how many sub-batches hold them
            by_type = bv.lanes_by_type()
            osp.set(
                sub_batches=len(by_type),
                host_lanes=sum(n for n, route in by_type.values() if route == "host"),
                **{
                    "device_lanes_" + kt: n
                    for kt, (n, route) in by_type.items()
                    if route == "device"
                },
            )
            try:
                oks = bv.verify()[1]
            finally:
                bv.close()
        elif not flat_keys:
            oks = []
        elif mesh is not None:
            from tendermint_tpu.parallel.sharding import verify_batch_sharded

            oks = verify_batch_sharded(flat_keys, flat_msgs, flat_sigs, mesh)
        elif use_device is False:
            from tendermint_tpu.crypto.ed25519_ref import verify_zip215

            oks = [
                verify_zip215(pk, m, s)
                for pk, m, s in zip(flat_keys, flat_msgs, flat_sigs)
            ]
        else:
            from tendermint_tpu.ops import verify_batch

            oks = verify_batch(flat_keys, flat_msgs, flat_sigs)

        with tracing.span(
            "merge_verdicts",
            lanes=len(oks),
            blocks=len(tasks) - refused_early - len(refused),
            scan="first_bad_per_block",
        ):
            for t_i, span in enumerate(spans):
                if span is None:
                    continue
                start, sig_idxs = span
                block_oks = oks[start : start + len(sig_idxs)]
                bad = next((i for i, ok in enumerate(block_oks) if not ok), None)
                if bad is None:
                    verdicts[t_i] = CommitVerdict(True)
                else:
                    sig = tasks[t_i].commit.signatures[sig_idxs[bad]]
                    verdicts[t_i] = CommitVerdict(
                        False,
                        InvalidCommitError(
                            f"wrong signature (#{sig_idxs[bad]}): "
                            f"{sig.signature.hex().upper()}"
                        ),
                    )
        for t_i in refused:
            verdicts[t_i] = _verify_light_single(tasks[t_i])
    return verdicts

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
verify, tally must exceed 2/3. The flat batch is the ed25519 engine's: a
block that includes a key of another type is left out of it and gets
verify_commit_light's own verdict.
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


def _verify_light_alone(task: CommitTask) -> CommitVerdict:
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
    """Batch-verify many commits in one device batch.

    Returns one verdict per task; a failed batch attributes the first bad
    signature per block (validation.go:244-251 semantics, per block).
    """
    verdicts: List[Optional[CommitVerdict]] = [None] * len(tasks)
    flat_pks: List[bytes] = []
    flat_msgs: List[bytes] = []
    flat_sigs: List[bytes] = []
    # per-task: (start of its lanes in the flat batch, [commit idx of each lane])
    spans: List[Optional[Tuple[int, List[int]]]] = [None] * len(tasks)
    alone: List[int] = []  # tasks that include a key the flat batch cannot take

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
                commit = task.commit
                signatures = commit.signatures
                encoder = commit.sign_bytes_encoder(task.chain_id)
                sign_bytes = (
                    partial(timed_lane, encoder.lane) if lsp.live else encoder.lane
                )
                start = len(flat_pks)
                sig_idxs: List[int] = []
                tallied = 0
                batchable = True
                for idx, cs in enumerate(signatures):
                    if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
                        continue  # light: ignore everything not for the block
                    val = validators[idx]
                    if val.pub_key.type != ED25519_KEY_TYPE:
                        batchable = False
                        break
                    flat_pks.append(val.pub_key.bytes())
                    flat_msgs.append(sign_bytes(idx))
                    flat_sigs.append(cs.signature)
                    sig_idxs.append(idx)
                    tallied += val.voting_power
                    if tallied > needed:
                        break
                prefixes += encoder.prefixes
                if batchable and tallied > needed:
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
                del flat_pks[start:], flat_msgs[start:], flat_sigs[start:]
                if not batchable:
                    alone.append(t_i)
                else:
                    verdicts[t_i] = CommitVerdict(
                        False,
                        NotEnoughVotingPowerError(got=tallied, needed=needed),
                    )
                    refused_early += 1
            lsp.set(lanes=len(flat_pks), sign_bytes_prefixes=prefixes)
        osp.set(lanes=len(flat_pks), skipped=skipped, refused_early=refused_early)

        if flat_pks:
            if mesh is not None:
                from tendermint_tpu.parallel.sharding import verify_batch_sharded

                oks = verify_batch_sharded(flat_pks, flat_msgs, flat_sigs, mesh)
            elif use_device is False:
                from tendermint_tpu.crypto.ed25519_ref import verify_zip215

                oks = [
                    verify_zip215(pk, m, s)
                    for pk, m, s in zip(flat_pks, flat_msgs, flat_sigs)
                ]
            else:
                from tendermint_tpu.ops import verify_batch

                oks = verify_batch(flat_pks, flat_msgs, flat_sigs)
        else:
            oks = []

        with tracing.span(
            "merge_verdicts",
            lanes=len(oks),
            blocks=len(tasks) - refused_early - len(alone),
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
        for t_i in alone:
            verdicts[t_i] = _verify_light_alone(tasks[t_i])
    return verdicts

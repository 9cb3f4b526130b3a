"""Plain light verification of a window of commits: the reference the
benchmark holds ``parallel/pipeline.verify_commits_pipelined`` to. It
imports ``chipbench/reference.py``'s big-integer ``verify`` and nothing
of the program, and takes plain values only.

The rule is upstream's ``VerifyCommitLight`` (types/validation.go), one
block after another, no block looking at its neighbours: walk the
commit's signatures in the validator set's order; skip what is not a
vote for the block; add the validator's power; stop once the tally
passes two thirds of the total; every signature walked that far must
verify. What comes after the stop is never looked at.
"""

from __future__ import annotations

from chipbench import reference

FLAG_COMMIT = 2  # types/block.go BlockIDFlagCommit; 1 is absent, 3 is nil

OK = ("ok", None)
INSUFFICIENT = ("insufficient power", None)


def verify_block(validators, signatures):
    """``validators``: (public key, power) in the set's order;
    ``signatures``: (flag, sign-bytes, signature), one per validator.
    Answers ``("ok", None)``, ``("insufficient power", None)`` or
    ``("wrong signature", i)`` with i the index *in the commit* of the
    first included signature that fails."""
    if len(validators) != len(signatures):
        raise ValueError("a commit holds one entry per validator")
    needed = sum(power for _, power in validators) * 2 // 3
    tallied = 0
    included = []
    for i, (flag, _, _) in enumerate(signatures):
        if flag != FLAG_COMMIT:
            continue
        included.append(i)
        tallied += validators[i][1]
        if tallied > needed:
            break
    if tallied <= needed:
        return INSUFFICIENT
    for i in included:
        _, msg, sig = signatures[i]
        if not reference.verify(validators[i][0], msg, sig):
            return ("wrong signature", i)
    return OK


def verify_window(blocks):
    """One answer per ``(validators, signatures)`` block, in order."""
    return [verify_block(vals, sigs) for vals, sigs in blocks]

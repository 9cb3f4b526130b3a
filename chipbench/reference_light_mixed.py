"""Plain light verification of a window of commits over a validator set
of mixed key types: the reference the ``syncmixed500`` cell holds
``parallel/pipeline.verify_commits_pipelined`` to. It is
``reference_light.verify_block``'s rule — upstream's
``VerifyCommitLight`` (types/validation.go), one block after another, no
block looking at its neighbours — over seats of (key type, public key,
power), each included lane judged by ``reference_mixed.verify``
(ZIP-215; schnorrkel over ristretto255 with the Merlin transcript
written out; ECDSA over secp256k1 with the low-s rule). It imports
``chipbench/reference_mixed.py`` and nothing of the program, and takes
plain values only, so a pool of fresh interpreters can run it
(``generators/catchup_mixed.verify_blocks_in_parallel``).
"""

from __future__ import annotations

from chipbench import reference_mixed

FLAG_COMMIT = 2  # types/block.go BlockIDFlagCommit; 1 is absent, 3 is nil

OK = ("ok", None)
INSUFFICIENT = ("insufficient power", None)


def verify_block(validators, signatures):
    """``validators``: (key type, public key, power) in the set's order;
    ``signatures``: (flag, sign-bytes, signature), one per validator.
    Answers ``("ok", None)``, ``("insufficient power", None)`` or
    ``("wrong signature", i)`` with i the index *in the commit* of the
    first included signature that fails, whatever its key's type."""
    if len(validators) != len(signatures):
        raise ValueError("a commit holds one entry per validator")
    needed = sum(power for _, _, power in validators) * 2 // 3
    tallied = 0
    included = []
    for i, (flag, _, _) in enumerate(signatures):
        if flag != FLAG_COMMIT:
            continue
        included.append(i)
        tallied += validators[i][2]
        if tallied > needed:
            break
    if tallied <= needed:
        return INSUFFICIENT
    for i in included:
        key_type, pub, _ = validators[i]
        _, msg, sig = signatures[i]
        if not reference_mixed.verify(key_type, pub, msg, sig):
            return ("wrong signature", i)
    return OK


def verify_window(blocks):
    """One answer per ``(validators, signatures)`` block, in order."""
    return [verify_block(vals, sigs) for vals, sigs in blocks]

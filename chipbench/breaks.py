"""Ways to break the timed path, for the controls (``control.py`` on the
chip, ``selftest.py`` here): each breaks one guarantee the
configurations state, or alters an answer where it is produced, and a
run so broken has to come out ``correct: false`` (or ``failed``). The
driver's runs never pass ``--break``.

- ``cache_answers``: the cycle of requests is cut to two, so the
  program's verdict cache answers the timed calls (guarantee 4).
- ``no_canonical_s``: the engine's ``s < L`` check is switched off, the
  step a faster engine would be tempted to drop; the ``s + L`` lane then
  verifies (guarantee 1).
- ``flip_verdict``: one lane's verdict is inverted where the engine
  returns it, in every batch it collects (guarantees 1 and 2).
- ``host_answers``: the device health machine is disabled, so the host
  oracle answers every lane, correctly (guarantee 3: ``failed``).
"""

from __future__ import annotations

NAMES = ("cache_answers", "no_canonical_s", "flip_verdict", "host_answers")


def before_setup(name: str, traffic_doc: dict, say) -> None:
    if name not in NAMES:
        raise SystemExit("chipbench: unknown --break %r" % name)
    say("BROKEN ON PURPOSE: %s" % name)
    if name == "cache_answers":
        traffic_doc["cycle_over_verdict_cache"] = 0.0
        traffic_doc["_allow_cache_answers"] = True
    elif name == "no_canonical_s":
        import numpy as np

        from tendermint_tpu.ops import ed25519_batch

        ed25519_batch._s_canonical = lambda s_arr: np.ones(len(s_arr), dtype=bool)


def after_setup(name: str, say) -> None:
    """Breaks that must leave set-up's warm-up calls sound."""
    if name == "flip_verdict":
        from tendermint_tpu.ops import ed25519_batch

        # where every path of both engines returns: a one-shot verify_batch,
        # each block of a call made of blocks, each sub-batch of a phased
        # call (sr25519_batch imports this class)
        sound = ed25519_batch._PendingJobs.collect

        def flipped(self):
            out = sound(self).copy()
            out[len(out) // 3] = not out[len(out) // 3]
            return out

        ed25519_batch._PendingJobs.collect = flipped
    elif name == "host_answers":
        from tendermint_tpu.ops.device_policy import shared as health

        health.begin_attempt = lambda engine="ed25519": None

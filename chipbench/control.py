"""The controls, on the chip: the cell as committed, broken one way at
a time (``breaks.py``), each on several seeds at the cell's own size,
has to come out ``correct: false`` (``host_answers``: ``failed``).

    python3 -m chipbench.control --workload hub150-warm --seeds 11,12,13 \\
        [--breaks cache_answers,no_canonical_s,flip_verdict,host_answers] [--seconds 3]

One child process per run, one after another; this parent never
touches JAX, so each child has the chip to itself. Exit code 0 only if
every broken run was caught. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys

from chipbench import breaks, spec


def run_cell(workload: str, seed: int, seconds: float, brk, extra=(), env=None) -> dict:
    cmd = [
        sys.executable, "-m", "chipbench.run", "--workload", workload,
        "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
    ] + list(extra)
    if brk:
        cmd += ["--break", brk]
    proc = subprocess.run(cmd, cwd=spec.ROOT, capture_output=True, text=True, env=env)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        return {"rc": proc.returncode, "stderr": proc.stderr[-2000:]}
    out = json.loads(lines[-1])
    out["rc"] = 0
    out["compared"] = [ln.split("compared: ", 1)[1] for ln in lines if "compared: " in ln]
    return out


def caught(brk: str, out: dict) -> bool:
    if out["rc"] != 0:
        return True  # a control that crashes has failed
    if brk == "host_answers":
        return out["failed"] > 0
    return out["correct"] is False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--breaks", default=",".join(breaks.NAMES))
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    ok = True
    for brk in args.breaks.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_cell(args.workload, seed, args.seconds, brk)
            good = caught(brk, out)
            ok &= good
            print(
                "control %s seed %d break %s: %s  correct=%s failed=%s/%s  %s"
                % (args.workload, seed, brk, "caught" if good else "NOT CAUGHT",
                   out.get("correct"), out.get("failed"), out.get("attempted"),
                   "; ".join(c for c in out.get("compared", []) if "over" in c)
                   or out.get("stderr", "")),
                flush=True,
            )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Does a change to the benchmark's data leave every cell's measurement as it was?

    python3 -m chipbench.same_measurement <checkout of the parent>

For ``BENCHMARK.json`` and every ``testdata/*benchmark.json``, cell by cell: the
multiset of per-layer definitions the cell reports (``reader``, ``args``,
``layer``, ``unit``, ``better``, ``source``, ``moves``; the name aside) in this
checkout against the parent's, and the old -> new names. Then everything of
``BENCHMARK.json`` but ``per_layer`` and the ``why`` strings, which has to be the
parent's. For a ``benchmark`` PR that merges, renames or shares entries; exit
code 0 only if nothing but names moved. Reads files, touches no device.
"""

from __future__ import annotations

import collections
import glob
import json
import os
import sys

from chipbench import spec

# what defines an entry: the one list, which selftest.test_files holds the cells to as well
KEYS = spec.load_json(os.path.join(spec.HERE, "testdata", "definitions_at_pr36.json"))["keys"]


def definitions(root: str, bench: str, cell: str) -> dict:
    """Definition -> the names the cell reports it under, in the checkout at ``root``."""
    out = collections.defaultdict(list)
    for m in spec.Spec(os.path.join(root, bench)).metrics_for("per_layer", cell):
        doc = spec.load_json(os.path.join(root, "chipbench", "layer_metrics", m["name"] + ".json"))
        out[json.dumps([doc[key] for key in KEYS], sort_keys=True)].append(m["name"])
    return out


def without_why(doc: dict) -> dict:
    rest = {key: value for key, value in doc.items() if key != "per_layer"}
    return json.loads(json.dumps(rest), object_hook=lambda d: {k: v for k, v in d.items() if k != "why"})


def main(argv=None) -> int:
    (parent,) = argv if argv is not None else sys.argv[1:]
    benches = ["BENCHMARK.json"] + sorted(
        os.path.relpath(p, spec.ROOT) for p in glob.glob(os.path.join(spec.HERE, "testdata", "*benchmark.json"))
    )
    ok, renamed = True, {}
    for bench in benches:
        for w in spec.load_json(os.path.join(spec.ROOT, bench))["workloads"]:
            was, now = definitions(parent, bench, w["name"]), definitions(spec.ROOT, bench, w["name"])
            same = {d: len(n) for d, n in was.items()} == {d: len(n) for d, n in now.items()}
            ok &= same
            moved = {o: n for d in was if d in now for o, n in zip(sorted(was[d]), sorted(now[d])) if o != n}
            renamed.update(moved)
            print("%-50s %-18s %3d definitions: %s, %d under a new name" % (
                bench, w["name"], sum(map(len, now.values())), "the parent's" if same else "DIFFER", len(moved)))
    for old in sorted(renamed):
        print("  %s -> %s" % (old, renamed[old]))
    rest = without_why(spec.load_json(os.path.join(parent, "BENCHMARK.json"))) == without_why(
        spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json")))
    print("BENCHMARK.json but per_layer and the why strings: %s" % ("the parent's" if rest else "DIFFERS"))
    return 0 if ok and rest else 1


if __name__ == "__main__":
    sys.exit(main())

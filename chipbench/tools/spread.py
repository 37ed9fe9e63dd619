"""Spreads of a cell's end-to-end metrics over sets of runs, as the bounds
are set from them:

    python -m chipbench.tools.spread <set1.jsonl> [<set2.jsonl> ...]

Each file holds one result line (the last stdout line of a run) per run of
one set. Prints, per metric: each set's median and its spread by the driver's
rule (range over median with the run farthest from the median left out:
``stats.range_spread``); then the widest of them, twice it — the least bound
under which the check can still tell a change (a bound holds where the runs
spread by no more than half of it) — and eight times it, over which the
check calls a bound too loose."""

import json
import statistics
import sys

from chipbench.stats import range_spread


def main(*paths):
    sets = []
    for p in paths:
        with open(p) as f:
            sets.append([json.loads(l) for l in f if l.startswith("{")])
    names = sorted({k for s in sets for r in s for k in r["metrics"]})
    for s, p in zip(sets, paths):
        bad = [i for i, r in enumerate(s) if not r["correct"] or r["failed"]]
        print(f"{p}: {len(s)} runs, not correct or failed: {bad}; attempted "
              f"{[r['attempted'] for r in s]}")
    for name in names:
        rows = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s if name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:]  # a set's first run compiles: recorded apart
            if vals:  # a set made before the metric existed has none
                rows.append((statistics.median(vals), range_spread(vals),
                             vals))
        widest = max(r[1] for r in rows)
        print(f"{name}: " + "; ".join(
            f"median {m:.6g} spread {100 * rs:.3f}%" for m, rs, _ in rows)
            + f"; widest {100 * widest:.3f}% -> bound >= {200 * widest:.2f}%"
            + f", <= {800 * widest:.2f}%")
        for *_, vals in rows:
            print("    " + " ".join(f"{v:.6g}" for v in vals))


if __name__ == "__main__":
    main(*sys.argv[1:])

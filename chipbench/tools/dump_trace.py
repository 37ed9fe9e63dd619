"""Write a trace's shape (planes, lines, the first events of each) and a
cut of it as the neutral JSON the reducer's test reads:

    python -m chipbench.tools.dump_trace <xplane.pb> <out.json> [seconds]

Used once per new kind of trace, by hand, to look before writing a reader
and to record ``chipbench/tests/fixtures/*.trace.json``."""

import json
import sys

from chipbench import trace_reduce as tr


def main(path, out, seconds=0.25):
    trace = tr.load_xplane(path, everything=True)
    win = tr.window(trace)
    for plane in trace["planes"]:
        print("PLANE", plane["name"])
        for line in plane["lines"]:
            evs = line["events"]
            print("  LINE", repr(line["name"]), len(evs),
                  [(e[0][:60], e[1], e[2]) for e in evs[:4]])
    if win is None:
        return
    t0 = win[0] + 0.3 * (win[1] - win[0])
    t1 = t0 + float(seconds) * 1e9
    keep = []
    for plane in trace["planes"]:
        dev = plane["name"].startswith("/device:TPU:")
        lines = []
        for line in plane["lines"]:
            if dev and line["name"] not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            evs = [e for e in line["events"]
                   if (dev or e[0].startswith("chipbench."))
                   and e[1] + e[2] > t0 and e[1] < t1]
            if evs:
                lines.append({"name": line["name"], "events": evs})
        if lines:
            keep.append({"name": plane["name"], "lines": lines})
    with open(out, "w") as f:
        json.dump({"planes": keep}, f)
    print("wrote", out, sum(len(l["events"]) for p in keep for l in p["lines"]),
          "events")


if __name__ == "__main__":
    main(*sys.argv[1:])

"""One run of one cell of BENCHMARK.json:

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. Refuses (non-zero exit, no result line) without a
TPU whose ``device_kind`` is in ``chipbench/peaks.json`` or with fewer
chips than the cell asks for. The last line of standard output is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced) and, last, ``compared``: each number that
decided ``correct`` with its limit, which are also the last lines of
standard error; everything else — sample counts, generator lateness — is
on earlier lines.

Driven by data: a cell is an entry of ``workloads``; its configuration is
``configs[].file``, its traffic ``chipbench/traffic/<traffic>.json``, and
each per-layer metric a reader ``chipbench/layer_metrics/<metric>.py``,
which is handed the configuration's family (its scope names and counting
functions: ``chipbench/families/<model_type>.py``) with the view.
Nothing here names a cell, a configuration, a family or a metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here: imports, device, compile

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str):
    """(cell, configuration dict, traffic dict) of a workload by name."""
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[0]
    conf = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, cfg, mix


def metrics_for(entries, workload: str):
    """The metric entries a cell reports: those without a ``workloads`` key,
    and those that list the cell."""
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(metric_name: str):
    """The reader module of a per-layer metric, found by the metric's name."""
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Programs handed to the backend compiler (persistent-cache hits
    included), counted through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def snapshot(self) -> int:
        return self.n


def memory_peak() -> Optional[int]:
    """Peak bytes in use on the fullest chip."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class Ctx:
    """What a runner is given."""
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    chips: int
    peaks: dict
    trace_dir: str
    compile_counter: CompileCounter
    controls: tuple = ()  # lower-precision controls: never in a benchmark run
    log: Callable[[str], None] = field(default=lambda s: print(s, flush=True))

    memory_peak = staticmethod(memory_peak)

    def make_tracer(self):
        import shutil

        from chipbench.spans import WindowTracer

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return WindowTracer(self.trace_dir)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed place: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``; every program stored, however
    quickly it compiled, so a cell's second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chip(chips: int) -> dict:
    """The chip's row of ``peaks.json``, or exit non-zero: a number from
    anything else is never printed under a device metric's name."""
    import jax

    peaks = load_json(os.path.join(HERE, "peaks.json"))
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found {dev.platform!r} "
                         f"({dev.device_kind}); nothing measured")
    if dev.device_kind not in peaks:
        raise SystemExit(f"chipbench: no peaks on record for device_kind "
                         f"{dev.device_kind!r} (chipbench/peaks.json)")
    if jax.device_count() < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chip(s), "
                         f"found {jax.device_count()}")
    return peaks[dev.device_kind]


def runner_for(cfg: dict):
    return importlib.import_module("chipbench.runners." + cfg["runner"])


def run_cell(bench: dict, workload: str, cfg: dict, mix: dict, ctx: Ctx
             ) -> dict:
    """Drive one run and build the result object (everything after the
    look for a chip; the tests call this on the CPU at a tiny size)."""
    import jax

    record = runner_for(cfg).run(ctx)
    log = ctx.log
    e2e = record["e2e"]
    log("chipbench: " + json.dumps(
        {k: v for k, v in e2e.items() if k not in ("attempted", "failed")}))
    log("chipbench: " + json.dumps({
        k: record[k] for k in ("reference_s", "check_tokens", "check_requests",
                               "compiles_in_window", "steps", "drain_s",
                               "memory_peak_bytes",
                               "memory_in_use_after_window_bytes", "numbers",
                               "control_numbers", "slowest_steps_s_at_s",
                               "setup_phases_s")
        if k in record}))
    correct = True
    compared = {}
    for name, value, limit in record["compared"]:
        ok = bool(value <= limit)
        correct = correct and ok
        # plain numbers: a numpy integer does not go through json.dumps
        compared[name] = {"value": getattr(value, "item", lambda: value)(),
                          "limit": getattr(limit, "item", lambda: limit)()}
        log(f"chipbench: compare {name} value={value!r} limit={limit!r} "
            f"ok={ok}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": int(e2e["attempted"]),
              "failed": int(e2e["failed"]), "metrics": {}, "device": device}
    if not ctx.trace:
        for m in metrics_for(bench["end_to_end"], workload):
            value = e2e.get(m["name"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["compared"] = compared
        return result
    from chipbench import trace_reduce as tr

    trace = tr.load_xplane(record["trace_path"]) \
        if record.get("trace_path") else None
    view = TraceView(trace, record, cfg, mix, ctx.peaks, ctx.chips)
    for m in metrics_for(bench["per_layer"], workload):
        value = load_reader(m["name"]).read(view)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if trace is not None and view.window is not None:
        device["busy_s"] = view.busy_s()
        device["window_s"] = (view.window[1] - view.window[0]) / 1e9
        result["breakdown"] = {
            "device_ops": tr.top_ops(view.ops(0), 10),
            "idle_gaps": tr.idle_gaps(view.ops(0), *view.window,
                                      view.host_spans, 10),
        }
    result["compared"] = compared  # the line's last key
    return result


class TraceView:
    """What a per-layer metric's reader is given: the reduced trace, the
    benchmark's host spans, the run record, the configuration with its
    family (``families.of``: None where it names none), the traffic mix and
    the chip's peaks."""

    def __init__(self, trace, record, cfg, mix, peaks, chips):
        from chipbench import families
        from chipbench import trace_reduce as tr

        self.trace, self.record = trace, record
        self.cfg, self.mix, self.peaks, self.chips = cfg, mix, peaks, chips
        self.family = families.of(cfg)
        self.tr = tr
        self.window = tr.window(trace) if trace is not None else None
        self.host_spans = tr.host_spans(trace) if trace is not None else []
        self._planes = tr.device_planes(trace)[:chips] \
            if trace is not None else []
        self._ops = {}  # chip -> its clipped operations: every reader asks

    def ops(self, chip: int):
        """Device operation events of one chip, cut to the traced window."""
        if chip >= len(self._planes) or self.window is None:
            return []
        if chip not in self._ops:
            self._ops[chip] = self.tr.clip(
                self.tr.line_events(self._planes[chip], self.tr.OPS_LINE),
                *self.window)
        return self._ops[chip]

    def program_runs(self, chip: int) -> float:
        """Runs of the chip's main program inside the traced window."""
        if chip >= len(self._planes) or self.window is None:
            return 0.0
        return self.tr.main_program_runs(
            self.tr.line_events(self._planes[chip], self.tr.MODULES_LINE),
            *self.window)

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips used."""
        n = max(1, len(self._planes))
        return sum(self.tr.busy_ns(self.ops(c))
                   for c in range(len(self._planes))) / n / 1e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = load_cell(bench, args.workload)
    import uccl_tpu  # noqa: F401 — the system under test must be here
    cache = enable_compile_cache()
    peaks = require_chip(cell["chips"])
    print(f"chipbench: workload {cell['name']} seed {args.seed} seconds "
          f"{args.seconds} trace {args.trace} cache {cache}", flush=True)
    ctx = Ctx(cfg=cfg, mix=mix, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t_start=T_START, chips=cell["chips"],
              peaks=peaks,
              trace_dir=os.path.join(ROOT, ".chipbench_trace",
                                     cell["name"]),
              compile_counter=CompileCounter())
    result = run_cell(bench, cell["name"], cfg, mix, ctx)
    print(json.dumps(result), flush=True)
    # each number compared beside its limit, as standard error's last lines
    for name, c in result["compared"].items():
        print(f"chipbench: compare {name} value={c['value']!r} "
              f"limit={c['limit']!r}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

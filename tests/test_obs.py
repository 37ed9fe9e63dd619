"""The obs layer: tracer ring/concurrency, Chrome-trace validity, request
lifecycle completeness, wire-fallback counters, Prometheus export.

Lifecycle tests drive the real ServingEngine over a stub backend (no jax
compiles — tier-1 wall time); the one jitted test (forced lax fallback
through a real shard_map) shares the suite's virtual mesh.
"""

import json
import threading
from collections import defaultdict

import numpy as np
import pytest

from uccl_tpu import obs
from uccl_tpu.collective import dma
from uccl_tpu.serving import ServingEngine


@pytest.fixture
def tracer():
    """Fresh enabled tracer, always disabled after (global state)."""
    t = obs.enable_tracing(4096)
    yield t
    obs.disable_tracing()


class _StubBackend:
    """Prefill emits 0, the i-th decode step emits i (no model, no jax)."""

    def __init__(self, n_slots=2, max_seq=64):
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.n_decodes = 0

    def prefill(self, tokens, lens, mask, start=None):
        return np.zeros(self.n_slots, np.int32)

    def decode(self, tokens, active):
        self.n_decodes += 1
        return np.full(self.n_slots, self.n_decodes, np.int32)


def _prompt(rng, n):
    return rng.integers(0, 64, n).astype(np.int32)


class TestTracer:
    def test_ring_buffer_bounds_memory(self):
        t = obs.Tracer(capacity=100)
        for i in range(500):
            t.instant(f"e{i}", track="t")
        evs = t.events()
        assert len(evs) == 100
        assert t.dropped == 400
        assert evs[0].name == "e400"  # oldest survivor

    def test_concurrent_writers(self):
        t = obs.Tracer(capacity=100000)
        errs = []
        # all 8 workers provably concurrent (ident reuse after a thread
        # dies would otherwise fold auto tracks together)
        barrier = threading.Barrier(8)

        def worker(k):
            try:
                barrier.wait(timeout=30)
                for i in range(500):
                    t.instant(f"w{k}-{i}")
                    with t.span(f"s{k}-{i}"):
                        pass
                barrier.wait(timeout=30)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        [th.start() for th in threads]
        [th.join() for th in threads]
        assert not errs
        evs = t.events()
        assert len(evs) == 8 * 1000 and t.dropped == 0
        # auto tracks keep concurrent writers on distinct rows
        assert len({e.track for e in evs}) == 8
        assert all(e.dur_us >= 0 for e in evs)

    def test_disabled_is_noop(self):
        obs.disable_tracing()
        assert obs.get_tracer() is None
        with obs.span("nothing", track="x", a=1):
            obs.instant("also-nothing")
        with obs.span("b"):
            obs.mark("m", track="x", k=1)
        assert obs.get_tracer() is None  # still off, nothing recorded

    def test_span_and_clear(self, tracer):
        with obs.span("outer", track="t", k="v"):
            obs.instant("mark", track="t")
        evs = tracer.events()
        assert [e.ph for e in evs] == ["i", "X"]  # X lands at exit
        assert evs[1].args == {"k": "v"} and evs[1].dur_us >= 0
        tracer.clear()
        assert len(tracer) == 0


def _by_track(trace):
    """{track: [span and instant events in ts order]}."""
    tracks = {e["tid"]: e["args"]["name"] for e in trace["traceEvents"]
              if e.get("name") == "thread_name"}
    by_track = defaultdict(list)
    for ev in trace["traceEvents"]:
        if ev["ph"] in "Xi":
            by_track[tracks[ev["tid"]]].append(ev)
    for evs in by_track.values():
        evs.sort(key=lambda ev: ev["ts"])
    return by_track


class TestHistograms:
    def _fam(self, reg=None):
        reg = reg if reg is not None else obs.Registry()
        return reg.histogram("lat_seconds", "test latencies",
                             buckets=[0.001, 0.01, 0.1, 1.0])

    def test_observe_and_text_export(self):
        reg = obs.Registry()
        h = self._fam(reg)
        for v in (0.0005, 0.0005, 0.05, 5.0):
            h.observe(v)
        reg.histogram("declared_empty_seconds", "exists as zeros")
        txt = obs.prometheus_text(reg)
        assert "# TYPE lat_seconds histogram" in txt
        # cumulative buckets, inclusive le, +Inf last
        assert 'lat_seconds_bucket{le="0.001"} 2' in txt
        assert 'lat_seconds_bucket{le="0.01"} 2' in txt
        assert 'lat_seconds_bucket{le="0.1"} 3' in txt
        assert 'lat_seconds_bucket{le="+Inf"} 4' in txt
        assert "lat_seconds_sum 5.051" in txt
        assert "lat_seconds_count 4" in txt
        # declared-but-empty family still exports an assertable series
        assert 'declared_empty_seconds_bucket{le="+Inf"} 0' in txt
        assert "declared_empty_seconds_count 0" in txt

    def test_labels_and_inclusive_edge(self):
        h = self._fam()
        h.observe(0.001, cls="a")  # == the edge: le is inclusive
        h.observe(0.002, cls="b")
        assert h.get(cls="a") == 1 and h.total() == 2
        (labels, counts, s), = [x for x in h.hist_samples()
                                if x[0] == {"cls": "a"}]
        assert counts[0] == 1 and sum(counts) == 1

    def test_quantile_matches_samples_within_bucket_width(self, rng):
        from uccl_tpu.serving.metrics import percentile

        h = obs.Registry().histogram(
            "q_seconds", buckets=obs.DEFAULT_LATENCY_BUCKETS
        )
        xs = list(rng.lognormal(-4.0, 1.5, 200))
        for v in xs:
            h.observe(v)
        for q in (50, 95):
            hv = h.quantile(q)
            sv = percentile(xs, q)
            assert abs(hv - sv) <= obs.bucket_width(h.uppers, hv), (q, hv, sv)

    def test_merge_safety_sum_equals_union(self, rng):
        """The fleet-aggregation property: two processes' bucket counts
        SUM into the distribution one process observing everything would
        have recorded — bit-exact, not approximate."""
        a, b = self._fam(), self._fam()
        union = self._fam()
        xs, ys = rng.exponential(0.05, 50), rng.exponential(0.5, 70)
        for v in xs:
            a.observe(v)
            union.observe(v)
        for v in ys:
            b.observe(v)
            union.observe(v)
        (_, ca, sa), = a.hist_samples()
        (_, cb, sb), = b.hist_samples()
        (_, cu, su), = union.hist_samples()
        assert [x + y for x, y in zip(ca, cb)] == cu
        assert abs((sa + sb) - su) < 1e-9
        for q in (50, 95):
            assert obs.histogram_quantile(
                a.uppers, [x + y for x, y in zip(ca, cb)], q
            ) == union.quantile(q)

    def test_bucket_mismatch_and_type_conflict_rejected(self):
        reg = obs.Registry()
        reg.histogram("h_seconds", buckets=[0.1, 1.0])
        with pytest.raises(ValueError):
            reg.histogram("h_seconds", buckets=[0.2, 1.0])
        with pytest.raises(TypeError):
            reg.counter("h_seconds")

    def test_serving_hooks_observe_histograms(self):
        """The lifecycle hooks feed the merge-safe histograms the SAME
        values they append as samples — the within-one-bucket agreement
        the fleet gate rests on."""
        from uccl_tpu.serving.metrics import (
            TTFT_HIST, ServingMetrics, reset_latency_histograms,
        )

        reset_latency_histograms()
        m = ServingMetrics()
        rng = np.random.default_rng(1)
        eng = ServingEngine(_StubBackend(n_slots=2))
        eng.submit(_prompt(rng, 4), max_new_tokens=2)
        eng.drain()
        assert TTFT_HIST.total() == 1
        assert len(eng.metrics.ttft_s) == 1
        assert abs(TTFT_HIST.quantile(50) - eng.metrics.ttft_s[0]) \
            <= obs.bucket_width(TTFT_HIST.uppers, TTFT_HIST.quantile(50))
        eng.reset_metrics()  # warmup reset clears the histograms too
        assert TTFT_HIST.total() == 0
        del m

    def test_trace_dropped_total_exported(self):
        obs.disable_tracing()
        txt = obs.prometheus_text(obs.Registry())
        assert "obs_trace_dropped_total 0" in txt
        t = obs.enable_tracing(4)
        try:
            for i in range(10):
                t.instant(f"e{i}", track="x")
            txt = obs.prometheus_text(obs.Registry())
            assert "obs_trace_dropped_total 6" in txt
        finally:
            obs.disable_tracing()


class TestChromeTrace:
    def test_valid_json_balanced_and_nonnegative(self, tracer):
        with obs.span("outer", track="manual", k=1):
            obs.instant("tick", track="manual")
            obs.mark("marked", track="manual", rid=3)
        with obs.span("x", track="other"):
            pass
        from uccl_tpu.obs import chrome_trace

        trace = json.loads(chrome_trace.dumps())
        assert isinstance(trace["traceEvents"], list)
        # complete spans and instants only: nothing a viewer must pair up
        assert {ev["ph"] for ev in trace["traceEvents"]} == {"M", "X", "i"}
        assert all(ev["dur"] >= 0 for ev in trace["traceEvents"]
                   if ev["ph"] == "X")
        manual = _by_track(trace)["manual"]
        assert [ev["name"] for ev in manual] == ["outer", "tick", "marked"]
        assert manual[2]["args"] == {"rid": 3} and manual[2]["s"] == "t"

    def test_flow_events_and_clock_metadata(self, tracer):
        fid = obs.flow_id("deadbeefcafe0123")
        with obs.span("tx", track="wire"):
            obs.flow_start("kv_handoff", fid, track="wire")
        with obs.span("import", track="wire"):
            obs.flow_end("kv_handoff", fid, track="wire")
        obs.set_clock_offset(-1234.5, rtt_us=80.0, peer="prefill")
        trace = obs.to_chrome_trace(process_name="uccl_tpu.decode")
        s = [e for e in trace["traceEvents"] if e["ph"] == "s"]
        f = [e for e in trace["traceEvents"] if e["ph"] == "f"]
        assert len(s) == len(f) == 1
        assert s[0]["id"] == f[0]["id"] == fid
        assert s[0]["cat"] == "flow" and f[0]["bp"] == "e"
        clock = trace["otherData"]["clock"]
        assert clock["offset_us"] == -1234.5
        assert clock["rtt_us"] == 80.0 and clock["peer"] == "prefill"
        assert clock["wall_epoch_us"] > 0
        assert trace["otherData"]["process_name"] == "uccl_tpu.decode"


class TestRequestLifecycle:
    def _run(self, *, prefill_chunk=None, n_reqs=4):
        rng = np.random.default_rng(0)
        eng = ServingEngine(_StubBackend(n_slots=2),
                            prefill_chunk=prefill_chunk)
        reqs = []
        # staggered: 2 submitted up front, the rest dribble in mid-flight,
        # so admission overlaps active decodes and slots get reused
        reqs.append(eng.submit(_prompt(rng, 5), max_new_tokens=3))
        reqs.append(eng.submit(_prompt(rng, 7), max_new_tokens=2))
        eng.step()
        reqs.append(eng.submit(_prompt(rng, 3), max_new_tokens=4))
        eng.step()
        reqs.append(eng.submit(_prompt(rng, 6), max_new_tokens=2))
        eng.drain()
        assert eng.pool.leaked() == 0
        return eng, reqs

    def test_lifecycle_complete_whole_prompt(self, tracer):
        _, reqs = self._run()
        trace = obs.to_chrome_trace()
        by_track = _by_track(trace)
        for r in reqs:
            names = [ev["name"] for ev in by_track[r.track]]
            # the full lifecycle, in timeline order, on the request's row
            assert names[0] == "submit"
            assert names[1] == "admit"
            assert "prefill" in names
            ft, fin = names.index("first_token"), names.index("finish")
            assert names.index("prefill") < ft < fin == len(names) - 1
        # engine-step and wire spans exist alongside the request rows
        assert any(ev["name"] == "engine.step"
                   for ev in by_track["engine"])
        wire = [ev["name"] for ev in by_track["wire"]]
        assert "wire.prefill" in wire and "wire.decode" in wire

    def test_lifecycle_complete_chunked(self, tracer):
        _, reqs = self._run(prefill_chunk=2)
        trace = obs.to_chrome_trace()
        by_track = _by_track(trace)
        for r in reqs:
            names = [ev["name"] for ev in by_track[r.track]]
            chunks = names.count("prefill_chunk")
            # one chunk span per C-token advance of the cursor
            assert chunks == -(-r.prompt.size // 2)
            assert names[:2] == ["submit", "admit"]
            assert names[-1] == "finish" and "first_token" in names

    def test_disabled_tracer_records_nothing(self):
        obs.disable_tracing()
        eng, reqs = self._run()
        assert obs.get_tracer() is None
        assert all(r.state.value == "finished" for r in reqs)
        # counters stay live even with tracing off
        assert obs.gauge("serving_slot_high_water").get() >= 1


class TestFallbackCounters:
    def _delta(self, before):
        after = {tuple(sorted(lb.items())): v
                 for lb, v in dma.WIRE_FALLBACK.samples()}
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v > before.get(k, 0)}

    def _snap(self):
        return {tuple(sorted(lb.items())): v
                for lb, v in dma.WIRE_FALLBACK.samples()}

    def test_resolver_reasons(self):
        from uccl_tpu.ep import ll as ep_ll
        from uccl_tpu.ep import ops as ep_ops

        b = self._snap()
        assert ep_ops.resolve_chunks(2, "pallas", 1, 8, 2, 64, 4) == 1
        assert ep_ops.resolve_chunks(2, "pallas", 4, 1, 2, 64, 4) == 1
        assert ep_ll.resolve_ll_chunks(2, "pallas", 1, 8) == 1
        # NOT fallbacks, must not count: chunks off the pallas wire are a
        # no-op knob, and auto (0) resolving to 1 on an unchunkable config
        # is the correct auto answer, not a downgrade
        assert ep_ops.resolve_chunks(2, "lax", 4, 8, 2, 64, 4) == 1
        assert ep_ops.resolve_chunks(0, "pallas", 1, 8, 2, 64, 4) == 1
        assert ep_ops.resolve_chunks(0, "pallas", 4, 1, 2, 64, 4) == 1
        assert ep_ll.resolve_ll_chunks(0, "pallas", 1, 8) == 1
        d = self._delta(b)
        key = lambda what, reason: (("reason", reason), ("what", what))  # noqa: E731
        assert d[key("ep_moe_chunked", "world_size")] == 1
        assert d[key("ep_moe_chunked", "capacity")] == 1
        assert d[key("ep_ll_chunked", "world_size")] == 1
        assert sum(d.values()) == 3
        # the depth gauge reflects the LAST resolution — a downgraded
        # layer reads 1, never a stale earlier depth
        assert obs.gauge("ep_chunk_depth").get(what="moe_layer") == 1

    def test_budget_gate_counts_and_quiet_probe_does_not(self):
        b = self._snap()
        assert not dma.check_budget(1 << 40, "ep_all_to_all", True)
        assert not dma.check_budget(1 << 40, "ep_all_to_all", True,
                                    quiet=True)
        d = self._delta(b)
        assert d == {(("reason", "interpret_budget"),
                      ("what", "ep_all_to_all")): 1}

    def test_forced_lax_wire_records_reason(self, devices):
        """A REAL over-budget pallas a2a inside shard_map: the exchange
        transparently rides lax and the fallback is counted, not silent."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P

        from uccl_tpu.ep import pallas_a2a
        from jax import shard_map

        from jax import lax

        mesh = Mesh(np.array(devices[:2]), ("x",))
        # per-shard [2, 8192] f32: 2 * n * m * 4B = 128 KiB > the 64 KiB
        # interpreter ceiling, so the kernel must take the lax fallback
        x = jnp.arange(4 * 8192, dtype=jnp.float32).reshape(4, 8192)
        b = self._snap()

        def shmap(f):
            return jax.jit(shard_map(
                f, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                check_vma=False,
            ))

        out = np.asarray(shmap(
            lambda v: pallas_a2a.all_to_all(v, "x"))(x))
        want = np.asarray(shmap(lambda v: lax.all_to_all(
            v, "x", split_axis=0, concat_axis=0, tiled=True))(x))
        # the lax fallback is numerically the same exchange
        np.testing.assert_array_equal(out, want)
        d = self._delta(b)
        assert any(dict(k)["what"] == "ep_all_to_all"
                   and dict(k)["reason"].endswith("_budget")
                   for k in d), d


class TestPrometheusExport:
    def test_sanitizer(self):
        assert obs.sanitize_name("a.b-c/d") == "a_b_c_d"
        assert obs.sanitize_name("9lives") == "_9lives"
        assert obs.sanitize_name("ok_name:x") == "ok_name:x"
        assert obs.escape_label_value('a"b\nc\\d') == 'a\\"b\\nc\\\\d'

    def test_counter_and_gauge_text(self):
        reg = obs.Registry()
        c = reg.counter("events_total", "help text")
        c.inc(reason="a b")
        c.inc(2, reason="x")
        reg.gauge("depth").set(3, what="moe")
        reg.counter("declared_but_empty_total", "exists as 0")
        txt = obs.prometheus_text(reg)
        assert '# TYPE events_total counter' in txt
        assert 'events_total{reason="a b"} 1' in txt
        assert 'events_total{reason="x"} 2' in txt
        assert 'depth{what="moe"} 3' in txt
        assert "declared_but_empty_total 0" in txt

    def test_sources_flatten_nested(self):
        reg = obs.Registry()
        reg.register_source("srv", lambda: {
            "goodput": 11.5, "ttft_ms": {"p50": 1.25}, "skip": "str",
        })
        txt = obs.prometheus_text(reg)
        assert "srv_goodput 11.5" in txt
        assert "srv_ttft_ms_p50 1.25" in txt
        assert "skip" not in txt
        snap = reg.snapshot()
        assert snap["sources"]["srv"]["ttft_ms"]["p50"] == 1.25

    def test_counter_rejects_decrease(self):
        with pytest.raises(ValueError):
            obs.Registry().counter("c").inc(-1)

    def test_serving_metrics_prometheus_lines(self):
        from uccl_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.completed = 3
        m.ttft_s.extend([0.01, 0.02])
        snap = m.snapshot(queued=1, active=2, n_slots=4, occupancy=0.5)
        lines = ServingMetrics.prometheus_lines(snap)
        assert "uccl_serving_completed 3" in lines
        assert any(line.startswith('uccl_serving_ttft_ms{q="p50"} ')
                   for line in lines)

    def test_per_step_series_are_bounded_and_their_totals_exact(
            self, monkeypatch):
        """A server steps for as long as it lives: the two per-step series
        keep the last STEP_SAMPLES; the slowest step and the decode wall
        time (``decode_tok_s``) are running values and stay exact."""
        from uccl_tpu.serving import metrics as sm

        monkeypatch.setattr(sm, "STEP_SAMPLES", 8)
        short, long_ = sm.ServingMetrics(), sm.ServingMetrics()
        for m, n in ((short, 6), (long_, 20)):
            m.on_step(0.5)  # the slowest step is the first: it falls off
            for i in range(n):
                m.on_step(0.01 * (i % 4 + 1))
                m.on_decode_step(0.01 * (i % 4 + 1), 2)
        assert len(short.step_s) == 7 and len(long_.step_s) == 8
        assert len(long_.decode_step_s) == 8
        a, b = short.snapshot(), long_.snapshot()
        # under the bound: what a list of every sample gave
        assert a["step_ms"] == sm.percentiles_ms(
            [0.5] + [0.01 * (i % 4 + 1) for i in range(6)])
        assert a["max_step_ms"] == b["max_step_ms"] == 500.0
        assert a["decode_tok_s"] == round(12 / (0.01 * (10 + 1 + 2)), 1)
        assert b["decode_tok_s"] == round(40 / (0.1 * 5), 1)
        assert b["step_ms"]["p50"] == 25.0  # the last eight: 1..4 twice

    def test_stats_registry_mirrors_into_obs(self):
        from uccl_tpu.utils import stats

        stats.registry.register("obs_shim_test", lambda: {"v": 7.0})
        try:
            assert obs.REGISTRY.sources_snapshot()["obs_shim_test"] == {
                "v": 7.0
            }
        finally:
            stats.registry.unregister("obs_shim_test")
        assert "obs_shim_test" not in obs.REGISTRY.sources_snapshot()

    def test_json_snapshot_shape(self):
        snap = obs.json_snapshot()
        assert snap["schema_version"] == obs.SCHEMA_VERSION
        assert "metrics" in snap and "tracer" in snap
        json.dumps(snap)  # JSON-ready end to end

    def test_exit_net_defers_to_explicit_dump(self, tmp_path):
        """dump_at_exit's fallback must not clobber an explicit dump's
        richer output (extra lines) with the bare registry state."""
        from uccl_tpu.obs import export

        class Args:
            trace_out = ""
            metrics_out = str(tmp_path / "m.prom")

        args = Args()
        export.dump_from_args(args, extra_lines=["rich_extra_series 1"])
        assert "rich_extra_series 1" in (tmp_path / "m.prom").read_text()
        # the registered fallback is a no-op once an explicit dump ran
        assert id(args) in export._dumped_args
        args2 = Args()
        args2.metrics_out = str(tmp_path / "m2.prom")
        assert id(args2) not in export._dumped_args

"""``program_trace.py`` and the readers over it (through ``scopes.py`` and the
first model's family), on a hand-made
trace: an XSpace encoded here field by field (one chip, one host thread),
so the wire parser, the clock alignment, the scope matching and every
reader's arithmetic are checked against numbers worked out by hand."""

import json
import os

import pytest

from chipbench import flops
from chipbench import program_trace as pt
from chipbench import run as R
from chipbench import trace_reduce as tr

MS = 1_000_000  # ns


# -- a minimal xplane.proto encoder ------------------------------------------

def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _int(field, x):
    return _varint(field << 3) + _varint(x)


def _msg(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _str(field, s):
    return _msg(field, s.encode())


class Plane:
    """One XPlane under construction: names get metadata ids as they come."""

    def __init__(self, name):
        self.name, self.lines = name, []
        self.event_ids, self.stat_ids, self.paths = {}, {}, {}

    def _stat_id(self, name):
        return self.stat_ids.setdefault(name, len(self.stat_ids) + 1)

    def _stat(self, name, value):
        body = _int(1, self._stat_id(name))
        body += _str(5, value) if isinstance(value, str) else _int(4, value)
        return body

    def line(self, name, events, t0_ns=0):
        """events: (name, start_ns, dur_ns[, {stat: value}[, scope path]])"""
        body = _str(2, name) + _int(3, t0_ns)
        for ev in events:
            ev_name, start, dur = ev[:3]
            eid = self.event_ids.setdefault(ev_name, len(self.event_ids) + 1)
            if len(ev) > 4:
                self.paths[eid] = ev[4]
            e = _int(1, eid) + _int(2, (start - t0_ns) * 1000) + _int(3, dur * 1000)
            for k, v in (ev[3] if len(ev) > 3 else {}).items():
                e += _msg(4, self._stat(k, v))
            body += _msg(4, e)
        self.lines.append(body)

    def encode(self):
        body = _str(2, self.name)
        for ln in self.lines:
            body += _msg(3, ln)
        for ev_name, eid in self.event_ids.items():
            meta = _int(1, eid) + _str(2, ev_name)
            if eid in self.paths:
                meta += _msg(5, self._stat("tf_op", self.paths[eid]))
            body += _msg(4, _int(1, eid) + _msg(2, meta))
        for name, sid in self.stat_ids.items():
            body += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _str(2, name)))
        return _msg(1, body)


LEAD = 1 * MS  # the device's clock runs this far ahead of the host's

# host time, ms:  step A = [10, 90): admit [10,12) | wire.prefill [12,60) ⊃
# stage [13,15) launch [15,18) fetch [18,59) | retire [60,61) | wire.decode
# [61,88) ⊃ stage [62,63) launch [63,66) fetch [66,87) | retire [88,89)
# step B = [100, 130): admit [100,101) | wire.decode [101,128) ⊃ stage
# [102,103) launch [103,106) fetch [106,127) | retire [128,129)
SPANS = [
    ("chipbench.window", 0, 200),
    ("uccl.engine.step", 10, 80, {"queued": 1, "decoding": 1}),
    ("uccl.engine.admit", 10, 2, {"queued": 1}),
    ("uccl.wire.prefill", 12, 48, {"n": 1, "chunk": 64}),
    ("uccl.backend.stage", 13, 2), ("uccl.backend.launch", 15, 3),
    ("uccl.backend.fetch", 18, 41), ("uccl.engine.retire", 60, 1),
    ("uccl.wire.decode", 61, 27, {"n": 1, "kv_rows": 300}),
    ("uccl.backend.stage", 62, 1), ("uccl.backend.launch", 63, 3),
    ("uccl.backend.fetch", 66, 21), ("uccl.engine.retire", 88, 1),
    ("uccl.engine.step", 100, 30, {"queued": 0, "decoding": 1}),
    ("uccl.engine.admit", 100, 1, {"queued": 0}),
    ("uccl.wire.decode", 101, 27, {"n": 1, "kv_rows": 301}),
    ("uccl.backend.stage", 102, 1), ("uccl.backend.launch", 103, 3),
    ("uccl.backend.fetch", 106, 21), ("uccl.engine.retire", 128, 1),
    # a step of the drain, after the window: counted nowhere
    ("uccl.engine.step", 210, 30, {"queued": 0, "decoding": 1}),
    ("uccl.wire.decode", 211, 27, {"n": 1, "kv_rows": 302}),
]
# device programs in HOST time (the encoder stamps them LEAD earlier):
# prefill runs [17, 57): experts 20, attention 8 + 4, exchange 2 + 1, head 2,
# an unscoped copy 3; decode A runs [65, 85) and decode B [105, 125):
# experts 8, attention 6 (core) + 1 (qkv), exchange 1, head 1, copies 2 + 1
P, V = "jit(uccl_moe_prefill_slots)/", "jit(uccl_moe_verify_slots)/"


def _program(t, rows):
    out = []
    for name, dur, path in rows:
        out.append((name, t, dur, {}, path))
        t += dur
    return out


PREFILL_ROWS = [
    ("%copy.1 = copy(pool)", 3, ""),
    ("%fusion.1 = qkv", 4, P + "attn.qkv/dot_general:"),
    ("%fusion.2 = core", 8, P + "attn.core/jit(_where)/select_n:"),
    ("%sort.1 = sort", 2, P + "moe.route/sort:"),
    ("%gather.1 = gather", 1, P + "moe.dispatch/jit(_take)/gather:"),
    ("%fusion.3 = experts", 20, P + "moe.experts/ebf,efh->ebh/dot_general:"),
    ("%fusion.4 = head", 2, P + "head/dot_general:"),
]


def _decode_rows(tag):
    return [
        (f"%copy.{tag} = copy(pool)", 2, ""),
        ("%fusion.5 = qkv", 1, V + "attn.qkv/dot_general:"),
        ("%fusion.6 = core", 6, V + "attn.core/broadcast_in_dim:"),
        ("%fusion.7 = router", 1, V + "moe.router/dot_general:"),
        ("%fusion.8 = experts", 8, V + "transpose(jvp(moe.experts))/dot:"),
        ("%fusion.9 = head", 1, V + "head/dot_general:"),
        ("%add.1 = residual", 1, V + "add:"),
    ]


def _ms(events):
    """ms -> ns in a list of (name, start, dur, ...) events."""
    return [(e[0], e[1] * MS, e[2] * MS) + tuple(e[3:]) for e in events]


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    ops = (_program(17, PREFILL_ROWS) + _program(65, _decode_rows(2))
           + _program(105, _decode_rows(3)) + _program(215, _decode_rows(4)))
    runs = [("jit_uccl_moe_prefill_slots(1)", 17, 40, {"run_id": 1}),
            ("jit_uccl_moe_verify_slots(2)", 65, 20, {"run_id": 2}),
            ("jit_uccl_moe_verify_slots(2)", 105, 20, {"run_id": 3})]
    dev = Plane("/device:TPU:0")
    shift = lambda evs: [(e[0], e[1] - LEAD, e[2]) + tuple(e[3:])
                         for e in _ms(evs)]
    dev.line("XLA Modules", shift(runs))
    dev.line("XLA Ops", shift(ops), t0_ns=2 * MS)
    dev.line("Async XLA Ops", shift([("%copy-start = x", 17, 30)]))
    host = Plane("/host:CPU")
    host.line("python3", _ms(SPANS))
    # the runtime's own events bound the lead: enqueued no later than 0.2 ms
    # before each start, completion reported no sooner than 0.4 ms after
    host.line("runtime", [
        (name, int((t + d) * MS), int(0.05 * MS), {"run_id": r})
        for r, start, dur in ((1, 17, 40), (2, 65, 20), (3, 105, 20))
        for name, t, d in (("DoEnqueueProgram", start, -0.2),
                           ("CompleteCallbacks", start + dur, 0.4))])
    other = Plane("#Chip0 Misc")
    other.line("x", _ms([("noise", 1, 1)]))
    path = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(other.encode() + dev.encode() + host.encode())
    return path


def test_scope_matches_as_a_path_component():
    assert pt.scope_of("jit(f)/moe.experts/ebf,efh->ebh/dot_general:") == "moe.experts"
    assert pt.scope_of("jit(f)/transpose(jvp(moe.experts))/dot:") == "moe.experts"
    assert pt.scope_of("jit(f)/checkpoint/rematted_computation/moe.experts/mul:") == "moe.experts"
    assert pt.scope_of("jit(f)/attn.core/jit(_where)/select_n:") == "attn.core"
    # innermost wins; a longer name that contains a scope's is not it
    assert pt.scope_of("jit(f)/attn.core/attn.flash/pallas_call:") == "attn.flash"
    assert pt.scope_of("jit(f)/not.moe.experts/dot:") is None
    assert pt.scope_of("jit(f)/moe.experts_backup/dot:") is None
    assert pt.scope_of("jit(f)/add:") is None and pt.scope_of("") is None


def test_the_wire_parser_agrees_with_profile_data(trace_path):
    with open(trace_path, "rb") as f:
        (ops,) = pt.device_ops(f.read())
    ref = tr.line_events(tr.device_planes(tr.load_xplane(trace_path))[0],
                         tr.OPS_LINE)
    assert [(n, s, d) for n, s, d, _ in ops] == [tuple(e) for e in ref]
    assert len(ops) == 7 * 4 and ops[0][1] == 16 * MS  # 17 ms less the lead
    assert ops[1][3] == P + "attn.qkv/dot_general:" and ops[0][3] == ""


def test_clock_lead_is_bounded_by_causality_and_taken_out(trace_path):
    loaded = pt.load(trace_path)
    lo, hi = loaded.lead_bounds_ns
    assert (lo, hi) == (0.8 * MS, 1.4 * MS) and loaded.lead_ns == 1.1 * MS
    assert loaded.ops[0][0][1] == 16 * MS + loaded.lead_ns
    # a run that "started before it was enqueued by more than it ended
    # before it was reported": no consistent lead, none applied
    assert pt.device_clock_lead({1: 50.0}, {1: 60.0}, {1: (10.0, 40.0)}) is None
    assert pt.device_clock_lead({}, {}, {1: (10.0, 40.0)}) is None
    assert [sp[0] for sp in loaded.spans[:2]] == ["uccl.engine.step",
                                                  "uccl.engine.admit"]
    assert loaded.spans[0][3] == {"queued": 1, "decoding": 1}


def _aligned(trace_path):
    """The loaded trace with the lead taken out exactly (the hand-made
    bound's middle is 0.1 ms off the true 1 ms; the arithmetic below wants
    whole numbers)."""
    loaded = pt.load(trace_path)
    with open(trace_path, "rb") as f:
        return pt.ProgramTrace(loaded.spans, pt.device_ops(f.read()),
                               (LEAD, LEAD))


def test_busy_by_scope_inside_one_span_name(trace_path):
    t = _aligned(trace_path)
    win = (0, 200 * MS)
    ops = tr.clip(t.ops[0], *win)
    dec = pt.busy_by_scope(ops, pt.spans_in(t.spans, pt.DECODE, *win), pt.DECODE)
    assert len(dec) == 2  # the drain's decode span starts outside the window
    assert dec[0] == {None: 3 * MS, "attn.qkv": 1 * MS, "attn.core": 6 * MS,
                      "moe.router": 1 * MS, "moe.experts": 8 * MS,
                      "head": 1 * MS}
    assert pt.scope_ms(dec, pt.MOE_EXPERTS) == 8
    assert pt.scope_ms(dec, pt.ATTENTION) == 7
    assert pt.scope_ms(dec, pt.MOE_EXCHANGE) == 1
    pre = pt.busy_by_scope(ops, pt.spans_in(t.spans, pt.PREFILL, *win), pt.PREFILL)
    assert pt.scope_ms(pre, pt.MOE_EXPERTS) == 20
    assert pt.scope_ms(pre, pt.MOE_EXCHANGE) == 3
    # acceptance (a): all scopes and the unscoped rest are the program
    assert sum(dec[0].values()) == 20 * MS and sum(pre[0].values()) == 40 * MS
    # nothing scoped at all (the parent's program): nothing to read
    bare = [(n, s, d, "jit(f)/dot:") for n, s, d, _ in ops]
    rows = pt.busy_by_scope(bare, pt.spans_in(t.spans, pt.DECODE, *win), pt.DECODE)
    assert pt.scope_ms(rows, pt.MOE_EXPERTS) is None
    assert pt.unscoped_share(bare) is None
    assert pt.unscoped_share(ops) == 100.0 * (3 + 3 + 3) / 80


def test_a_loops_envelope_is_unscoped_only_where_no_scoped_body_covers_it():
    """The decode program's expert loop is a ``while`` the trace shows under
    no scope, around its body's operations, which carry ``moe.experts``:
    the union forms count the body once and the envelope's own turns as
    unscoped."""
    j = "jit(p)/"
    ops = _ms([("%a = qkv", 0, 2, j + "attn.qkv/dot:"),
               ("%while.1 = while", 2, 6, ""),            # [2, 8)
               ("%b = experts", 2, 2, j + "moe.experts/dot:"),
               ("%c = experts", 5, 2, j + "moe.experts/dot:"),  # gap [4, 5)
               ("%copy.2 = copy", 8, 2, "")])
    # busy 10 ms; scoped 2 + 2 + 2; unscoped: the loop's turn [4, 5), its
    # tail [7, 8) and the copy
    assert pt.unscoped_share(ops) == pytest.approx(100.0 * 4 / 10)
    spans = _ms([(pt.DECODE, 0, 12, {"n": 1, "kv_rows": 5})])
    (row,) = pt.busy_by_scope(ops, spans, pt.DECODE)
    assert row == {"attn.qkv": 2 * MS, None: 8 * MS, "moe.experts": 4 * MS}
    assert sum(row.values()) == 14 * MS  # what a sum over scopes would say
    assert tr.busy_ns(ops) == 10 * MS  # what the union says
    # a family's own scope list decides what is scoped
    assert pt.unscoped_share(ops, ("attn.qkv",)) \
        == pytest.approx(100.0 * 8 / 10)
    assert pt.unscoped_share(ops, ("conv.mix",)) is None


@pytest.fixture(scope="module")
def view(trace_path):
    trace = tr.load_xplane(trace_path)
    return R.TraceView(trace, {"trace_path": trace_path}, MIXTRAL, {},
                       PEAKS, 1)


MIXTRAL = R.load_json(os.path.join(R.HERE, "configs",
                                   "mixtral-8x7b-serve.json"))
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
EXPECTED = {
    "decode_moe_experts_dev_ms": 8.0,
    "decode_moe_exchange_dev_ms": 1.0,
    "decode_full_attention_dev_ms": 7.0,
    "prefill_moe_experts_dev_ms": 20.0,
    "prefill_moe_exchange_dev_ms": 3.0,
    "prefill_full_attention_dev_ms": 12.0,
    "unscoped_dev_share": 100.0 * 9 / 80,
    # one row decoding reaches 2 of 8 experts; the program's 20 ms as one
    # union, its two spans alike but for one cached row
    "decode_hbm_roofline_share": 100.0 * (
        flops.decode_step_bytes(MIXTRAL, 2.0, 300)
        + flops.decode_step_bytes(MIXTRAL, 2.0, 301)) / 2 / 819e9 / 20e-3,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader(view, metric):
    assert R.load_reader(metric).read(view) == pytest.approx(EXPECTED[metric])


def test_every_scoped_reading_of_the_first_cell_is_here_and_none_raises_on_a_parent():
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    def scoped(name):  # a reader over ``chipbench/scopes.py``
        with open(os.path.join(R.HERE, "layer_metrics", name + ".py")) as f:
            return "chipbench import scopes" in f.read().replace(
                "chipbench.scopes import", "chipbench import scopes")

    mine = [m["name"] for m in R.metrics_for(
        bench["per_layer"], "mixtral-8x7b-serve.chat") if scoped(m["name"])]
    # the two programs' times by the benchmark's own spans are the
    # fixture-trace test's (test_trace_reduce.py)
    assert sorted(set(mine) - {"decode_step_dev_ms", "prefill_step_dev_ms"}) \
        == sorted(EXPECTED)
    # a program without spans (the parent): every reader gives None
    plane = Plane("/device:TPU:0")
    plane.line("XLA Ops", _ms([("%fusion.1 = x", 5, 10, {}, "jit(f)/dot:")]))
    host = Plane("/host:CPU")
    host.line("python3", _ms([("chipbench.window", 0, 50)]))
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "p.xplane.pb")
        with open(path, "wb") as f:
            f.write(plane.encode() + host.encode())
        v = R.TraceView(tr.load_xplane(path), {"trace_path": path}, MIXTRAL,
                        {}, PEAKS, 1)
        assert [R.load_reader(m).read(v) for m in mine] == [None] * len(mine)
    v = R.TraceView(None, {"trace_path": None}, MIXTRAL, {}, PEAKS, 1)
    assert [R.load_reader(m).read(v) for m in mine] == [None] * len(mine)
    json.dumps(EXPECTED)

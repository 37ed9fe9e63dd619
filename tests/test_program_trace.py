"""The program's own tracing, as a profiler sees it (ISSUE 24).

* every ``obs.span`` of the serving path lands in an open ``jax.profiler``
  session's host plane as ``uccl.<name>``, nested on the engine's thread,
  with its entry arguments — whether or not the obs ring is on;
* with no session and the ring off nothing is recorded, and ``obs`` still
  imports without JAX;
* the ring, when on, holds the same spans (plus the exit-time arguments);
* the device programs carry their ``jax.named_scope``s into the compiled
  text, and each jitted serving program has a module name of its own;
* (ISSUE 40) a step's ``wire.prefill`` and ``wire.decode`` share its
  number, and a request's ``admit`` and ``first_token`` reach the session
  as empty ``uccl.<mark>`` annotations with the request's ``rid``
  (``obs.mark``: what a reader of the trace consumes, nothing else), the
  ring keeping the instants it had.
"""

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from uccl_tpu import obs
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import DenseBackend, MoEBackend, ServingEngine
from uccl_tpu.serving.backend import DensePrograms

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, SLOTS, MAX_SEQ = 8, 2, 64

ENGINE_SPANS = ("engine.step", "engine.admit", "wire.prefill", "wire.decode",
                "backend.stage", "backend.launch", "backend.fetch",
                "engine.retire")
SPAN_ARGS = {
    "engine.step": {"queued", "active", "prefilling", "decoding"},
    "engine.admit": {"queued"},
    "wire.prefill": {"step", "n", "chunk"},
    "wire.decode": {"step", "n", "kv_rows"},
}
# a request's lifecycle in the ring, in order, with what each instant
# carries; MARKS are the two bridged to the profiler, with the ``rid`` a
# reader pairs them by
LIFECYCLE = {"submit": {"rid", "prompt_len", "max_new_tokens"},
             "admit": {"rid", "slot"},
             "first_token": {"rid", "ttft_ms"},
             "finish": {"reason", "tokens"}}
MARKS = ("admit", "first_token")
PARENT = {"engine.admit": "engine.step", "engine.retire": "engine.step",
          "wire.prefill": "engine.step", "wire.decode": "engine.step",
          "backend.stage": "wire.", "backend.launch": "wire.",
          "backend.fetch": "wire."}


@pytest.fixture(scope="module")
def moe(devices):
    cfg = MoEServeConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                         n_kv_heads=2, head_dim=8, moe_experts=8,
                         moe_topk=2, moe_ffn=64)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    params = srv.shard_params(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, srv, params


def _engine(moe):
    _, srv, params = moe
    backend = MoEBackend(srv, params, batch_local=SLOTS, max_seq=MAX_SEQ,
                         decode_impl="sort")
    return ServingEngine(backend, prefill_chunk=CHUNK)


def _serve_two(eng):
    """Two requests through chunked prefill and a few decode steps."""
    reqs = [eng.submit(np.arange(1, 13, dtype=np.int32), max_new_tokens=3),
            eng.submit(np.arange(3, 8, dtype=np.int32), max_new_tokens=4)]
    eng.drain()
    assert all(r.is_done() for r in reqs)
    return reqs


def _host_events(path):
    """[(name, start_ns, end_ns, args, hlo_module)] of one xplane's host
    plane, every thread."""
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, stats,
                            stats.get("hlo_module")))
    return out


@pytest.fixture(scope="module")
def profiled(moe, tmp_path_factory):
    """Host events of a tiny engine served inside a profiler session with
    the obs ring OFF (the bridge must not need it)."""
    assert not obs.tracing_enabled()
    eng = _engine(moe)
    _serve_two(eng)  # compile outside the session
    out = str(tmp_path_factory.mktemp("xplane"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        _serve_two(eng)
    finally:
        jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return _host_events(path)


@pytest.mark.parametrize("span", ENGINE_SPANS)
def test_span_reaches_the_profiler_nested_and_with_its_arguments(
        profiled, span):
    mine = [e for e in profiled if e[0] == "uccl." + span]
    assert mine, f"no uccl.{span} in the host plane"
    for name, t0, t1, args, _ in mine:
        assert SPAN_ARGS.get(span, set()) <= set(args), (span, args)
        want = PARENT.get(span)
        if want is not None:
            outer = [e for e in profiled if e[0].startswith("uccl." + want)
                     and e[1] <= t0 and t1 <= e[2] and e is not mine]
            assert outer, f"uccl.{span} at {t0} lies in no uccl.{want}*"
    if span == "wire.decode":
        # prompt rows + tokens generated so far, over the decoding slots
        assert all(e[3]["kv_rows"] >= e[3]["n"] > 0 for e in mine)
    if span == "backend.launch":
        # one per backend call: every wire span holds exactly one
        wires = [e for e in profiled if e[0].startswith("uccl.wire.")]
        assert len(mine) == len(wires)


def test_a_steps_calls_share_its_number(profiled):
    steps = sorted((e for e in profiled if e[0] == "uccl.engine.step"),
                   key=lambda e: e[1])
    wires = [e for e in profiled if e[0].startswith("uccl.wire.")]
    assert {e[0] for e in wires} == {"uccl.wire.prefill", "uccl.wire.decode"}
    numbers = []  # each engine.step's: what its calls carry, all the same
    for _, t0, t1, _, _ in steps:
        mine = {e[3]["step"] for e in wires if t0 <= e[1] and e[2] <= t1}
        assert len(mine) == 1, "a step's calls carry one number"
        numbers.extend(mine)
    # the engine counts its steps: back-to-back steps differ by one
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))
    # a chunk step that also decodes: two calls, one number
    both = [n for n in numbers
            if {e[0] for e in wires if e[3]["step"] == n}
            == {"uccl.wire.prefill", "uccl.wire.decode"}]
    assert both


@pytest.mark.parametrize("mark", MARKS)
def test_request_mark_reaches_the_profiler_with_its_rid(profiled, mark):
    mine = [e for e in profiled if e[0] == "uccl." + mark]
    # the session served the engine's third and fourth request, once each
    assert sorted(e[3]["rid"] for e in mine) == [2, 3]
    for _, t0, t1, args, _ in mine:
        assert LIFECYCLE[mark] <= set(args)
        assert t1 - t0 < 1e6  # empty: a mark covers no time (under a ms)


@pytest.mark.parametrize("instant", sorted(set(LIFECYCLE) - set(MARKS))
                         + ["prefill_chunk"])
def test_what_no_reader_consumes_stays_out_of_the_profiler(profiled, instant):
    assert not [e for e in profiled if e[0] == "uccl." + instant]


def test_a_requests_wait_holds_its_prefill_calls(profiled):
    for rid in (2, 3):
        admit, first = sorted((e for e in profiled if e[0][5:] in MARKS
                               and e[3].get("rid") == rid),
                              key=lambda e: e[1])
        assert (admit[0], first[0]) == ("uccl.admit", "uccl.first_token")
        # the wait a reader sums device time over: it holds the request's
        # prefill calls (12 and 5 tokens at a chunk of 8: two and one)
        calls = [e for e in profiled if e[0] == "uccl.wire.prefill"
                 and admit[1] <= e[1] and e[2] <= first[2]]
        assert len(calls) >= (2 if rid == 2 else 1)


def test_the_experts_count_arrives_as_before(profiled):
    counts = [e for e in profiled if e[0] == "uccl.ep.experts"]
    decodes = [e for e in profiled if e[0] == "uccl.wire.decode"]
    assert len(counts) == len(decodes) > 0
    for _, t0, t1, args, _ in counts:
        assert set(args) == {"experts_read", "experts_held"}
        assert 0 < args["experts_read"] <= args["experts_held"] == 8 * 2
        fetch_ends = [e[2] for e in profiled if e[0] == "uccl.backend.fetch"]
        (wire,) = [e for e in decodes if e[1] <= t0 and t1 <= e[2]]
        # after the fetch that brought it, still inside the wire span
        assert any(wire[1] <= f <= t0 for f in fetch_ends)


def test_a_decode_call_runs_one_program(profiled):
    """(ISSUE 43) Whatever executes inside a ``wire.decode`` is the step's
    own program: no trivial program is dispatched before or after it."""
    decodes = [e for e in profiled if e[0] == "uccl.wire.decode"]
    assert decodes
    for _, t0, t1, _, _ in decodes:
        ran = {e[4] for e in profiled if e[4] and t0 <= e[1] and e[2] <= t1}
        assert ran == {"jit_uccl_moe_decode_slots"}, ran


def test_module_names_tell_the_programs_apart(profiled, devices):
    modules = {e[4] for e in profiled if e[4]}
    assert {"jit_uccl_moe_prefill_slots",
            "jit_uccl_moe_decode_slots"} <= modules
    assert not {m for m in modules if m in ("jit_f", "jit_gen", "jit_run")}
    from uccl_tpu.models.dense import DenseConfig, init_params as dense_init

    cfg = DenseConfig(vocab=64, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
                      head_dim=8, ffn=64)
    backend = DenseBackend(dense_init(jax.random.PRNGKey(1), cfg), cfg,
                           n_slots=2, max_seq=32)
    # the compiled callables behind the backend's three programs
    assert isinstance(backend.programs, DensePrograms)
    names = {backend.programs.compiled(kind, s, False, False).__name__
             for kind, s in (("prefill", 8), ("decode", 1), ("verify", 3))}
    assert names == {"uccl_dense_prefill_slots", "uccl_dense_decode_slots",
                     "uccl_dense_verify_slots"}


def test_nothing_is_recorded_with_no_session_and_the_ring_off(moe):
    assert obs.get_tracer() is None
    eng = _engine(moe)
    _serve_two(eng)
    eng.close()
    assert obs.get_tracer() is None  # nothing switched it on
    assert obs.span("x") is not obs.span("x")  # bridged: JAX is loaded here


def test_obs_imports_without_jax():
    code = ("import sys, tracemalloc\n"
            "from uccl_tpu import obs\n"
            "from uccl_tpu.obs import tracer\n"
            "with obs.span('engine.step', 'engine', queued=1) as sp:\n"
            "    sp.add(finished=0)\n"
            "assert obs.span('a') is obs.span('b')  # the cached no-op\n"
            "obs.mark('admit', 'req-0', rid=0, slot=1)  # binds what it can\n"
            "tracemalloc.start()\n"
            "a = tracemalloc.take_snapshot()\n"
            "for _ in range(1000):\n"
            "    obs.mark('admit', 'req-0', rid=0, slot=1)\n"
            "b = tracemalloc.take_snapshot()\n"
            "held = [d for d in b.compare_to(a, 'filename')\n"
            "        if d.traceback[0].filename == tracer.__file__]\n"
            "assert not held, held  # the ring off, no JAX: nothing kept\n"
            "assert obs.get_tracer() is None\n"
            "assert 'jax' not in sys.modules, 'obs pulled JAX in'\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   timeout=120)


def test_ring_holds_the_same_spans_with_exit_arguments(moe):
    eng = _engine(moe)
    _serve_two(eng)
    tr = obs.enable_tracing()
    try:
        reqs = _serve_two(eng)
        evs = tr.events()
    finally:
        obs.disable_tracing()
        eng.close()
    spans = [e for e in evs if e.ph == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e.name, []).append(e)
    assert set(ENGINE_SPANS) <= set(by_name)
    assert {e.track for e in by_name["engine.step"]} == {"engine"}
    assert {e.track for e in by_name["wire.decode"]} == {"wire"}
    steps = by_name["engine.step"]
    assert all({"queued", "active", "prefilling", "decoding", "finished"}
               <= set(e.args) for e in steps)
    assert sum(e.args["finished"] for e in steps) == len(reqs)
    for e in by_name["backend.launch"]:  # nested in a wire span, in time
        assert any(w.ts_us <= e.ts_us
                   and e.ts_us + e.dur_us <= w.ts_us + w.dur_us + 1e-3
                   for n in ("wire.prefill", "wire.decode")
                   for w in by_name[n])
    # the per-request lifecycle is untouched: the instants it had, on the
    # request's own row, admit and first_token now with the request's rid
    for r in reqs:
        mine = [e for e in evs if e.track == r.track]
        names = [e.name for e in mine]
        assert names[0] == "submit" and names[-1] == "finish"
        assert "first_token" in names and "prefill_chunk" in names
        marks = [e for e in mine if e.name in LIFECYCLE]
        assert [e.name for e in marks] == list(LIFECYCLE)
        assert all(e.ph == "i" and e.args.get("rid", r.rid) == r.rid
                   and LIFECYCLE[e.name] <= set(e.args) for e in marks)
    counts = [e for e in evs if e.name == "ep.experts"]
    assert len(counts) == len(by_name["wire.decode"])
    assert all(e.ph == "i" and e.track == "wire" for e in counts)
    # and each step's calls carry its number, as in the profiler's trace
    numbers = sorted({e.args["step"] for n in ("wire.prefill", "wire.decode")
                      for e in by_name[n]})
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))


# -- scope names in the compiled programs -----------------------------------

SERVE_SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core",
                "attn.out", "moe.router", "moe.route", "moe.dispatch",
                "moe.experts", "moe.combine", "head")
TRAIN_SCOPES = ("embed", "attn.qkv", "attn.core", "attn.out", "moe.router",
                "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
                "head")


@pytest.fixture(scope="module")
def program_text(moe, devices):
    """Compiled text of the tiny decode, prefill and training-step
    programs, built once."""
    cfg, srv, params = moe
    cache = srv.slot_cache(SLOTS, MAX_SEQ)
    grid = lambda a, dt: jnp.asarray(np.asarray(a, dt).reshape(1, SLOTS, -1))

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    def decode_ll(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="ll")

    def prefill(p, tok, lens, mask, k, v, ln):
        return srv.prefill_slots(p, tok, lens, mask, MoESlotCache(k, v, ln))

    tok1 = grid([1, 2], np.int32)[..., 0]
    act = grid([1, 1], bool)[..., 0]
    text = {
        "decode": jax.jit(decode).lower(
            params, tok1, act, *cache).compile().as_text(),
        "decode_ll": jax.jit(decode_ll).lower(
            params, tok1, act, *cache).compile().as_text(),
        "prefill": jax.jit(prefill).lower(
            params, grid(np.ones((SLOTS, CHUNK)), np.int32),
            grid([CHUNK, CHUNK], np.int32)[..., 0], act,
            *cache).compile().as_text(),
    }

    from uccl_tpu.models.flagship import (
        FlagshipConfig, init_params as train_init, make_train_step,
        shard_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    tcfg = FlagshipConfig(vocab=64, dim=32, n_layers=1, n_heads=4,
                          n_kv_heads=2, head_dim=8, moe_experts=4,
                          moe_topk=2, moe_ffn=32, capacity_factor=2.0)
    mesh = make_mesh(MeshConfig(dp=2), devices[:2])
    tparams = shard_params(train_init(jax.random.PRNGKey(2), tcfg), mesh,
                           tcfg)
    train_step, init_opt = make_train_step(tcfg, mesh)
    data = jnp.zeros((4, 16), jnp.int32)
    lowered = jax.jit(train_step).lower(tparams, init_opt(tparams), data,
                                        data)
    text["train"] = lowered.compile().as_text()
    text["train_module"] = lowered.as_text()[:200]
    return text


@pytest.mark.parametrize("scope", SERVE_SCOPES)
@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_serving_programs_carry_their_scopes(program_text, program, scope):
    assert f"/{scope}/" in program_text[program], (
        f"{scope} is in no op_name of the compiled {program} program")


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_training_step_carries_its_scopes(program_text, scope):
    text = program_text["train"]
    assert f"/{scope}/" in text
    if scope == "moe.experts":
        # forward, backward and rematerialised copies keep the component
        import re

        paths = [m for m in re.findall(r'op_name="([^"]*)"', text)
                 if "/moe.experts/" in m]
        assert any("/jvp()/" in m for m in paths)
        assert any("transpose(jvp())" in m for m in paths)
        assert any("rematted_computation/moe.experts/" in m for m in paths)


def test_ll_path_and_module_names_of_the_compiled_programs(program_text):
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine"):
        assert f"/{scope}/" in program_text["decode_ll"]
    assert "jit(uccl_moe_decode_slots)" in program_text["decode"]
    assert "jit(uccl_moe_prefill_slots)" in program_text["prefill"]
    assert "jit_train_step" in program_text["train_module"]


def test_flash_kernels_are_scoped():
    from uccl_tpu.ops.pallas_attention import flash_attention

    q = jnp.zeros((1, 16, 2, 8), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, interpret=True).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q).as_text(
        debug_info=True)
    assert "attn.flash" in text
    # forward and backward kernels both
    assert text.count("attn.flash") >= 3

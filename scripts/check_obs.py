#!/usr/bin/env python
"""Validate the obs smoke arm's artifacts (qa.sh / ci.yml).

Usage: python scripts/check_obs.py TRACE_JSON METRICS_PROM
       python scripts/check_obs.py --quant METRICS_PROM WIRE_DTYPE
       python scripts/check_obs.py --plan METRICS_PROM BENCH_JSON
       python scripts/check_obs.py --a2a-sched METRICS_PROM BENCH_JSON
       python scripts/check_obs.py --disagg METRICS_PROM

Asserts, with a named failure for each:

* the trace parses (``json.loads``) and its ``traceEvents`` are a valid
  Chrome trace: every ``B`` has a matching ``E`` on its tid, every ``X``
  duration is non-negative;
* at least one request track carries the complete lifecycle
  (submit → admit → prefill[(-chunk)] → first_token → finish, in timeline
  order), and engine-step + wire spans exist;
* the metrics file is Prometheus text containing the wire-fallback and
  serving goodput series.

``--quant`` mode (the quantized-wire smoke arm): the metrics file must
export a nonzero ``ep_bytes_total{...,wire_dtype="<WIRE_DTYPE>"}`` sample
— i.e. a quantized run's wire bytes landed on the labeled byte series the
benches read bandwidth off (docs/QUANT_WIRE.md), not on an unlabeled or
full-precision bucket.

``--plan`` mode (the planner smoke arm): the metrics file must export a
nonzero ``collective_plan_total`` sample (every planner decision lands
there) plus the ``collective_plan_predicted_us`` gauge, and every arm of
the bench's ``all_reduce_plan`` JSON lines must carry an ``algo`` label
present on that counter — i.e. bench arms were labeled off the REAL plan
series, not mirrored selector math (docs/PLAN_BENCH.md round-8).

``--disagg`` mode (the disaggregated-serving smoke arm,
examples/disagg_kv.py --metrics-out): the metrics file must carry nonzero
KV-handoff telemetry — one-sided write bytes on
``p2p_bytes_total{verb="write"}``, streamed slabs on
``kv_stream_chunks_total{role="tx"}``, and ≥1 ``prefix_cache_hits_total``
(the run's shared-prefix requests really reused cached KV) with the
``serving_prefill_tokens_total`` computed/skipped split present — i.e.
the chunk-streamed handoff AND the prefix cache both demonstrably fired.

``--spec`` mode (the speculative-decoding smoke arm, serve --server
--spec-k ... --metrics-out): the metrics file must show ≥1 ACCEPTED
speculation on ``spec_tokens_total{outcome="accepted"}`` plus nonzero
bonus tokens and the ``spec_accepted_len_total`` histogram, and the
engine's committed-token accounting (``uccl_serving_decode_tokens``)
must be present and nonzero — i.e. speculation really ran, really
accepted drafts, and throughput derives from committed tokens rather
than an assumed one token per step.

``--fleet`` mode (the fleet-tracing smoke arm: the 2-process disagg
example dumped per-role with --trace-out/--metrics-out, merged by
scripts/trace_merge.py and federated by uccl_tpu.obs.aggregate): the
MERGED trace must hold >= 1 request whose events span >= 2 pids with a
resolved cross-process flow pair (s on one pid, f on another) and
causally ordered lifecycle stages (submit <= grant <= adopt) after clock
alignment; the FLEET metrics must carry >= 2 replica-labeled
``serving_ttft_seconds`` histograms whose fleet-summed ``_count`` equals
the per-replica sum, and every replica exporting a sample-derived
``uccl_serving_ttft_ms`` percentile must agree with its own
histogram-derived percentile within one bucket width — i.e. tracing
crossed the process boundary, the clocks aligned, and the merge-safe
histograms tell the same story as the exact in-process samples.

``--transport`` mode (the windowed-SACK-transport smoke arm,
benchmarks/incast_bench.py --smoke --metrics-out ... [--json-out ...]):
the metrics file must show the lossy+reordering loopback run really
exercised the transport — nonzero ``p2p_channel_retx_total`` WITH its
``kind="fast"|"rto"`` split (selective repeat's fast-vs-timeout
recovery), nonzero chunk issues, the credit plane visible (granted and
consumed gauges nonzero, ``p2p_credit_stall_seconds_total`` present)
and a nonzero srtt gauge (completion RTTs fed the estimator); with a
bench JSON, every arm must carry its counter-delta retx labels.

``--weights`` mode (the bandwidth-optimal collectives + weight-push
smoke arm: ``weight_push_bench.py --smoke --metrics-out`` and
``all_reduce_perf.py --bench bcast,ag --metrics-out``): the PUSH metrics
must show the fleet distribution really ran — nonzero
``weight_push_bytes_total`` for BOTH roles (tx and rx), a counted
``weight_push_versions_total`` publish, ≥1 peer on
``weight_push_peers_total`` and the service-verb byte series
``p2p_bytes_total{verb="weight_push"}`` nonzero; the PLAN metrics must
carry nonzero ``collective_plan_total`` decisions for BOTH new verbs
(``verb="broadcast"`` and ``verb="all_gather"``) — i.e. the planner's
broadcast/all-gather coverage and the weight-push plane both
demonstrably fired.

``--chaos`` mode (the fault-tolerance smoke arm,
benchmarks/chaos_bench.py --smoke --metrics-out [--json-out]): the
metrics must prove the chaos really bit AND the fleet really recovered —
≥1 recovered request on ``serving_recovered_total`` with a nonzero
resubmitted/restarted split (not everything lost), the EXTENDED
conservation invariant ``submitted == completed + active + queued +
rejected + expired + lost`` re-asserted from the exported
``uccl_serving_*`` fleet lines, ≥1 reclaimed GRANT lease on
``disagg_leases_expired_total``, and every ``serving_leaked_slots``
component gauge exactly 0 (survivors AND the decode pool's reclaimed
slots). With a bench JSON, every arm must be ``oracle_exact`` with a
counter-delta ``recovered`` label block.

``--a2a-sched`` mode (the contention-aware scheduled a2a smoke arm,
``ep_bench.py --skew ... --a2a-sched on --metrics-out``): the metrics
must show a scheduled decision really landed and really drove rounds —
a nonzero ``collective_plan_total{verb="ep_a2a",algo="ep_sched"}``
sample, nonzero ``ep_a2a_rounds_total{algo="ep_sched"}``, and the
``ep_a2a_skew`` gauge present at >= 1.0; every arm of the bench's
``ep_sched_sweep`` JSON must be bit-identical to its off-arm anchor
(the schedule is a pure reordering of the same write-once DMAs), carry
algo labels present on the plan counter, and >= 1 arm must have
actually ridden the schedule (``sched_active`` with counted
``ep_sched`` rounds) — i.e. the scheduled wire demonstrably fired,
oracle-exact, with every label counter-audited.

``--kv-tiers`` mode (the tiered-KV-cache smoke arm,
``serving_bench.py --kv-tiers ... --check-oracle --metrics-out``): the
metrics must prove every exercised tier demonstrably cycled — ≥1 counted
``kv_tier_demotions_total`` AND ``kv_tier_promotions_total`` for the t1
tier (and for t2 when any bench arm ran a t1-t2 config), nonzero
``kv_tier_resident_bytes{tier="t1"}`` (entries really live at rest in
the host pool), the ``prefix_cache_resident_tokens`` gauge exported, and
— from the bench JSON — every lossless-at-rest arm (``exact_rest``)
``oracle_exact`` with ≥1 such arm present, every tier-enabled arm's
traffic labeled off real counter deltas.

``--router`` mode (the replica-router smoke arm, serve --server
--replicas N --priority-classes ... --metrics-out): the metrics file
must carry ≥2 replica-labeled ``serving_router_requests_total`` series
with every replica nonzero (the router really spread admissions), ≥1
counted ``serving_preempted_total`` with resumes == preemptions (every
paused request came back), and per-class SLO percentile series
(``uccl_serving_class_ttft_ms{cls="interactive"...}`` + batch) — i.e.
routing, preemption and the per-class surfaces all demonstrably fired.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict


def fail(msg: str) -> None:
    print(f"check_obs: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def check_trace(path: str) -> None:
    with open(path) as f:
        trace = json.loads(f.read())
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        fail(f"{path}: no traceEvents")
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e.get("name") == "thread_name"}
    by_track = defaultdict(list)
    for ev in evs:
        if ev["ph"] == "X" and ev.get("dur", 0) < 0:
            fail(f"{path}: X event {ev['name']!r} with negative dur")
        if ev["ph"] in "Xi":
            track = tracks.get(ev["tid"])
            if track is None:
                fail(f"{path}: event on unnamed tid {ev['tid']}")
            by_track[track].append(ev)

    complete = 0
    for track, track_evs in by_track.items():
        if not track.startswith("req-"):
            continue
        names = [ev["name"]
                 for ev in sorted(track_evs, key=lambda ev: ev["ts"])]
        if ("submit" in names and "admit" in names
                and ("prefill" in names or "prefill_chunk" in names)
                and "first_token" in names and "finish" in names):
            order = [names.index("submit"), names.index("admit"),
                     min(i for i, n in enumerate(names)
                         if n in ("prefill", "prefill_chunk")),
                     names.index("first_token"), names.index("finish")]
            if order == sorted(order):
                complete += 1
    if complete < 1:
        fail(f"{path}: no request track with a complete "
             f"submit->admit->prefill->first_token->finish timeline "
             f"(tracks: {sorted(by_track)})")
    if not any(ev["name"] == "engine.step"
               for ev in by_track.get("engine", [])):
        fail(f"{path}: no engine.step spans")
    if not any(ev["name"].startswith("wire.")
               for ev in by_track.get("wire", [])):
        fail(f"{path}: no wire spans")
    print(f"check_obs: trace OK — {len(evs)} events, "
          f"{complete} complete request timeline(s)")


def check_metrics(path: str) -> None:
    with open(path) as f:
        text = f.read()
    for series in ("ep_wire_fallback_total", "uccl_serving_goodput_tok_s"):
        if series not in text:
            fail(f"{path}: missing series {series!r}")
    print(f"check_obs: metrics OK — {len(text.splitlines())} lines")


def check_quant_metrics(path: str, wire_dtype: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()
    label = f'wire_dtype="{wire_dtype}"'
    hits = [ln for ln in lines
            if ln.startswith("ep_bytes_total{") and label in ln]
    if not hits:
        fail(f"{path}: no ep_bytes_total sample labeled {label} — the "
             f"quantized run's wire bytes never reached the labeled series")
    nonzero = [ln for ln in hits if float(ln.rsplit(" ", 1)[1]) > 0]
    if not nonzero:
        fail(f"{path}: ep_bytes_total{{...,{label}}} present but zero")
    print(f"check_obs: quant metrics OK — {len(nonzero)} nonzero "
          f"{label} byte series")


def check_plan_metrics(path: str, bench_json: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()
    hits = [ln for ln in lines if ln.startswith("collective_plan_total{")]
    nonzero = [ln for ln in hits if float(ln.rsplit(" ", 1)[1]) > 0]
    if not nonzero:
        fail(f"{path}: no nonzero collective_plan_total sample — the "
             f"planner's decisions never reached the plan series")
    if not any(ln.startswith("collective_plan_predicted_us")
               for ln in lines):
        fail(f"{path}: missing collective_plan_predicted_us gauge — no "
             f"modeled cost beside the decisions")
    algos = set()
    for ln in nonzero:
        for part in ln[ln.index("{") + 1:ln.index("}")].split(","):
            k, _, v = part.partition("=")
            if k == "algo":
                algos.add(v.strip('"'))
    arms = 0
    with open(bench_json) as f:
        for raw in f:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                rec = json.loads(raw)
            except json.JSONDecodeError:
                continue
            if rec.get("bench") != "all_reduce_plan":
                continue
            for arm in rec.get("arms", []):
                arms += 1
                if arm.get("algo") not in algos:
                    fail(f"{bench_json}: arm labeled {arm.get('algo')!r} "
                         f"has no collective_plan_total series in {path} "
                         f"(counter algos: {sorted(algos)}) — the label "
                         f"did not come off the plan counter")
                if "modeled_us" not in arm:
                    fail(f"{bench_json}: arm {arm.get('algo')!r} carries "
                         f"no modeled_us")
    if arms < 1:
        fail(f"{bench_json}: no all_reduce_plan arms to cross-check")
    print(f"check_obs: plan metrics OK — {len(nonzero)} nonzero plan "
          f"series, {arms} bench arm(s) label-matched "
          f"(algos: {sorted(algos)})")


def _prom_total(lines, prefix: str, path: str) -> float:
    """Sum every sample whose series line starts with ``prefix`` (name or
    name{label-prefix}); a missing series is a named failure — the shared
    parse of the disagg and spec validators."""
    vals = [float(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith(prefix)]
    if not vals:
        fail(f"{path}: no sample for {prefix!r}")
    return sum(vals)


def check_disagg_metrics(path: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()

    def total(prefix: str) -> float:
        return _prom_total(lines, prefix, path)

    if total('p2p_bytes_total{verb="write"}') <= 0:
        fail(f"{path}: zero one-sided write bytes — no KV crossed the "
             f"p2p wire")
    if total('kv_stream_chunks_total{role="tx"}') <= 0:
        fail(f"{path}: zero streamed KV slabs — the chunk stream never "
             f"fired")
    hits = total("prefix_cache_hits_total")
    if hits < 1:
        fail(f"{path}: no prefix_cache_hits_total — the shared-prefix "
             f"requests never reused cached KV")
    if total('serving_prefill_tokens_total{kind="skipped"}') <= 0:
        fail(f"{path}: prefix hits counted but no skipped prefill tokens "
             f"— the hit did not shorten prefill")
    total('serving_prefill_tokens_total{kind="computed"}')  # must exist
    print(f"check_obs: disagg metrics OK — {int(hits)} prefix-cache "
          f"hit(s), stream + skip series all nonzero")


def check_transport_metrics(path: str, bench_json: str = "") -> None:
    """The windowed-transport smoke arm (incast_bench --smoke): the lossy
    +reordering loopback run must land its evidence on the REAL series —
    nonzero SACK retransmissions with the fast/timeout split exported
    (p2p_channel_retx_total{kind=}), chunk issues counted, the credit
    plane visible (granted/consumed gauges nonzero, stall counter
    present), and the RTT estimator fed (srtt gauge nonzero). With a
    bench JSON, every arm's retx labels must have come from counter
    deltas (retx_fast/retx_rto fields present and consistent with a
    counted total)."""
    with open(path) as f:
        lines = f.read().splitlines()

    def total(prefix: str) -> float:
        return _prom_total(lines, prefix, path)

    if total("p2p_channel_chunks_total") <= 0:
        fail(f"{path}: zero channel chunks — the windowed spray never ran")
    retx_lines = [ln for ln in lines
                  if ln.startswith("p2p_channel_retx_total")]
    split = [ln for ln in retx_lines if 'kind="' in ln]
    if not split:
        fail(f"{path}: p2p_channel_retx_total carries no kind= split — "
             f"fast-vs-timeout recovery is not distinguishable")
    retx_total = sum(float(ln.rsplit(" ", 1)[1]) for ln in split)
    if retx_total <= 0:
        fail(f"{path}: zero SACK retransmissions — the lossy arm never "
             f"exercised recovery")
    for ln in split:
        kind = ln.split('kind="', 1)[1].split('"', 1)[0]
        if kind not in ("fast", "rto"):
            fail(f"{path}: unexpected retx kind {kind!r}")
    if total("p2p_credit_granted_bytes") <= 0:
        fail(f"{path}: no pull credit granted — the eqds arm never ran "
             f"receiver-driven")
    if total("p2p_credit_consumed_bytes") <= 0:
        fail(f"{path}: no pull credit consumed — senders never issued "
             f"under credit")
    if not any(ln.startswith("p2p_credit_stall_seconds_total")
               for ln in lines):
        fail(f"{path}: missing p2p_credit_stall_seconds_total — incast "
             f"credit waits are invisible")
    if total("p2p_chan_srtt_us") <= 0:
        fail(f"{path}: p2p_chan_srtt_us zero — completion RTTs never fed "
             f"the estimator")
    arms_checked = 0
    if bench_json:
        with open(bench_json) as f:
            for ln in f.read().splitlines():
                if not ln.strip():
                    continue
                arm = json.loads(ln)
                for k in ("retx_fast", "retx_rto", "chunks_issued"):
                    if k not in arm:
                        fail(f"{bench_json}: arm {arm.get('cc')} missing "
                             f"counter-delta label {k!r}")
                arms_checked += 1
        if not arms_checked:
            fail(f"{bench_json}: no bench arms recorded")
    print(f"check_obs: transport metrics OK — {int(retx_total)} SACK "
          f"retx with kind split, credit plane visible"
          + (f", {arms_checked} counter-labeled arm(s)"
             if bench_json else ""))


def check_spec_metrics(path: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()

    def total(prefix: str) -> float:
        return _prom_total(lines, prefix, path)

    acc = total('spec_tokens_total{outcome="accepted"}')
    if acc < 1:
        fail(f"{path}: zero accepted speculations — the drafter never "
             f"predicted the target's greedy output (counted on "
             f'spec_tokens_total{{outcome="accepted"}})')
    if total('spec_tokens_total{outcome="bonus"}') <= 0:
        fail(f"{path}: zero bonus tokens — no verify window ever ran")
    total('spec_tokens_total{outcome="rejected"}')  # series must exist
    if not any(ln.startswith("spec_accepted_len_total{") for ln in lines):
        fail(f"{path}: missing spec_accepted_len_total histogram")
    if total("uccl_serving_decode_tokens") <= 0:
        fail(f"{path}: uccl_serving_decode_tokens missing or zero — "
             f"decode throughput is not being derived from committed "
             f"tokens")
    print(f"check_obs: spec metrics OK — {int(acc)} accepted "
          f"speculation(s), bonus + histogram + committed-token series "
          f"all present")


def check_weights_metrics(push_path: str, plan_path: str) -> None:
    with open(push_path) as f:
        lines = f.read().splitlines()

    def total(prefix: str) -> float:
        return _prom_total(lines, prefix, push_path)

    for role in ("tx", "rx"):
        hits = [ln for ln in lines
                if ln.startswith("weight_push_bytes_total{")
                and f'role="{role}"' in ln
                and float(ln.rsplit(" ", 1)[1]) > 0]
        if not hits:
            fail(f"{push_path}: no nonzero weight_push_bytes_total "
                 f"role={role} — the push plane never moved bytes that "
                 f"way")
    if total("weight_push_versions_total") < 1:
        fail(f"{push_path}: no counted snapshot publish")
    peers = total("weight_push_peers_total")
    if peers < 1:
        fail(f"{push_path}: no peer ever reached consistency")
    if total('p2p_bytes_total{verb="weight_push"}') <= 0:
        fail(f"{push_path}: weight bytes missing from the "
             f'p2p_bytes_total{{verb="weight_push"}} fleet series')
    with open(plan_path) as f:
        plines = f.read().splitlines()
    for verb in ("broadcast", "all_gather"):
        hits = [ln for ln in plines
                if ln.startswith("collective_plan_total{")
                and f'verb="{verb}"' in ln
                and float(ln.rsplit(" ", 1)[1]) > 0]
        if not hits:
            fail(f"{plan_path}: no nonzero collective_plan_total series "
                 f"with verb={verb!r} — the planner never decided that "
                 f"verb")
    print(f"check_obs: weights metrics OK — {int(peers)} consistent "
          f"peer(s), push byte/version series nonzero, plan series "
          f"present for both new verbs")


def check_chaos_metrics(path: str, bench_json: str = "") -> None:
    with open(path) as f:
        lines = f.read().splitlines()

    recovered = {}
    for ln in lines:
        if ln.startswith("serving_recovered_total{"):
            label = ln[ln.index("{") + 1:ln.index("}")]
            outcome = label.split('outcome="', 1)[1].split('"', 1)[0]
            recovered[outcome] = float(ln.rsplit(" ", 1)[1])
    placed = recovered.get("resubmitted", 0) + recovered.get(
        "restarted", 0)
    if placed < 1:
        fail(f"{path}: no resubmitted/restarted recovery on "
             f"serving_recovered_total (have {recovered}) — the killed "
             f"replica's requests never reached a survivor")
    unknown = set(recovered) - {"resubmitted", "restarted", "lost"}
    if unknown:
        fail(f"{path}: unexpected recovery outcomes {sorted(unknown)}")

    # the EXTENDED conservation invariant, re-asserted from the exported
    # fleet lines (not trusted from the bench's own in-process check)
    terms = {}
    for term in ("submitted", "completed", "active", "queued",
                 "rejected", "expired", "lost"):
        terms[term] = _prom_total(lines, f"uccl_serving_{term} ", path)
    rhs = sum(v for k, v in terms.items() if k != "submitted")
    if terms["submitted"] != rhs:
        fail(f"{path}: conservation violated — submitted "
             f"{terms['submitted']} != completed+active+queued+rejected"
             f"+expired+lost = {rhs} ({terms})")
    if terms["lost"] < 1:
        fail(f"{path}: zero lost requests — the kill arms never "
             f"exercised the recovery sink term")

    if _prom_total(lines, "disagg_leases_expired_total", path) < 1:
        fail(f"{path}: no reclaimed GRANT lease — the post-GRANT kill "
             f"never exercised lease expiry")

    leaked = [ln for ln in lines
              if ln.startswith("serving_leaked_slots{")]
    if not leaked:
        fail(f"{path}: no serving_leaked_slots component gauges")
    bad = [ln for ln in leaked if float(ln.rsplit(" ", 1)[1]) != 0]
    if bad:
        fail(f"{path}: leaked slots after chaos: {bad}")

    arms = 0
    if bench_json:
        with open(bench_json) as f:
            for ln in f.read().splitlines():
                if not ln.strip():
                    continue
                arm = json.loads(ln)
                if arm.get("oracle_exact") is not True:
                    fail(f"{bench_json}: arm {arm.get('bench')} is not "
                         f"oracle_exact — a recovered output diverged")
                if "recovered" not in arm:
                    fail(f"{bench_json}: arm {arm.get('bench')} carries "
                         f"no counter-delta recovered labels")
                arms += 1
        if not arms:
            fail(f"{bench_json}: no chaos arms recorded")
    print(f"check_obs: chaos metrics OK — {int(placed)} recovered "
          f"request(s) placed on survivors, {int(terms['lost'])} lost, "
          f"conservation holds, leases reclaimed, zero leaked slots"
          + (f", {arms} oracle-exact arm(s)" if bench_json else ""))


def check_a2a_sched_metrics(path: str, bench_json: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()

    def _nonzero(prefix: str, what: str) -> float:
        hits = [ln for ln in lines if ln.startswith(prefix)
                and float(ln.rsplit(" ", 1)[1]) > 0]
        if not hits:
            fail(f"{path}: no nonzero {prefix!r} sample — {what}")
        return sum(float(ln.rsplit(" ", 1)[1]) for ln in hits)

    plan_algos = set()
    for ln in lines:
        if (ln.startswith("collective_plan_total{")
                and 'verb="ep_a2a"' in ln
                and float(ln.rsplit(" ", 1)[1]) > 0):
            for part in ln[ln.index("{") + 1:ln.index("}")].split(","):
                k, _, v = part.partition("=")
                if k == "algo":
                    plan_algos.add(v.strip('"'))
    if "ep_sched" not in plan_algos:
        fail(f"{path}: no nonzero collective_plan_total{{verb=\"ep_a2a\","
             f"algo=\"ep_sched\"}} — the planner never committed a "
             f"scheduled decision (algos: {sorted(plan_algos)})")
    rounds = _nonzero('ep_a2a_rounds_total{algo="ep_sched"}',
                      "no scheduled round ever drove the wire")
    skews = [float(ln.rsplit(" ", 1)[1]) for ln in lines
             if ln.startswith("ep_a2a_skew")]
    if not skews:
        fail(f"{path}: missing ep_a2a_skew gauge — the planner's "
             f"contention feature is invisible")
    if max(skews) < 1.0:
        fail(f"{path}: ep_a2a_skew {max(skews)} < 1.0 — not a valid "
             f"max/mean load ratio")

    sweeps = arms = active = 0
    with open(bench_json) as f:
        for raw in f:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                rec = json.loads(raw)
            except json.JSONDecodeError:
                continue
            if rec.get("bench") != "ep_sched_sweep":
                continue
            for sweep in rec.get("sweeps", []):
                sweeps += 1
                if "model" not in sweep:
                    fail(f"{bench_json}: sweep alpha={sweep.get('alpha')} "
                         f"carries no model round-time block")
                for arm in sweep.get("arms", []):
                    arms += 1
                    tag = (f"alpha={sweep.get('alpha')} "
                           f"mode={arm.get('a2a_sched')}")
                    if arm.get("bit_identical_to_off") is not True:
                        fail(f"{bench_json}: arm {tag} is not bit-"
                             f"identical to the off-arm anchor — the "
                             f"schedule changed the bytes, not just "
                             f"their order")
                    # the off arm never consults the planner — its
                    # ep_streams label is definitional, not a delta
                    audited = (arm.get("algo", "").split("+")
                               if arm.get("a2a_sched") != "off" else [])
                    for algo in filter(None, audited):
                        if algo not in plan_algos:
                            fail(f"{bench_json}: arm {tag} labeled "
                                 f"{algo!r} with no matching "
                                 f"collective_plan_total series in "
                                 f"{path} — the label did not come off "
                                 f"the plan counter")
                    if arm.get("sched_active"):
                        active += 1
                        if arm.get("rounds", {}).get("ep_sched", 0) <= 0:
                            fail(f"{bench_json}: arm {tag} claims "
                                 f"sched_active but counted no ep_sched "
                                 f"rounds")
    if not sweeps:
        fail(f"{bench_json}: no ep_sched_sweep records to cross-check")
    if active < 1:
        fail(f"{bench_json}: no arm ever rode the schedule — the smoke "
             f"arm proved nothing about the scheduled wire")
    print(f"check_obs: a2a-sched metrics OK — {int(rounds)} scheduled "
          f"round(s) counted, {arms} bit-identical arm(s) across "
          f"{sweeps} sweep(s), {active} schedule-active")


def check_kv_tiers_metrics(path: str, bench_json: str) -> None:
    """The tiered-KV smoke arm: the host (and, when exercised, remote)
    tier must have demonstrably cycled — counted demotions AND promotions
    per tier, at-rest residency visible on the byte gauge, and every
    lossless-at-rest bench arm oracle-exact."""
    with open(path) as f:
        lines = f.read().splitlines()

    def tier_total(name: str, tier: str) -> float:
        hits = [float(ln.rsplit(" ", 1)[1]) for ln in lines
                if ln.startswith(f"{name}{{") and f'tier="{tier}"' in ln]
        return sum(hits)

    arms = exact_arms = 0
    tiers_run = set()
    with open(bench_json) as f:
        for raw in f:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                arm = json.loads(raw)
            except json.JSONDecodeError:
                continue
            if arm.get("bench") != "serving_kv_tiers" or "skipped" in arm:
                continue
            arms += 1
            cfg = arm.get("tier_config", "")
            tiers_run.update(t for t in ("t1", "t2") if t in cfg)
            if "kv_tier" not in arm:
                fail(f"{bench_json}: arm {cfg!r} carries no counter-delta "
                     f"kv_tier traffic block")
            if arm.get("exact_rest"):
                if "oracle_exact" not in arm:
                    fail(f"{bench_json}: lossless arm {cfg!r} was never "
                         f"oracle-checked (run with --check-oracle)")
                if arm["oracle_exact"] is not True:
                    fail(f"{bench_json}: lossless arm {cfg!r} is not "
                         f"oracle_exact — a promoted prefix diverged")
                exact_arms += 1
            if cfg != "t0":
                traffic = arm["kv_tier"]
                if traffic.get("demotions", {}).get("t1", 0) < 1:
                    fail(f"{bench_json}: tier arm {cfg!r} counted no t1 "
                         f"demotion — eviction pressure never moved an "
                         f"entry down")
    if arms < 1:
        fail(f"{bench_json}: no serving_kv_tiers arms recorded")
    if exact_arms < 1:
        fail(f"{bench_json}: no lossless-at-rest arm was oracle-checked "
             f"— the bit-exact tier contract went unproven")
    for tier in sorted(tiers_run):
        for name, what in (("kv_tier_demotions_total",
                            "an entry moved down"),
                           ("kv_tier_promotions_total",
                            "a hit imported back")):
            if tier_total(name, tier) < 1:
                fail(f"{path}: no counted {name} for tier {tier!r} — "
                     f"never {what} through the exercised tier")
    if tier_total("kv_tier_resident_bytes", "t1") <= 0:
        fail(f"{path}: kv_tier_resident_bytes{{tier=\"t1\"}} is zero — "
             f"no entry lives at rest in the host pool")
    if not any(ln.startswith("prefix_cache_resident_tokens")
               for ln in lines):
        fail(f"{path}: missing prefix_cache_resident_tokens gauge — the "
             f"device-tier pressure axis is invisible")
    print(f"check_obs: kv-tiers metrics OK — {arms} arm(s), "
          f"{exact_arms} oracle-exact lossless, tiers cycled: "
          f"{sorted(tiers_run)}")


def check_tenants_metrics(path: str, bench_json: str) -> None:
    """The multi-tenant isolation smoke arm: per-tenant accounting must be
    real (>= 2 tenant-labeled serving_tenant_* series with non-zero
    counts), the bounded adapter store must have demonstrably cycled
    (counted hits AND evictions), and the bench's paired arms must prove
    isolation — victim SLO attainment with tenant-fair admission on under
    overload >= 0.9x its no-overload value, while the fairness-off arm
    sits visibly below the baseline (the collapse the fair path
    prevents)."""
    with open(path) as f:
        lines = f.read().splitlines()

    tenants = {}
    for ln in lines:
        if ln.startswith("serving_tenant_requests_total{"):
            label = ln[ln.index("{") + 1:ln.index("}")]
            tenants[label] = float(ln.rsplit(" ", 1)[1])
    if len(tenants) < 2:
        fail(f"{path}: {len(tenants)} tenant-labeled "
             f"serving_tenant_requests_total series — the engine never "
             f"accounted more than one tenant (labels: {sorted(tenants)})")
    dead = [lab for lab, v in tenants.items() if v <= 0]
    if dead:
        fail(f"{path}: tenant series with zero finished requests: {dead}")
    if not any(ln.startswith("serving_tenant_tokens_total{")
               for ln in lines):
        fail(f"{path}: missing serving_tenant_tokens_total — per-tenant "
             f"goodput is invisible")
    hits = _prom_total(lines, "adapter_cache_hits_total", path)
    evictions = _prom_total(lines, "adapter_cache_evictions_total", path)
    if hits < 1:
        fail(f"{path}: zero adapter_cache_hits_total — no acquisition "
             f"ever reused a device-resident adapter row")
    if evictions < 1:
        fail(f"{path}: zero adapter_cache_evictions_total — the bounded "
             f"store never restaged under pressure (capacity >= tenants?)")

    arms = {}
    with open(bench_json) as f:
        for raw in f:
            raw = raw.strip()
            if not raw.startswith("{"):
                continue
            try:
                arm = json.loads(raw)
            except json.JSONDecodeError:
                continue
            if arm.get("bench") != "serving_tenants" or "skipped" in arm:
                continue
            if arm.get("tenant_series", 0) < 2:
                fail(f"{bench_json}: arm fair={arm.get('fair')} "
                     f"overload={arm.get('overload')} counted "
                     f"{arm.get('tenant_series')} tenant series")
            va = (arm.get("victim_slo") or {}).get("ttft_attainment")
            if va is None:
                fail(f"{bench_json}: arm fair={arm.get('fair')} "
                     f"overload={arm.get('overload')} carries no victim "
                     f"TTFT attainment")
            arms[(bool(arm.get("fair")), bool(arm.get("overload")))] = va
    for key, what in (((True, False), "fair/no-overload baseline"),
                      ((True, True), "fair/overload"),
                      ((False, True), "nofair/overload")):
        if key not in arms:
            fail(f"{bench_json}: missing the {what} arm — the isolation "
                 f"claim needs all three")
    base, fair, nofair = arms[(True, False)], arms[(True, True)], \
        arms[(False, True)]
    if fair < 0.9 * base:
        fail(f"{bench_json}: victim TTFT attainment under overload with "
             f"fairness on is {fair} < 0.9x its no-overload value {base} "
             f"— the overloading tenant pushed victims off their SLO")
    if not nofair < base - 0.05:
        fail(f"{bench_json}: fairness-off victim attainment {nofair} did "
             f"not visibly collapse below the baseline {base} — the smoke "
             f"arm never demonstrated the failure mode fairness prevents")
    print(f"check_obs: tenants metrics OK — {len(tenants)} tenant series, "
          f"{int(hits)} adapter hit(s) / {int(evictions)} eviction(s), "
          f"victim attainment base={base} fair={fair} nofair={nofair}")


def check_router_metrics(path: str) -> None:
    with open(path) as f:
        lines = f.read().splitlines()

    def total(prefix: str) -> float:
        return _prom_total(lines, prefix, path)

    routed = {}
    for ln in lines:
        if ln.startswith("serving_router_requests_total{"):
            label = ln[ln.index("{") + 1:ln.index("}")]
            routed[label] = float(ln.rsplit(" ", 1)[1])
    if len(routed) < 2:
        fail(f"{path}: {len(routed)} replica-labeled "
             f"serving_router_requests_total series — a replica set "
             f"never routed (labels: {sorted(routed)})")
    dead = [lab for lab, v in routed.items() if v <= 0]
    if dead:
        fail(f"{path}: replica series with zero admissions: {dead} — "
             f"the router never spread load there")
    preempted = total("serving_preempted_total")
    if preempted < 1:
        fail(f"{path}: zero serving_preempted_total — no interactive "
             f"arrival ever paused batch work (the smoke arm must force "
             f">= 1 preemption)")
    resumed = total("serving_resumed_total")
    if resumed != preempted:
        fail(f"{path}: resumes ({int(resumed)}) != preemptions "
             f"({int(preempted)}) — a paused request never came back")
    for cls in ("interactive", "batch"):
        prefix = f'uccl_serving_class_ttft_ms{{cls="{cls}"'
        if not any(ln.startswith(prefix) for ln in lines):
            fail(f"{path}: missing per-class TTFT percentile series for "
                 f"{cls!r} — SLO attainment has nothing to read")
    print(f"check_obs: router metrics OK — {len(routed)} replicas "
          f"routed, {int(preempted)} preemption(s) all resumed, "
          f"per-class percentile series present")


def _parse_prom_labeled(path):
    """[(name, {label: value}, float)] from a Prometheus text file —
    enough label-awareness for the fleet checks (stdlib-only)."""
    import re

    sample = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$'
    )
    label = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    out = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln or ln.startswith("#"):
                continue
            m = sample.match(ln)
            if not m:
                continue
            try:
                v = float(m.group(3))
            except ValueError:
                continue
            labels = {k: raw for k, raw in label.findall(m.group(2) or "")}
            out.append((m.group(1), labels, v))
    return out


def _hist_quantile(uppers, counts, q):
    """Quantile off per-bucket counts (last = +Inf overflow); returns
    (value, width of its bucket) or (None, None) when empty — the
    stdlib mirror of obs.histogram_quantile/bucket_width."""
    n = sum(counts)
    if n == 0:
        return None, None
    target = 1.0 + (n - 1) * q / 100.0  # the obs.histogram_quantile rank
    cum = 0
    for i, c in enumerate(counts):
        if c and cum + c >= target:
            if i >= len(uppers):
                return float(uppers[-1]), float("inf")
            lo = uppers[i - 1] if i > 0 else 0.0
            hi = uppers[i]
            return lo + (hi - lo) * (target - cum) / c, hi - lo
        cum += c
    return float(uppers[-1]), float("inf")


def _width_at(uppers, v):
    """Width of the bucket containing value ``v`` (inf for overflow)."""
    import bisect

    i = bisect.bisect_left(uppers, v)
    if i >= len(uppers):
        return float("inf")
    return uppers[i] - (uppers[i - 1] if i > 0 else 0.0)


def _replica_hist(samples, family, replica):
    """(uppers, per-bucket counts) of one replica's histogram, from its
    cumulative ``_bucket`` lines."""
    buckets = []
    for name, labels, v in samples:
        if name != f"{family}_bucket" or labels.get("replica") != replica:
            continue
        le = labels.get("le")
        if le is None:
            continue
        buckets.append((float("inf") if le == "+Inf" else float(le), v))
    if not buckets:
        return None, None
    buckets.sort()
    uppers = [u for u, _ in buckets if u != float("inf")]
    cum = [c for _, c in buckets]
    counts = [cum[0]] + [cum[i] - cum[i - 1] for i in range(1, len(cum))]
    return uppers, counts


def check_fleet_trace(path: str) -> None:
    with open(path) as f:
        trace = json.loads(f.read())
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        fail(f"{path}: no traceEvents")
    by_trace = defaultdict(list)
    flows = defaultdict(lambda: {"s": set(), "f": set()})
    for ev in evs:
        if ev.get("ph") in ("s", "f"):
            flows[str(ev.get("id"))][ev["ph"]].add(ev["pid"])
        tid = (ev.get("args") or {}).get("trace_id")
        if tid:
            by_trace[tid].append(ev)
    cross = 0
    for tid, tevs in by_trace.items():
        pids = {ev["pid"] for ev in tevs}
        if len(pids) < 2:
            continue
        try:
            fid = str(int(tid[:15], 16))
        except ValueError:
            continue
        sf = flows.get(fid)
        if not (sf and sf["s"] and sf["f"] and sf["s"] != sf["f"]):
            continue
        # causal order on the aligned timeline: submit <= grant <= adopt
        # (BEGIN <= GRANT <= FINAL in stream terms; local finishes are
        # not globally ordered — the prefill fleet's 1-token request
        # finishes before the decode side adopts)
        stages = {}
        for ev in tevs:
            if ev["name"] in ("submit", "grant", "adopt") \
                    and ev["name"] not in stages:
                stages[ev["name"]] = ev["ts"]
        chain = [stages[n] for n in ("submit", "grant", "adopt")
                 if n in stages]
        if len(chain) < 3:
            fail(f"{path}: trace {tid} spans {sorted(pids)} but misses "
                 f"lifecycle stages (have {sorted(stages)}) — the remote "
                 f"side never stamped its events")
        if chain != sorted(chain):
            fail(f"{path}: trace {tid} lifecycle out of causal order "
                 f"after alignment ({stages})")
        cross += 1
    if cross < 1:
        fail(f"{path}: no request with flow-linked spans across >= 2 "
             f"processes — cross-process tracing never happened "
             f"({len(by_trace)} trace id(s) seen)")
    print(f"check_obs: fleet trace OK — {cross} cross-process "
          f"request(s), {len(by_trace)} trace id(s)")


def check_fleet_metrics(path: str) -> None:
    samples = _parse_prom_labeled(path)
    fam = "serving_ttft_seconds"
    replicas = sorted({lb["replica"] for n, lb, _ in samples
                       if n == f"{fam}_count" and "replica" in lb})
    if len(replicas) < 2:
        fail(f"{path}: {len(replicas)} replica-labeled {fam} histogram(s) "
             f"— the aggregate does not span a fleet "
             f"(replicas: {replicas})")
    per_rep_counts = {
        r: sum(v for n, lb, v in samples
               if n == f"{fam}_count" and lb.get("replica") == r)
        for r in replicas
    }
    fleet_count = sum(v for n, lb, v in samples
                      if n == f"{fam}_count" and "replica" not in lb)
    if fleet_count != sum(per_rep_counts.values()):
        fail(f"{path}: fleet {fam}_count {fleet_count} != per-replica sum "
             f"{sum(per_rep_counts.values())} — histogram summation broke")
    if fleet_count <= 0:
        fail(f"{path}: fleet {fam} histogram is empty — no TTFT was ever "
             f"observed")
    checked = 0
    for r in replicas:
        uppers, counts = _replica_hist(samples, fam, r)
        if uppers is None:
            fail(f"{path}: replica {r} exports no {fam}_bucket series")
        for q in (50, 95):
            sample_ms = [v for n, lb, v in samples
                         if n == "uccl_serving_ttft_ms"
                         and lb.get("replica") == r
                         and lb.get("q") == f"p{q}"]
            if not sample_ms:
                continue  # this replica had no completed samples
            hist_s, width_s = _hist_quantile(uppers, counts, q)
            if hist_s is None:
                fail(f"{path}: replica {r} has sample p{q} but an empty "
                     f"histogram — the two derivations diverged")
            diff_ms = abs(hist_s * 1e3 - sample_ms[0])
            # tolerance: one bucket width at EACH derivation's value. The
            # histogram lands in the bucket of the order statistic at
            # rank ceil(1+(n-1)q/100) while the sample percentile
            # interpolates between that statistic and its predecessor —
            # when the two straddle a bucket edge the values sit in
            # different buckets, so a single-bucket tolerance (measured
            # at the histogram alone) could fail a healthy run
            tol_ms = (width_s + _width_at(uppers,
                                          sample_ms[0] / 1e3)) * 1e3
            if diff_ms > tol_ms + 1e-9:
                fail(f"{path}: replica {r} TTFT p{q} disagrees — "
                     f"histogram {hist_s * 1e3:.3f} ms vs samples "
                     f"{sample_ms[0]:.3f} ms (diff {diff_ms:.3f} > "
                     f"tolerance {tol_ms:.3f} ms)")
            checked += 1
    if checked < 1:
        fail(f"{path}: no replica exported sample-derived "
             f"uccl_serving_ttft_ms percentiles to cross-check")
    print(f"check_obs: fleet metrics OK — {len(replicas)} replicas, "
          f"fleet count {int(fleet_count)}, {checked} histogram-vs-sample "
          f"percentile cross-check(s) within one bucket width")


def check_fleet_cache_metrics(path: str, bench_json: str) -> None:
    """The fleet prefix-cache smoke arm (benchmarks/fleet_bench.py): a
    prefix computed on one worker PROCESS must land as a counted,
    wire-audited hit on another — >= 1 ``fleet_cache_hits_total`` with
    nonzero ``p2p_bytes_total{verb="kv_tier"}`` in the federated prom and
    a live per-replica ``fleet_dir_resident_entries`` gauge; the bench
    JSON must show the directory arm computing strictly fewer prefill
    tokens AND reaching first token sooner than the no-directory arm,
    every arm bit-exact vs the one-shot oracle with request conservation,
    and the chaos arm absorbing the owner kill (counted dial error +
    directory invalidation, never a wrong byte)."""
    samples = _parse_prom_labeled(path)
    hits = sum(v for n, lab, v in samples
               if n == "fleet_cache_hits_total" and "replica" in lab)
    if hits < 1:
        fail(f"{path}: zero replica-labeled fleet_cache_hits_total — no "
             f"cross-worker prefix import was ever counted")
    wire = sum(v for n, lab, v in samples
               if n == "p2p_bytes_total" and lab.get("verb") == "kv_tier"
               and "replica" in lab)
    if wire <= 0:
        fail(f"{path}: fleet hits without p2p_bytes_total{{verb="
             f"\"kv_tier\"}} bytes — the 'import' never crossed the wire")
    resident = [(lab.get("replica"), v) for n, lab, v in samples
                if n == "fleet_dir_resident_entries" and "replica" in lab]
    if not any(v > 0 for _, v in resident):
        fail(f"{path}: no live fleet_dir_resident_entries gauge — the "
             f"directory view is invisible (samples: {resident})")

    with open(bench_json) as f:
        bench = json.load(f)
    arms = bench.get("arms", {})
    for need in ("no_directory", "directory", "chaos"):
        if need not in arms:
            fail(f"{bench_json}: missing arm {need!r} (have "
                 f"{sorted(arms)})")
    for name, arm in arms.items():
        if not arm.get("oracle_exact"):
            fail(f"{bench_json}: arm {name!r} not bit-exact vs the "
                 f"one-shot oracle — the fleet path corrupted KV")
        if not arm.get("conserved"):
            fail(f"{bench_json}: arm {name!r} leaked slots or lost "
                 f"requests (conservation broken)")
    d, b = arms["directory"], arms["no_directory"]
    if d.get("fleet_hits", 0) < 1:
        fail(f"{bench_json}: directory arm counted no fleet hits")
    if d["computed_prefill_tokens"] >= b["computed_prefill_tokens"]:
        fail(f"{bench_json}: directory arm computed "
             f"{d['computed_prefill_tokens']} prefill tokens vs baseline "
             f"{b['computed_prefill_tokens']} — the directory saved "
             f"nothing")
    if d["ttft_ms_mean"] >= b["ttft_ms_mean"]:
        fail(f"{bench_json}: directory TTFT {d['ttft_ms_mean']} ms not "
             f"below baseline {b['ttft_ms_mean']} ms — importing cost "
             f"more than recomputing")
    c = arms["chaos"]
    if c.get("invalidations", 0) < 1:
        fail(f"{bench_json}: chaos arm swept no directory entries — the "
             f"dead owner's refs are still live")
    if c.get("dial_errors", 0) < 1:
        fail(f"{bench_json}: chaos arm never dialed the dead owner — the "
             f"kill landed after the measured window")
    print(f"check_obs: fleet cache OK — {int(hits)} cross-worker hit(s), "
          f"{int(wire)} kv_tier wire bytes, "
          f"{d['computed_prefill_tokens']}/{b['computed_prefill_tokens']} "
          f"computed prefill tokens, TTFT {d['ttft_ms_mean']}/"
          f"{b['ttft_ms_mean']} ms, chaos invalidations "
          f"{int(c['invalidations'])}")


def check_flight_metrics(path: str, bench_json: str) -> None:
    """``--flight`` mode: the flight-recorder acceptance gate. Re-audits
    the ``chaos_flight`` arm chaos_bench emitted with ``--flight-dir``:

    * every bundle on disk is schema-valid, its filename kind matches its
      ``trigger.kind``, and — count-before-snapshot — its OWN dump is
      visible in its embedded counter snapshot;
    * the per-trigger bundle census equals the arm's ``expected`` map
      (one attributable dump per injected fault class, nothing extra),
      and the final exported ``obs_flight_dumps_total{trigger=}`` agrees;
    * the doctor replays every bundle to the root cause its trigger kind
      maps to (``uccl_tpu.doctor.ROOT_CAUSE``);
    * the faulted window burned (``obs_slo_burn_alerts_total >= 1``) while
      the clean phase produced zero bundles and zero burn alerts.
    """
    import glob
    import os
    import sys as _sys

    with open(bench_json) as f:
        arms = [json.loads(ln) for ln in f if ln.strip()]
    flight_arms = [a for a in arms if a.get("bench") == "chaos_flight"]
    if not flight_arms:
        fail(f"{bench_json}: no chaos_flight arm — run chaos_bench with "
             f"--flight-dir")
    arm = flight_arms[0]
    expected = {k: int(v) for k, v in arm["expected"].items()}

    _sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from uccl_tpu import doctor as doctor_mod

    def dumps_counted(prom_text: str, kind: str) -> float:
        want = f'obs_flight_dumps_total{{trigger="{kind}"}}'
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in prom_text.splitlines()
                   if ln.startswith(want))

    bundles = sorted(glob.glob(os.path.join(arm["flight_dir"],
                                            "flight_*.json")))
    if not bundles:
        fail(f"{arm['flight_dir']}: no flight bundles on disk")
    census: dict = {}
    for bp in bundles:
        try:
            b = doctor_mod.load_bundle(bp)
        except SystemExit:
            raise
        except Exception as e:
            fail(f"{bp}: unloadable bundle ({type(e).__name__}: {e})")
        kind = b["trigger"]["kind"]
        census[kind] = census.get(kind, 0) + 1
        for key in ("trigger", "host", "events", "metrics_prom",
                    "registry", "state"):
            if key not in b:
                fail(f"{bp}: bundle missing {key!r}")
        fname_kind = os.path.basename(bp).split("_", 2)[2][:-len(".json")]
        if fname_kind != kind:
            fail(f"{bp}: filename kind {fname_kind!r} != trigger.kind "
                 f"{kind!r}")
        if dumps_counted(b["metrics_prom"], kind) < 1:
            fail(f"{bp}: its own dump is missing from the embedded "
                 f"obs_flight_dumps_total{{trigger={kind!r}}} snapshot — "
                 f"count-before-snapshot broke")
        verdict = doctor_mod.diagnose(b)
        want_cause = doctor_mod.ROOT_CAUSE.get(kind)
        if verdict["root_cause"] != want_cause:
            fail(f"{bp}: doctor root cause {verdict['root_cause']!r} != "
                 f"{want_cause!r} for trigger {kind!r}")
    if census != expected:
        fail(f"{arm['flight_dir']}: bundle census {census} != injected "
             f"fault classes {expected} — dumps are not one-per-fault")

    with open(path) as f:
        text = f.read()
    lines = text.splitlines()
    for kind, n in expected.items():
        got = dumps_counted(text, kind)
        if got != n:
            fail(f"{path}: obs_flight_dumps_total{{trigger={kind!r}}} = "
                 f"{got}, bundle census says {n}")
    total = _prom_total(lines, "obs_flight_dumps_total", path)
    if total != sum(expected.values()):
        fail(f"{path}: obs_flight_dumps_total sums to {total}, expected "
             f"{sum(expected.values())} — an unattributed dump fired")
    if _prom_total(lines, "obs_slo_burn_alerts_total", path) < 1:
        fail(f"{path}: the faulted window never burned "
             f"(obs_slo_burn_alerts_total < 1)")
    if not any(ln.startswith("obs_trace_events_dropped_total")
               for ln in lines):
        fail(f"{path}: obs_trace_events_dropped_total series missing")
    if arm.get("clean_bundles") != 0 or arm.get("clean_burn_alerts") != 0:
        fail(f"{bench_json}: clean phase was not clean: {arm}")
    leftover = glob.glob(os.path.join(arm["clean_dir"], "flight_*.json"))
    if leftover:
        fail(f"{arm['clean_dir']}: clean phase left bundles: {leftover}")
    print(f"check_obs --flight: {len(bundles)} bundle(s), "
          f"{len(expected)} fault class(es) attributed, doctor verdicts "
          f"match, clean phase empty")


def main(argv) -> None:
    if len(argv) == 4 and argv[1] == "--fleet":
        check_fleet_trace(argv[2])
        check_fleet_metrics(argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) in (3, 4) and argv[1] == "--chaos":
        check_chaos_metrics(argv[2], argv[3] if len(argv) == 4 else "")
        print("check_obs: ALL OK")
        return
    if len(argv) == 3 and argv[1] == "--router":
        check_router_metrics(argv[2])
        print("check_obs: ALL OK")
        return
    if len(argv) == 3 and argv[1] == "--spec":
        check_spec_metrics(argv[2])
        print("check_obs: ALL OK")
        return
    if len(argv) == 3 and argv[1] == "--disagg":
        check_disagg_metrics(argv[2])
        print("check_obs: ALL OK")
        return
    if len(argv) in (3, 4) and argv[1] == "--transport":
        check_transport_metrics(argv[2], argv[3] if len(argv) == 4 else "")
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--quant":
        check_quant_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--plan":
        check_plan_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--a2a-sched":
        check_a2a_sched_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--weights":
        check_weights_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--kv-tiers":
        check_kv_tiers_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--tenants":
        check_tenants_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--fleet-cache":
        check_fleet_cache_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) == 4 and argv[1] == "--flight":
        check_flight_metrics(argv[2], argv[3])
        print("check_obs: ALL OK")
        return
    if len(argv) != 3:
        fail("usage: check_obs.py TRACE_JSON METRICS_PROM | "
             "check_obs.py --quant METRICS_PROM WIRE_DTYPE | "
             "check_obs.py --plan METRICS_PROM BENCH_JSON | "
             "check_obs.py --a2a-sched METRICS_PROM BENCH_JSON | "
             "check_obs.py --weights PUSH_PROM PLAN_PROM | "
             "check_obs.py --kv-tiers METRICS_PROM BENCH_JSON | "
             "check_obs.py --tenants METRICS_PROM BENCH_JSON | "
             "check_obs.py --disagg METRICS_PROM | "
             "check_obs.py --chaos METRICS_PROM [BENCH_JSON] | "
             "check_obs.py --transport METRICS_PROM [BENCH_JSON] | "
             "check_obs.py --spec METRICS_PROM | "
             "check_obs.py --router METRICS_PROM | "
             "check_obs.py --fleet MERGED_TRACE FLEET_PROM | "
             "check_obs.py --fleet-cache FLEET_PROM BENCH_JSON | "
             "check_obs.py --flight METRICS_PROM BENCH_JSON")
    check_trace(argv[1])
    check_metrics(argv[2])
    print("check_obs: ALL OK")


if __name__ == "__main__":
    main(sys.argv)

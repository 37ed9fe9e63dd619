"""The reductions of a traced run that depend on a model family's scope
names, each made ONCE and handed the family: a reader in ``layer_metrics/``
names a GROUP of scopes (``"moe_experts"``, ``"cache_read.full"``) and a
counting function (``"decode_step_bytes"``) by their keys, and what those
are for the cell at hand comes from ``view.family`` — the module
``chipbench/families/<model_type>.py`` of the cell's configuration, which
``run.TraceView`` resolves. A family without the key a reading asks for
gives that reader ``None`` (such a cell is not in the reading's
``workloads``), and so does a program without scopes or spans (the parent
of the PR that added them).

A family module holds names and counts, nothing else:

- ``SCOPES``: the ``jax.named_scope`` names its programs carry;
- ``GROUPS``: {key: tuple of scopes} — ``full_attention``,
  ``window_attention``, ``latent_attention``, ``conv``, ``moe_experts``,
  ``moe_exchange``, ``moe_shared``, ``shared_dense_ffn``,
  ``cache_read.<kind>`` (where a kind's cached rows are read), as far as
  its model has them;
- counting functions ``f(cfg, facts) -> bytes`` of a decode step
  (``decode_step_bytes``, ``full_cache_bytes``, ``window_cache_bytes``,
  ``latent_cache_bytes``, ``conv_decode_bytes``), where ``facts`` are the
  arguments of the step's own ``uccl.wire.decode`` span (``n``,
  ``kv_rows``, ``window_rows`` ...) with those of the ``uccl.ep.experts``
  span inside it (``experts_read``, ``experts_held``), and which return
  None where a fact they need is missing; ``routed_expert_flops(cfg,
  tokens)``;
- ``RING_POOL_GROUPS``: the cache groups of the slot pool that are rings.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.experts_read import EXPERTS, counts_of
from chipbench.stats import percentile

DECODE, PREFILL = pt.DECODE, pt.PREFILL


@dataclass(frozen=True)
class Row:
    """One program span's operations: device ns by scope (None: under no
    scope), the device time of all of them as ONE union (the expert loop's
    ``while`` envelops its body's operations, so a sum over scopes would
    count those twice), and the facts a counting function is handed."""
    by: Dict[Optional[str], float]
    busy: float
    facts: dict


def _of(view, name: str, default=None):
    """What the view's family holds under ``name``; ``default`` where it
    holds none (or the view has no family)."""
    return getattr(getattr(view, "family", None), name, default)


def group(view, key: str) -> Optional[Tuple[str, ...]]:
    """The family's scopes under ``key``; None where it has no such group."""
    return _of(view, "GROUPS", {}).get(key)


@functools.lru_cache(maxsize=4)
def _scope_rows(path: str, span_name: str, t0: float, t1: float,
                scopes: Tuple[str, ...]) -> List[Row]:
    loaded = pt.load(path)
    spans = pt.spans_in(loaded.spans, span_name, t0, t1)
    counts = counts_of(spans, pt.spans_in(loaded.spans, EXPERTS, t0, t1))
    out = []
    for sp, count, evs in zip(spans, counts, tr.events_inside(
            pt._window_ops(path, t0, t1), spans, span_name)):
        if evs:  # a span no operation starts in is no program
            out.append(Row(pt.by_scope(evs, scopes), tr.busy_ns(evs),
                           {**(sp[3] if len(sp) > 3 else {}),
                            **(count or {})}))
    return out


def rows_in(view, span_name: str) -> Optional[List[Row]]:
    """The window's spans of ``span_name`` that hold an operation, as
    :class:`Row`; None without a program trace or a family."""
    scopes = _of(view, "SCOPES")
    if scopes is None or pt._loaded(view) is None:
        return None
    return _scope_rows(view.record["trace_path"], span_name, *view.window,
                       tuple(scopes))


def scope_ms_in(view, span_name: str, key: str) -> Optional[float]:
    """Device ms under the family's group ``key`` in the operations that
    start inside a span of ``span_name``, median over the window's spans."""
    scopes, rows = group(view, key), rows_in(view, span_name)
    if scopes is None or not rows:
        return None
    return pt.scope_ms([r.by for r in rows], scopes)


def unscoped_share(view) -> Optional[float]:
    """Share (%) of the window's device-busy time in which no operation
    under one of the family's scopes ran (``program_trace.unscoped_share``)."""
    scopes = _of(view, "SCOPES")
    if scopes is None or pt._loaded(view) is None:
        return None
    return pt.unscoped_share(
        pt._window_ops(view.record["trace_path"], *view.window),
        tuple(scopes))


def step_dev_ms(view, span_name: str) -> Optional[float]:
    """Device-busy time of one program (the operations that start inside
    the benchmark's span ``span_name``), median over the window's."""
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, span_name)
    busy = [b for b, _, _ in rows if b > 0]
    return percentile(busy, 50) / 1e6 if busy else None


def decode_roofline_share(view, bytes_of: str, under: Optional[str] = None
                          ) -> Optional[float]:
    """Median over the window's ``uccl.wire.decode`` spans of the bytes the
    family's ``bytes_of(cfg, facts)`` says the step must move, over the
    chip's HBM bandwidth, over the span's device time — under the family's
    group ``under``, or all of it as one union —, in %."""
    count = _of(view, bytes_of)
    scopes = group(view, under) if under else ()
    rows = rows_in(view, DECODE)
    if count is None or scopes is None or not rows:
        return None
    shares = []
    for row in rows:
        ns = sum(row.by.get(s, 0.0) for s in scopes) if under else row.busy
        if ns <= 0 or int(row.facts.get("n", 0)) < 1 \
                or int(row.facts.get("kv_rows", 0)) < 1:
            continue
        need = count(view.cfg, row.facts)
        if need is not None:
            shares.append(100.0 * need / view.peaks["hbm_bytes_per_s"]
                          / (ns / 1e9))
    return percentile(shares, 50) if shares else None


def prefill_expert_mxu_share(view) -> Optional[float]:
    """What the padded expert queues cost: the FLOPs of the ROUTED rows of
    one prefill program (the family's ``routed_expert_flops`` of its ``rows
    x chunk`` tokens, the span's own arguments; the pool's ``serving.slots``
    and ``prefill_chunk`` where a span has none) over the chip's bfloat16
    peak, over the device time under ``moe_experts`` in its
    ``uccl.wire.prefill`` span; quotient program by program, median."""
    count = _of(view, "routed_expert_flops")
    scopes, rows = group(view, "moe_experts"), rows_in(view, PREFILL)
    if count is None or scopes is None or not rows:
        return None
    s = view.cfg["serving"]
    shares = []
    for row in rows:
        ns = sum(row.by.get(scope, 0.0) for scope in scopes)
        if ns <= 0:
            continue
        tokens = (int(row.facts.get("rows", s["slots"]))
                  * int(row.facts.get("chunk", s["prefill_chunk"])))
        shares.append(100.0 * count(view.cfg, tokens)
                      / view.peaks["bf16_flops"] / (ns / 1e9))
    return percentile(shares, 50) if shares else None


def kv_pool_ring_share(view) -> Optional[float]:
    """The ring groups' share (%) of the slot pool's bytes, from the
    program's ``serving_kv_pool_bytes{group}`` gauge as the runner recorded
    it (``kv_pool_bytes``); the family says which groups are rings."""
    rings = _of(view, "RING_POOL_GROUPS", ())
    by = view.record.get("kv_pool_bytes") or {}
    ring = sum(by.get(g) or 0 for g in rings)
    if not ring or not by.get("full"):
        return None
    return 100.0 * ring / (ring + by["full"])

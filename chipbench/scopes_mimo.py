"""Scope groups of the ``mimo_v2_flash`` programs and the bodies of the
per-layer readers of the cells that run them (each reader in
``layer_metrics/`` imports this module alone). ``program_trace.SCOPES``
lists the twelve scopes the first model's programs have; here the attention
scopes carry the layer's KIND (``attn.core.full`` / ``attn.core.window``,
likewise ``attn.qkv``, ``attn.kv_write``, ``attn.out``), and the leading
dense layer's FFN is ``ffn.dense``. The reductions are ``program_trace``'s,
made again over the wider list. A program without these scopes (the parent
of the PR that added them) gives every reader ``None``."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

from chipbench import flops_mimo
from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.runners.serve import NAME_DECODE, NAME_PREFILL
from chipbench.stats import percentile

DECODE, PREFILL = pt.DECODE, pt.PREFILL
MOE_EXPERTS, MOE_EXCHANGE = pt.MOE_EXPERTS, pt.MOE_EXCHANGE
KINDS = ("full", "window")
_PARTS = ("attn.qkv", "attn.kv_write", "attn.core", "attn.out")
ATTENTION = {kind: tuple(f"{p}.{kind}" for p in _PARTS) for kind in KINDS}
# where the cached rows are read: the core, and the write's scope, under
# which the compiler also prepares a layer's cached rows for the MXU
CACHE_READ = {kind: (f"attn.kv_write.{kind}", f"attn.core.{kind}")
              for kind in KINDS}
SCOPES = pt.SCOPES + ATTENTION["full"] + ATTENTION["window"] + ("ffn.dense",)


@functools.lru_cache(maxsize=4)
def _scope_rows(path: str, span_name: str, t0: float, t1: float
                ) -> List[Dict[Optional[str], float]]:
    """``program_trace.busy_by_scope`` over the wider scope list, with the
    span's own arguments beside each row (``"args"``)."""
    spans = pt.spans_in(pt.load(path).spans, span_name, t0, t1)
    out = []
    for sp, group in zip(spans, tr.events_inside(
            pt._window_ops(path, t0, t1), spans, span_name)):
        by: Dict[Optional[str], list] = {}
        for ev in group:
            by.setdefault(pt.scope_of(ev[3], SCOPES), []).append(ev)
        row = {s: tr.busy_ns(evs) for s, evs in by.items()}
        if row:
            row["args"] = sp[3] if len(sp) > 3 else {}
        out.append(row)
    return out


def rows_in(view, span_name: str) -> Optional[List[dict]]:
    """Per span of ``span_name`` in the window: device ns by scope (and the
    span's arguments under ``"args"``); None without a program trace."""
    if pt._loaded(view) is None:
        return None
    return _scope_rows(view.record["trace_path"], span_name, *view.window)


def scope_ms_in(view, span_name: str, scopes: Sequence[str]
                ) -> Optional[float]:
    """Device ms under ``scopes`` in the operations that start inside a
    span of ``span_name``, median over the window's spans."""
    rows = rows_in(view, span_name)
    if not rows:
        return None
    return pt.scope_ms([{k: v for k, v in r.items() if k != "args"}
                        for r in rows], scopes)


def unscoped_share(view) -> Optional[float]:
    """Share (%) of the window's device-busy time under none of the scopes."""
    if pt._loaded(view) is None:
        return None
    ops = pt._window_ops(view.record["trace_path"], *view.window)
    bare = [ev for ev in ops if pt.scope_of(ev[3], SCOPES) is None]
    if len(bare) == len(ops):
        return None
    return 100.0 * tr.busy_ns(bare) / tr.busy_ns(ops)


def idle_in_launch_ms_per_step(view) -> Optional[float]:
    return pt.idle_ms_per_step(view, pt.IDLE_LAUNCH)


def step_dev_ms(view, span_name: str) -> Optional[float]:
    """Device-busy time of one program (the operations that start inside
    the benchmark's span ``span_name``), median over the window's."""
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, span_name)
    busy = [b for b, _, _ in rows if b > 0]
    return percentile(busy, 50) / 1e6 if busy else None


def device_idle_share(view) -> Optional[float]:
    if view.window is None:
        return None
    window_s = (view.window[1] - view.window[0]) / 1e9
    return 100.0 * (1.0 - view.busy_s() / window_s)


def _decode_shares(view, need, scopes=None) -> Optional[float]:
    """Median over the window's ``uccl.wire.decode`` spans of ``need(slots,
    kv_rows)`` bytes over the chip's HBM bandwidth over the span's device
    time (under ``scopes``, or all of it), in %. ``slots`` and ``kv_rows``
    are the span's own arguments ``n`` and ``kv_rows``."""
    rows = rows_in(view, DECODE)
    if not rows:
        return None
    shares = []
    for row in rows:
        args = row.get("args", {})
        slots, kv_rows = int(args.get("n", 0)), int(args.get("kv_rows", 0))
        ns = sum(v for k, v in row.items()
                 if k != "args" and (scopes is None or k in scopes))
        if ns <= 0 or slots < 1 or kv_rows < 1:
            continue
        shares.append(100.0 * need(slots, kv_rows)
                      / view.peaks["hbm_bytes_per_s"] / (ns / 1e9))
    return percentile(shares, 50) if shares else None


def decode_hbm_roofline_share(view):
    """A decode program's share of its HBM roofline: the bytes the step must
    read (``flops_mimo.decode_step_bytes``) over the program's device time."""
    return _decode_shares(
        view, lambda n, kv: flops_mimo.decode_step_bytes(view.cfg, n, kv))


def decode_attention_roofline_share(view, kind: str):
    """The share of its HBM roofline of a kind's attention over the cache in
    a decode program (device time under ``attn.kv_write`` + ``attn.core``:
    the new rows written, the cached rows read and prepared for the MXU,
    scores, softmax, values), over the rows in use: a full layer's every
    cached row of the decoding slots, a window layer's ``min(length,
    window)`` a slot."""
    if kind == "full":
        def need(n, kv):
            return flops_mimo.full_cache_bytes(view.cfg, kv)
    else:
        def need(n, kv):
            return flops_mimo.window_cache_bytes(view.cfg, n, kv)
    return _decode_shares(view, need, CACHE_READ[kind])


def kv_pool_window_share(view) -> Optional[float]:
    """The window group's share (%) of the slot pool's bytes, from the
    program's ``serving_kv_pool_bytes`` gauge as the runner recorded it."""
    by = view.record.get("kv_pool_bytes") or {}
    if not by.get("window") or not by.get("full"):
        return None
    return 100.0 * by["window"] / (by["window"] + by["full"])

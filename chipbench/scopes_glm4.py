"""Scope groups of the ``glm4_moe_lite`` programs for the per-layer readers
of the cells that run them. ``program_trace.SCOPES`` lists the twelve scopes
the first model's programs have; the latent-attention, shared-expert and
dense-layer scopes are named here and handed to ``program_trace.scope_of``,
which takes them. The reductions are ``program_trace``'s, made again over
the wider list. A program without scopes (the parent of the PR that added
them) gives every reader ``None``."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr

# what the readers of those cells use of ``program_trace``, under this
# module's name (they import this module alone)
DECODE, PREFILL = pt.DECODE, pt.PREFILL
MOE_EXPERTS, MOE_EXCHANGE = pt.MOE_EXPERTS, pt.MOE_EXCHANGE
IDLE_LAUNCH = pt.IDLE_LAUNCH
idle_ms_per_step = pt.idle_ms_per_step

NEW = ("attn.latent_q", "attn.latent_kv", "moe.shared", "ffn.dense")
SCOPES = pt.SCOPES + NEW
LATENT_ATTENTION = pt.ATTENTION + ("attn.latent_q", "attn.latent_kv")
ATTENTION_CORE = ("attn.core",)
SHARED_DENSE = ("moe.shared", "ffn.dense")


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


def unscoped_share_in(view) -> Optional[float]:
    """Share (%) of the window's device-busy time under none of the scopes."""
    if pt._loaded(view) is None:
        return None
    ops = pt._window_ops(view.record["trace_path"], *view.window)
    bare = [ev for ev in ops if pt.scope_of(ev[3], SCOPES) is None]
    if len(bare) == len(ops):
        return None
    return 100.0 * tr.busy_ns(bare) / tr.busy_ns(ops)

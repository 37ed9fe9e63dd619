"""Scope groups of the ``lfm2_moe`` programs (LFM2-24B-A2B) and the bodies of
the per-layer readers of the cells that run them (each reader in
``layer_metrics/`` imports this module alone: nine, what the benchmark's
cap of 128 per-layer metrics left room for; ``scope_ms_in`` and ``CONV`` /
``ATTENTION`` serve ``tools/scope_table.py``-style readings by hand). Beside the full attention
layers' four scopes (``attn.*.full``, as ``scopes_mimo`` names them) a conv
layer's operator has four of its own: ``conv.in_proj`` (the three gates'
projection and ``y = b * u``), ``conv.state`` (the ring write and the read
of the positions before the call), ``conv.mix`` (the taps and the ``c``
gate) and ``conv.out_proj``. The reductions are ``scopes_mimo``'s and
``program_trace``'s, made again over this list; a decode span's experts
reached come from the program's own ``uccl.ep.experts`` span inside it
(``experts_read.py`` reads the same span). A program without these scopes
gives every reader ``None``."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

from chipbench import flops_lfm2
from chipbench import program_trace as pt
from chipbench import scopes_mimo as sm
from chipbench import trace_reduce as tr
from chipbench.experts_read import (  # noqa: F401 — a reader's body
    EXPERTS, decode_experts_read_share,
)
from chipbench.stats import percentile

# what the readers use of ``scopes_mimo`` as it stands, under this module's
# name
DECODE, PREFILL = sm.DECODE, sm.PREFILL
NAME_DECODE, NAME_PREFILL = sm.NAME_DECODE, sm.NAME_PREFILL
MOE_EXPERTS, MOE_EXCHANGE = sm.MOE_EXPERTS, sm.MOE_EXCHANGE
step_dev_ms = sm.step_dev_ms
device_idle_share = sm.device_idle_share

ATTENTION = sm.ATTENTION["full"]
CACHE_READ = sm.CACHE_READ["full"]
CONV = ("conv.in_proj", "conv.state", "conv.mix", "conv.out_proj")
SCOPES = pt.SCOPES + ATTENTION + CONV + ("ffn.dense",)


@functools.lru_cache(maxsize=4)
def _scope_rows(path: str, span_name: str, t0: float, t1: float
                ) -> List[Dict[Optional[str], float]]:
    """``program_trace.busy_by_scope`` over this module's scope list, with
    the span's own arguments beside each row (``"args"``), the device time
    of all the span's operations as ONE union (``"busy"``: the expert
    loop's ``while`` envelops its body's operations, so a sum over scopes
    would count those twice) and, for a span that holds one, the arguments
    of its ``uccl.ep.experts`` span (``"experts"``)."""
    loaded = pt.load(path)
    spans = pt.spans_in(loaded.spans, span_name, t0, t1)
    counts = pt.spans_in(loaded.spans, EXPERTS, t0, t1)
    out, k = [], 0
    for sp, group in zip(spans, tr.events_inside(
            pt._window_ops(path, t0, t1), spans, span_name)):
        by: Dict[Optional[str], list] = {}
        for ev in group:
            by.setdefault(pt.scope_of(ev[3], SCOPES), []).append(ev)
        row = {s: tr.busy_ns(evs) for s, evs in by.items()}
        # both lists are in time order: the span's count is the first that
        # starts inside it
        while k < len(counts) and counts[k][1] < sp[1]:
            k += 1
        if row:
            row["args"] = sp[3] if len(sp) > 3 else {}
            row["busy"] = tr.busy_ns(group)
            if k < len(counts) and counts[k][1] <= sp[1] + sp[2]:
                row["experts"] = counts[k][3]
        out.append(row)
    return out


_NOT_SCOPES = ("args", "busy", "experts")


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
    return pt.scope_ms([{k: v for k, v in r.items() if k not in _NOT_SCOPES}
                        for r in rows], scopes)


def unscoped_share(view) -> Optional[float]:
    """Share (%) of the window's device-busy time in which NO operation
    under a scope ran. The decode program's expert loop is a ``while`` the
    trace shows under no scope, and it envelops its body's operations, which
    carry ``moe.experts``: the time of the envelope that no body operation
    covers (the loop's own turns) is unscoped, the body's is not."""
    if pt._loaded(view) is None:
        return None
    ops = pt._window_ops(view.record["trace_path"], *view.window)
    scoped = [ev for ev in ops if pt.scope_of(ev[3], SCOPES) is not None]
    if not scoped:
        return None
    busy = tr.busy_ns(ops)
    return 100.0 * (busy - tr.busy_ns(scoped)) / busy


def _decode_shares(view, need, scopes=None) -> Optional[float]:
    """Median over the window's ``uccl.wire.decode`` spans of ``need(slots,
    kv_rows, experts_read)`` bytes over the chip's HBM bandwidth over the
    span's device time (under ``scopes``, or all of it as one union of its
    operations), in %. ``slots`` and
    ``kv_rows`` are the span's own arguments ``n`` and ``kv_rows``,
    ``experts_read`` its ``uccl.ep.experts`` span's (None on a program that
    reports no count: ``need`` then says whether it can do without)."""
    rows = rows_in(view, DECODE)
    if not rows:
        return None
    shares = []
    for row in rows:
        args = row.get("args", {})
        slots, kv_rows = int(args.get("n", 0)), int(args.get("kv_rows", 0))
        read = row.get("experts", {}).get("experts_read")
        ns = row.get("busy", 0.0) if scopes is None \
            else sum(row.get(k, 0.0) for k in scopes)
        if ns <= 0 or slots < 1 or kv_rows < 1:
            continue
        bytes_ = need(slots, kv_rows, None if read is None else float(read))
        if bytes_ is not None:
            shares.append(100.0 * bytes_ / view.peaks["hbm_bytes_per_s"]
                          / (ns / 1e9))
    return percentile(shares, 50) if shares else None


def decode_hbm_roofline_share(view):
    """A decode program's share of its HBM roofline: the bytes the step must
    read (``flops_lfm2.decode_step_bytes``, with the experts the program
    counted as reached) over the program's device time."""
    return _decode_shares(
        view, lambda n, kv, read: None if read is None
        else flops_lfm2.decode_step_bytes(view.cfg, n, kv, read))


def decode_conv_roofline_share(view):
    """The conv operators' share of their HBM roofline in a decode program:
    every conv layer's bfloat16 matrices and the decoding rows' ring rows
    (``flops_lfm2.conv_decode_bytes``) over the device time under
    ``conv.*``."""
    return _decode_shares(
        view, lambda n, kv, read: flops_lfm2.conv_decode_bytes(view.cfg, n),
        CONV)


def decode_full_attention_roofline_share(view):
    """The attention layers' share of their HBM roofline over the cache in a
    decode program: the cached rows in use over the device time under
    ``attn.kv_write.full`` + ``attn.core.full``."""
    return _decode_shares(
        view, lambda n, kv, read: flops_lfm2.full_cache_bytes(view.cfg, kv),
        CACHE_READ)


def prefill_expert_mxu_share(view) -> Optional[float]:
    """What the padded expert queues cost: the FLOPs of the ROUTED rows of
    one prefill program (``flops_lfm2.routed_expert_flops`` of its ``rows x
    chunk`` tokens, the span's own arguments) over the chip's bfloat16 peak,
    over the device time under ``moe.experts`` in its ``uccl.wire.prefill``
    span; quotient program by program, median over the window's."""
    rows = rows_in(view, PREFILL)
    if not rows:
        return None
    s = view.cfg["serving"]
    shares = []
    for row in rows:
        ns = sum(row.get(scope, 0.0) for scope in MOE_EXPERTS)
        if ns <= 0:
            continue
        args = row.get("args", {})
        tokens = (int(args.get("rows", s["slots"]))
                  * int(args.get("chunk", s["prefill_chunk"])))
        shares.append(100.0 * flops_lfm2.routed_expert_flops(view.cfg, tokens)
                      / view.peaks["bf16_flops"] / (ns / 1e9))
    return percentile(shares, 50) if shares else None

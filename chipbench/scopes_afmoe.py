"""Scope groups of the ``afmoe`` programs (Trinity) and the bodies of the
per-layer readers of the cells that run them (each reader in
``layer_metrics/`` imports this module alone). The attention scopes carry
the layer's KIND as ``scopes_mimo``'s do, with one more part a kind: the
output's gate (``attn.gate.full`` / ``attn.gate.window``: the gate's
projection, its sigmoid and the product); QK-norm lies under ``attn.qkv.*``
and the attention branch's closing norm under ``attn.out.*``. Beside them
``moe.shared`` (the shared expert) and ``ffn.post_norm`` (the FFN branch's
closing norm). The reductions are ``scopes_mimo``'s and ``program_trace``'s,
made again over the wider list. A program without these scopes gives every
reader ``None``."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

from chipbench import flops_afmoe
from chipbench import program_trace as pt
from chipbench import scopes_mimo as sm
from chipbench import trace_reduce as tr
from chipbench.stats import percentile

# what the readers use of ``scopes_mimo`` as it stands, under this module's
# name
DECODE, PREFILL = sm.DECODE, sm.PREFILL
NAME_DECODE, NAME_PREFILL = sm.NAME_DECODE, sm.NAME_PREFILL
MOE_EXPERTS, MOE_EXCHANGE = sm.MOE_EXPERTS, sm.MOE_EXCHANGE
KINDS = sm.KINDS
step_dev_ms = sm.step_dev_ms
device_idle_share = sm.device_idle_share
idle_in_launch_ms_per_step = sm.idle_in_launch_ms_per_step
kv_pool_window_share = sm.kv_pool_window_share

GATE = {kind: (f"attn.gate.{kind}",) for kind in KINDS}
ATTENTION = {kind: sm.ATTENTION[kind] + GATE[kind] for kind in KINDS}
# where the cached rows are read and what the attended rows pass before the
# output projection: the write's scope (under which the compiler also
# prepares a layer's cached rows for the MXU), the core and the gate
CACHE_READ = {kind: sm.CACHE_READ[kind] + GATE[kind] for kind in KINDS}
MOE_SHARED = ("moe.shared",)
SCOPES = sm.SCOPES + GATE["full"] + GATE["window"] + MOE_SHARED \
    + ("ffn.post_norm",)


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


def _decode_shares(view, need, scopes=None) -> Optional[float]:
    """Median over the window's ``uccl.wire.decode`` spans of ``need(slots,
    kv_rows, window_rows)`` bytes over the chip's HBM bandwidth over the
    span's device time (under ``scopes``, or all of it), in %. The three
    are the span's own arguments ``n``, ``kv_rows`` and ``window_rows``; a
    span without the last (a program before it) is given its upper bound,
    ``min(n x sliding_window, kv_rows)``."""
    rows = rows_in(view, DECODE)
    if not rows:
        return None
    shares = []
    for row in rows:
        args = row.get("args", {})
        slots, kv_rows = int(args.get("n", 0)), int(args.get("kv_rows", 0))
        window_rows = int(args.get("window_rows", min(
            slots * view.cfg["sliding_window"], kv_rows)))
        ns = sum(v for k, v in row.items()
                 if k != "args" and (scopes is None or k in scopes))
        if ns <= 0 or slots < 1 or kv_rows < 1:
            continue
        shares.append(100.0 * need(slots, kv_rows, window_rows)
                      / view.peaks["hbm_bytes_per_s"] / (ns / 1e9))
    return percentile(shares, 50) if shares else None


def decode_hbm_roofline_share(view):
    """A decode program's share of its HBM roofline: the bytes the step must
    read (``flops_afmoe.decode_step_bytes``) over the program's device
    time."""
    return _decode_shares(
        view, lambda n, kv, win: flops_afmoe.decode_step_bytes(
            view.cfg, n, kv, win))


def decode_attention_roofline_share(view, kind: str):
    """The share of its HBM roofline of a kind's attention over the cache in
    a decode program (device time under ``attn.kv_write`` + ``attn.core`` +
    ``attn.gate`` of the kind), over the rows in use: a full layer's every
    cached row of the decoding slots, a window layer's ``min(length,
    window)`` a slot."""
    if kind == "full":
        def need(n, kv, win):
            return flops_afmoe.full_cache_bytes(view.cfg, kv)
    else:
        def need(n, kv, win):
            return flops_afmoe.window_cache_bytes(view.cfg, win)
    return _decode_shares(view, need, CACHE_READ[kind])


def prefill_expert_mxu_share(view) -> Optional[float]:
    """What the padded expert queues cost: the FLOPs of the rows routed to
    the held experts in one prefill program
    (``flops_afmoe.routed_expert_flops`` of its ``rows x chunk`` tokens, the
    span's own arguments) over the chip's bfloat16 peak, over the device
    time under ``moe.experts`` in its ``uccl.wire.prefill`` span; quotient
    program by program, median over the window's."""
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
        shares.append(100.0 * flops_afmoe.routed_expert_flops(view.cfg, tokens)
                      / view.peaks["bf16_flops"] / (ns / 1e9))
    return percentile(shares, 50) if shares else None

"""Shares of a peak of one PREFILL program, span by span: what
``scopes.decode_roofline_share`` is for a decode step. A reader in
``layer_metrics/`` names counting functions of the cell's family by key
(``f(cfg, facts)``, ``facts`` the ``uccl.wire.prefill`` span's own
arguments); a family without one gives ``None``, as does a run without a
program trace."""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench.stats import percentile


def prefill_peak_share(view, flops_of: Optional[str], bytes_of: Optional[str],
                       under: Optional[str] = None) -> Optional[float]:
    """Median over the window's ``uccl.wire.prefill`` spans of the LEAST
    time the chip could take — the larger of the family's ``flops_of`` over
    the bfloat16 peak and ``bytes_of`` over the HBM bandwidth (either may be
    None: not counted) — over the span's device time under the family's
    group ``under``, or all of it as one union; in %."""
    counts = [(sc._of(view, name), view.peaks[peak])
              for name, peak in ((flops_of, "bf16_flops"),
                                 (bytes_of, "hbm_bytes_per_s")) if name]
    scopes = sc.group(view, under) if under else ()
    rows = sc.rows_in(view, sc.PREFILL)
    if not counts or any(f is None for f, _ in counts) or scopes is None \
            or not rows:
        return None
    shares = []
    for row in rows:
        ns = sum(row.by.get(s, 0.0) for s in scopes) if under else row.busy
        if ns <= 0 or int(row.facts.get("n", 0)) < 1:
            continue
        least = max(f(view.cfg, row.facts) / peak for f, peak in counts)
        shares.append(100.0 * least / (ns / 1e9))
    return percentile(shares, 50) if shares else None

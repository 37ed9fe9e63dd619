"""The absorbed attention core's share of its HBM roofline in a decode
program: the cached latent rows in use (``kv_rows`` of the program's own
``uccl.wire.decode`` span) x 576 float32 numbers x layers
(``flops_glm4.latent_cache_bytes``: what the core must read at least
once) over the chip's HBM bandwidth, over the device time under
``attn.core`` in that span; median over the window's decode spans. The
core reads the whole pool, not the rows in use, and reads the compressed
part twice (scores, then values): this share says what that costs."""

from chipbench import flops_glm4
from chipbench import scopes_glm4 as sc
from chipbench.stats import percentile


def read(view):
    rows = sc.rows_in(view, sc.DECODE)
    if not rows:
        return None
    shares = []
    for row in rows:
        core_ns = sum(row.get(s, 0.0) for s in sc.ATTENTION_CORE)
        kv_rows = int(row.get("args", {}).get("kv_rows", 0))
        if core_ns <= 0 or kv_rows < 1:
            continue
        need = flops_glm4.latent_cache_bytes(view.cfg, kv_rows)
        shares.append(100.0 * need / view.peaks["hbm_bytes_per_s"]
                      / (core_ns / 1e9))
    return percentile(shares, 50) if shares else None

"""What part of the pool's slots decode in a decode step: the
``uccl.wire.decode`` span's ``n`` over ``serving.slots``, median over the
window's decode spans, in %. A program that passes over every slot's state
whatever decodes does this share of useful work on it: read for a family
whose pool has state groups (``STATE_POOL_GROUPS``), None for another."""
from chipbench import scopes as sc
from chipbench.stats import percentile


def read(view):
    if not sc._of(view, "STATE_POOL_GROUPS"):
        return None
    rows = sc.rows_in(view, sc.DECODE)
    n = [int(r.facts["n"]) for r in rows or () if "n" in r.facts]
    if not n:
        return None
    return 100.0 * percentile(n, 50) / view.cfg["serving"]["slots"]

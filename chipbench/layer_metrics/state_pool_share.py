"""The state groups' share of the chip's peak memory in use: the program's
``serving_kv_pool_bytes{group}`` gauge of the family's ``STATE_POOL_GROUPS``
(the per-slot states that have no position axis), as the runner recorded it
(``kv_pool_bytes``), over ``memory_peak_bytes``; in %."""


def read(view):
    groups = getattr(view.family, "STATE_POOL_GROUPS", None)
    by = view.record.get("kv_pool_bytes") or {}
    peak = view.record.get("memory_peak_bytes")
    held = sum(by.get(g) or 0 for g in groups or ())
    if not held or not peak:
        return None
    return 100.0 * held / peak

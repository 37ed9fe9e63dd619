"""The window group's share of the slot pool's bytes (the program's
``serving_kv_pool_bytes{group}`` gauge, set when the pool is born): five of
seven layers in 7 % of the pool, because a ring holds 256 rows a slot and
not 16,384."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.kv_pool_window_share(view)

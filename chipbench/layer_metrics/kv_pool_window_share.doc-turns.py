"""The window group's share of the slot pool's bytes (the program's
``serving_kv_pool_bytes{group}`` gauge, set when the pool is born): four of
five layers in rings of 4,224 rows a slot beside one layer of 16,384."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.kv_pool_window_share(view)

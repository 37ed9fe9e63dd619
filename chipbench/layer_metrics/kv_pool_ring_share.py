"""The ring groups' share of the slot pool's bytes (the program's
``serving_kv_pool_bytes{group}`` gauge, set when the pool is born, as the
runner recorded it): the layers whose cache is a ring of a window's or a
filter's rows a slot, beside those that hold every position."""
from chipbench.scopes import kv_pool_ring_share as read  # noqa: F401

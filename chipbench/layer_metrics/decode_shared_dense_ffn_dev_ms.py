"""Device time of the feed-forward blocks every token passes whatever the
router says (the shared expert of each expert layer and the leading dense
layer's FFN) in one decode program: the family's group
``shared_dense_ffn`` of scopes, over the operations that start inside a
``uccl.wire.decode`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, "shared_dense_ffn")

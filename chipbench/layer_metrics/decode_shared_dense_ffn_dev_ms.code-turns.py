"""Device time of the feed-forward blocks every token passes whatever the
router says, in one decode program: the shared expert of each expert layer
(scope ``moe.shared``) and the leading dense layer's FFN (``ffn.dense``),
inside a ``uccl.wire.decode`` span, median over the window's spans."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.SHARED_DENSE)

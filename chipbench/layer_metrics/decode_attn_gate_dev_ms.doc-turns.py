"""Device time of the attention output's gate in one decode program, all
layers of both kinds — the gate's projection (3072 x 6144 a layer), its
sigmoid and the product with the heads' output: scopes ``attn.gate.full``
+ ``attn.gate.window`` inside a ``uccl.wire.decode`` span, median over the
window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE,
                          sc.GATE["full"] + sc.GATE["window"])

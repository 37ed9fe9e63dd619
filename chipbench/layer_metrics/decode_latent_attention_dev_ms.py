"""Device time of latent attention (the query and key/value down-projections
with their norms, the up-projection and rope, the write of the latent row,
the absorbed core over the cached rows, the value up-projection and the
output projection) in one decode program: the family's group
``latent_attention`` of scopes, over the operations that start inside a
``uccl.wire.decode`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, "latent_attention")

"""Device time of latent attention in one decode program — the query and
key/value down-projections with their norms, the up-projection and rope,
the write of the 576-wide row, the absorbed core over the cached rows, the
value up-projection and output projection: scopes ``attn.*`` (with
``attn.latent_q`` and ``attn.latent_kv``) inside a ``uccl.wire.decode``
span, median over the window's spans."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.LATENT_ATTENTION)

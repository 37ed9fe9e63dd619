"""Device time of latent attention in one prefill program (``[1 | 2 | slots,
chunk]`` since PR 28): scopes ``attn.*`` (with ``attn.latent_q`` and
``attn.latent_kv``) inside a ``uccl.wire.prefill`` span, median over the
window's spans."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, sc.LATENT_ATTENTION)

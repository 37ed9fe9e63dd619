"""``brumby`` (Brumby-14B-Base): every layer a power retention, whose five
scopes are ``ret.qkv`` (projections, QK-norm, rotation), ``ret.gate``,
``ret.intra`` (the call's own ``[S, S]`` part), ``ret.state`` (the feature
map, the read of S and z, the inter-chunk term, the state's update and
write) and ``ret.out`` (normalise and ``W_o``), and a dense SwiGLU under
``ffn.dense``; no expert layer, so no expert group and no experts-read
count. ``flops_brumby.py``'s counts: a decode step's bytes need the span's
``n`` alone (no row by position is read)."""

from chipbench import flops_brumby

RETENTION = ("ret.qkv", "ret.gate", "ret.intra", "ret.state", "ret.out")
SCOPES = ("embed",) + RETENTION + ("ffn.dense", "head")
GROUPS = {
    "retention": RETENTION,
    "shared_dense_ffn": ("ffn.dense",),
}
STATE_POOL_GROUPS = ("retention",)


def decode_step_bytes(cfg, facts):
    return flops_brumby.decode_step_bytes(cfg, int(facts["n"]))


def retention_decode_bytes(cfg, facts):
    return flops_brumby.retention_decode_bytes(cfg, int(facts["n"]))


def _prefill_facts(cfg, facts):
    """(real tokens, real rows, chunk) of a ``uccl.wire.prefill`` span: its
    own arguments; a span without ``tokens`` (an engine before it carried
    them) is given ``n x chunk``, which overcounts a last chunk."""
    chunk = int(facts.get("chunk", cfg["serving"]["prefill_chunk"]))
    rows = int(facts.get("n", 1))
    return int(facts.get("tokens", rows * chunk)), rows, chunk


def retention_prefill_flops(cfg, facts):
    tokens, _, chunk = _prefill_facts(cfg, facts)
    return flops_brumby.retention_prefill_flops(cfg, tokens, chunk)


def retention_prefill_bytes(cfg, facts):
    return flops_brumby.retention_prefill_bytes(
        cfg, _prefill_facts(cfg, facts)[1])


def prefill_flops(cfg, facts):
    return flops_brumby.prefill_flops(cfg, *_prefill_facts(cfg, facts))

"""Device time of attention in one decode program — projections and rope,
the KV write, the GQA repeat with scores, softmax and values, the output
projection: scopes ``attn.*`` inside a ``uccl.wire.decode`` span, median
over the window's spans. (The pool copy the compiler inserts because the
slot pool is not donated carries no scope: ``unscoped_dev_share.chat``.)"""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.DECODE, pt.ATTENTION)

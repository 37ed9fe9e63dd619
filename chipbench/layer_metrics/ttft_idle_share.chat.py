"""Time the chip ran no program between a request's ``uccl.admit`` and
``uccl.first_token`` marks, summed over the requests that have both inside
the window, over the summed intervals, in % (``chipbench/request_timeline.py``):
the host's turns in the chunk steps a first token waits through."""

from chipbench import request_timeline


def read(view):
    return request_timeline.share(view, "idle")

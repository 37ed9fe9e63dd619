"""Device time in prefill programs between a request's ``uccl.admit`` and
``uccl.first_token`` marks, summed over the requests that have both inside
the window, over the summed intervals, in % (``chipbench/request_timeline.py``):
the part of a wait for the first token that is the prompt's own work."""

from chipbench import request_timeline


def read(view):
    return request_timeline.share(view, "prefill")

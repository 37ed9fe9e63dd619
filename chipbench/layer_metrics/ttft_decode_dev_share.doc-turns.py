"""Device time in decode and verify programs between a request's
``uccl.admit`` and ``uccl.first_token`` marks, summed over the requests that
have both inside the window, over the summed intervals, in %
(``chipbench/request_timeline.py``): what the other slots' tokens cost a
request that waits for its first."""

from chipbench import request_timeline


def read(view):
    return request_timeline.share(view, "decode")

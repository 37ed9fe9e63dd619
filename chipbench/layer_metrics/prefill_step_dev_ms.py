"""Device-busy time of one prefill program, ``[1 | 2 | slots, chunk]`` (the
operations that start inside the benchmark's span around
``backend.prefill``), median over the window's."""
from chipbench import scopes as sc
from chipbench.runners.serve import NAME_PREFILL


def read(view):
    return sc.step_dev_ms(view, NAME_PREFILL)

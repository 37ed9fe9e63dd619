"""Device-busy time of one decode-step program (the operations that start
inside the benchmark's span around ``backend.decode``), median."""
from chipbench import scopes as sc
from chipbench.runners.serve import NAME_DECODE


def read(view):
    return sc.step_dev_ms(view, NAME_DECODE)

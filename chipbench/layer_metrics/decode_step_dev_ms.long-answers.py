"""Device-busy time of one decode-step program (the operations that start
inside the benchmark's span around ``backend.decode``), median."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.step_dev_ms(view, sc.NAME_DECODE)

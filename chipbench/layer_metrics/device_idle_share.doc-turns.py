"""Share of the traced window in which no operation ran on the chip."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.device_idle_share(view)

"""Share of the traced window in which no operation ran on the chip."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.device_idle_share(view)

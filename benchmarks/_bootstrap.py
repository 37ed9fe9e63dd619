"""Shared benchmark bootstrap: repo path + optional virtual-CPU device forcing."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def init_devices(n_virtual: int):
    """Import jax, forcing n_virtual CPU devices when n_virtual > 0 (guarding
    against double-appending the XLA flag on repeated calls), and point the
    persistent compile cache at its fixed place (utils/device.py)."""
    if n_virtual:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_virtual}"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax
    from uccl_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    return jax

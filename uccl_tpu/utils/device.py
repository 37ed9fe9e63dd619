"""What this process runs on: the device summary every result line carries,
the compile-vs-interpret decision for Pallas kernels, and the persistent
compile cache.

One place, so a CPU run can never be read as a chip run: entry points stamp
:func:`describe` into their summary JSON, kernels ask
:func:`pallas_interpret` instead of probing devices themselves, and every
entry point calls :func:`enable_compile_cache` before its first compile.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def describe() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports the devices."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def pallas_interpret() -> bool:
    """Whether Pallas TPU kernels run under the interpreter: compiled by
    Mosaic on a TPU backend, interpreted on the CPU backend (how the tests
    run them). Any other backend can do neither and is an error."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need the 'tpu' backend (compiled) or the 'cpu' "
        f"backend (interpreted); jax.default_backend() is {backend!r}"
    )


def pin_cpu() -> None:
    """Pin this process, and every process it starts afterwards, to the CPU
    backend — for multi-process programs that measure host wires. A chip
    belongs to one process, so a parent and its children must never all
    reach for it; which backend they get must not depend on the ambient
    environment. Call before anything touches a device."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by children
    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a fixed directory, before
    the first compile. ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads
    it into ``jax_compilation_cache_dir`` itself, so nothing is set here);
    otherwise, on a TPU, the cache lives at ``<checkout>/.jax_cache`` — a
    fixed path, because the path is part of what makes a later run hit.
    CPU runs (the tests, at tiny sizes) get no default cache: XLA:CPU logs a
    machine-feature error for every executable it loads back. Returns the
    directory in effect, or None."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class _CompileCounter:
    """Process-wide, monotonic counts of XLA compilations, fed by
    jax.monitoring: programs handed to the backend compiler (persistent-
    cache hits included), the seconds that took, and the persistent-cache
    hits among them."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_compile_counter: Optional[_CompileCounter] = None


def compile_counts() -> dict:
    """``{"compiles", "compile_s", "cache_hits"}`` since this function was
    first called in the process. Callers diff two snapshots around a window
    (:func:`counts_since`) — e.g. to show that nothing compiles after
    warm-up."""
    global _compile_counter
    if _compile_counter is None:
        _compile_counter = _CompileCounter()
    c = _compile_counter
    return {"compiles": c.compiles, "compile_s": round(c.compile_s, 3),
            "cache_hits": c.cache_hits}


def counts_since(before: dict) -> dict:
    now = compile_counts()
    return {k: round(now[k] - before[k], 3) for k in now}


def peak_bytes_in_use() -> Optional[int]:
    """Largest ``peak_bytes_in_use`` over the local devices, where the
    backend reports memory statistics (the CPU backend does not)."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

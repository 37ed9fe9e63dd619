"""Elastic hybrid device/host buffers (XLA memory-kind offload).

The reference's DeepEPv2 runtime backs its EP windows with *host* memory when
device memory is short or GPUDirect is absent (ElasticBuffer,
experimental/lite/lite-ep/csrc/elastic/buffer.hpp: ``uccl_use_host_window``,
host workspace mapped into the device; lite-ep/README.md:35 "elastic hybrid
GPU/CPU buffers"). The TPU-native analog is XLA's memory-space annotation:
an array lives in ``device`` (HBM) or ``pinned_host`` memory of the same
TPU, moved by ``jax.device_put`` (async, DMA-backed on TPU).

Two facilities:

* :class:`ElasticBuffer` — a named tensor store with an HBM budget: arrays
  placed on device while the budget holds, spilled to pinned host memory
  beyond it; ``get`` stages host-resident arrays back on demand.
* :class:`ElasticKVCache` — the serving-side application: a blockwise KV
  cache whose hot tail lives in HBM and whose cold prefix is offloaded to
  host memory, letting decode contexts grow past the HBM budget. Feeds the
  same attention contract as ``models.inference`` (see
  ``decode_step_elastic`` there).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding


def _nbytes(arr) -> int:
    return int(np.prod(arr.shape)) * arr.dtype.itemsize


def _memory_shardings(device) -> Tuple[SingleDeviceSharding, SingleDeviceSharding, bool]:
    """(device_sharding, host_sharding, has_host) for one device. Backends
    without a pinned_host memory space degrade to device-only placement —
    the elastic API keeps working, spills just stay in HBM. The device-side
    kind is probed rather than assumed: some CPU backends expose only
    ``unpinned_host`` and reject the literal ``"device"`` kind."""
    kinds = {m.kind for m in device.addressable_memories()}
    dev_kind = (
        "device" if "device" in kinds else device.default_memory().kind
    )
    device_s = SingleDeviceSharding(device, memory_kind=dev_kind)
    if "pinned_host" in kinds:
        return device_s, SingleDeviceSharding(device, memory_kind="pinned_host"), True
    return device_s, device_s, False


class ElasticBuffer:
    """Named tensor store with an HBM budget and pinned-host spill.

    put() places an array in device memory while ``device_bytes`` stays
    under the budget, else in pinned host memory. get() always returns a
    device-resident array (host-resident entries are staged per call and
    NOT promoted — the store's placement is the durable state, a get is a
    read). pin=True forces device placement regardless of budget (the
    analog of the reference's always-device workspace).
    """

    def __init__(self, hbm_budget_bytes: int, device=None):
        self.device = device if device is not None else jax.devices()[0]
        self.budget = int(hbm_budget_bytes)
        self._device_s, self._host_s, self.has_host = _memory_shardings(
            self.device
        )
        self._store: Dict[str, jax.Array] = {}
        self._on_device: Dict[str, bool] = {}

    @property
    def device_bytes(self) -> int:
        return sum(
            _nbytes(a) for n, a in self._store.items() if self._on_device[n]
        )

    @property
    def host_bytes(self) -> int:
        return sum(
            _nbytes(a) for n, a in self._store.items() if not self._on_device[n]
        )

    def put(self, name: str, arr: jax.Array, *, pin: bool = False) -> None:
        if name in self._store:
            self.delete(name)
        fits = self.device_bytes + _nbytes(arr) <= self.budget
        on_dev = pin or fits or not self.has_host
        sharding = self._device_s if on_dev else self._host_s
        self._store[name] = jax.device_put(arr, sharding)
        self._on_device[name] = on_dev

    def get(self, name: str) -> jax.Array:
        arr = self._store[name]
        if self._on_device[name]:
            return arr
        return jax.device_put(arr, self._device_s)

    def placement(self, name: str) -> str:
        return "device" if self._on_device[name] else "host"

    def offload(self, name: str) -> None:
        """Explicitly demote an entry to host memory (frees its HBM)."""
        if self._on_device[name] and self.has_host:
            self._store[name] = jax.device_put(self._store[name], self._host_s)
            self._on_device[name] = False

    def delete(self, name: str) -> None:
        self._store.pop(name, None)
        self._on_device.pop(name, None)

    def names(self) -> List[str]:
        return list(self._store)


class ElasticKVCache:
    """Blockwise KV cache: hot blocks in HBM, cold blocks in host memory.

    Token layout mirrors ``models.inference.KVCache`` per block:
    k/v blocks are ``[L, B, block_tokens, Hkv, D]``. The cache holds
    ``hot_blocks`` most-recent full blocks on device; older full blocks are
    offloaded to pinned host memory as they age out. A partial "current"
    block accumulates decode-time tokens on device.

    ``kv()`` returns the full (K, V, length) context on device — cold
    blocks are staged back per call (async ``device_put``s overlap on TPU),
    which is the streaming cost elasticity pays for contexts beyond HBM.
    """

    def __init__(
        self,
        n_layers: int,
        batch: int,
        n_kv_heads: int,
        head_dim: int,
        *,
        block_tokens: int = 128,
        hot_blocks: int = 4,
        dtype=jnp.float32,
        device=None,
    ):
        self.shape = (n_layers, batch, block_tokens, n_kv_heads, head_dim)
        self.block_tokens = block_tokens
        self.hot_blocks = max(1, int(hot_blocks))
        self.dtype = dtype
        self.device = device if device is not None else jax.devices()[0]
        self._device_s, self._host_s, self.has_host = _memory_shardings(
            self.device
        )
        self._cold: List[Tuple[jax.Array, jax.Array]] = []
        self._hot: List[Tuple[jax.Array, jax.Array]] = []
        self._cur_k = jnp.zeros(self.shape, dtype)
        self._cur_v = jnp.zeros(self.shape, dtype)
        self._cur_fill = 0

    @property
    def length(self) -> int:
        return (
            (len(self._cold) + len(self._hot)) * self.block_tokens
            + self._cur_fill
        )

    @property
    def cold_blocks(self) -> int:
        return len(self._cold)

    def device_committed_bytes(self) -> int:
        """HBM durably held by the cache (hot ring + current block); cold
        blocks live in host memory and only transit HBM inside kv()."""
        per_block = 2 * int(np.prod(self.shape)) * jnp.dtype(self.dtype).itemsize
        return (len(self._hot) + 1) * per_block

    def _seal_current(self) -> None:
        self._hot.append(
            (
                jax.device_put(self._cur_k, self._device_s),
                jax.device_put(self._cur_v, self._device_s),
            )
        )
        self._cur_k = jnp.zeros(self.shape, self.dtype)
        self._cur_v = jnp.zeros(self.shape, self.dtype)
        self._cur_fill = 0
        while len(self._hot) > self.hot_blocks:
            k, v = self._hot.pop(0)
            self._cold.append(
                (
                    jax.device_put(k, self._host_s),
                    jax.device_put(v, self._host_s),
                )
            )

    def append_tokens(self, k_new: jax.Array, v_new: jax.Array) -> None:
        """k/v_new: [L, B, S_new, Hkv, D] — append S_new tokens (prefill
        chunks or single decode tokens)."""
        s_new = k_new.shape[2]
        off = 0
        while off < s_new:
            room = self.block_tokens - self._cur_fill
            take = min(room, s_new - off)
            sl = (slice(None), slice(None), slice(off, off + take))
            self._cur_k = jax.lax.dynamic_update_slice(
                self._cur_k,
                k_new[sl].astype(self.dtype),
                (0, 0, self._cur_fill, 0, 0),
            )
            self._cur_v = jax.lax.dynamic_update_slice(
                self._cur_v,
                v_new[sl].astype(self.dtype),
                (0, 0, self._cur_fill, 0, 0),
            )
            self._cur_fill += take
            off += take
            if self._cur_fill == self.block_tokens:
                self._seal_current()

    def kv(self) -> Tuple[jax.Array, jax.Array, int]:
        """Full context on device: (K, V, length), K/V
        [L, B, n_blocks*block_tokens, Hkv, D] (tail beyond `length` is
        zero padding from the partial block)."""
        staged_k, staged_v = [], []
        for k, v in self._cold:  # issue all stagings first: async overlap
            staged_k.append(jax.device_put(k, self._device_s))
            staged_v.append(jax.device_put(v, self._device_s))
        for k, v in self._hot:
            staged_k.append(k)
            staged_v.append(v)
        staged_k.append(self._cur_k)
        staged_v.append(self._cur_v)
        return (
            jnp.concatenate(staged_k, axis=2),
            jnp.concatenate(staged_v, axis=2),
            self.length,
        )

    @staticmethod
    def from_cache(cache, *, block_tokens=128, hot_blocks=4, device=None):
        """Blockify a ``models.inference.KVCache`` produced by prefill (the
        disaggregation hand-off: prefill ships a dense cache, the decode
        worker re-homes it elastically)."""
        n_layers, batch, _, hkv, d = cache.k.shape
        length = int(cache.length)
        ekv = ElasticKVCache(
            n_layers, batch, hkv, d,
            block_tokens=block_tokens, hot_blocks=hot_blocks,
            dtype=cache.k.dtype, device=device,
        )
        ekv.append_tokens(cache.k[:, :, :length], cache.v[:, :, :length])
        return ekv


def admit_warm_spare(buf: ElasticBuffer, weights, *, prefix: str = "",
                     pin: bool = False) -> int:
    """Warm-spare admission: import a model's weights into an elastic
    store — the spin-up path of an elastic resize (a spare joining the
    fleet mid-run stages its params here before taking traffic).

    ``weights`` is a fetched weight-push snapshot
    (:class:`uccl_tpu.p2p.weight_push.WeightSnapshot` — the versioned
    fleet distribution path, whose wire bytes were already counted at
    fetch time) or a plain ``{name: array}`` mapping / param pytree. A
    raw tree is the legacy local-copy path: its bytes land on
    ``p2p_bytes_total{verb="weight_push"}`` here so a spare admitted off
    an untracked host copy is visible on the SAME fleet byte series as a
    wire-fetched one — never silent. Returns the bytes imported; entries
    are named ``prefix + dotted-path``."""
    from uccl_tpu import obs
    from uccl_tpu.p2p import weight_push as _wp

    if isinstance(weights, _wp.WeightSnapshot):
        pairs = list(weights.flat().items())
        version = weights.version
    else:
        pairs = [(k, np.asarray(v))
                 for k, v in _wp.flatten_tree(weights)]
        version = None
        obs.counter("p2p_bytes_total").inc(
            sum(int(a.nbytes) for _, a in pairs), verb="weight_push")
    total = 0
    for key, arr in pairs:
        buf.put(prefix + key, jnp.asarray(arr), pin=pin)
        total += int(arr.nbytes)
    obs.instant("warm_spare_admit", track="wire", entries=len(pairs),
                bytes=total, version=version)
    return total


def admit_warm_replica(router, prototype_backend, *, weights=None,
                       engine_kw: Optional[Dict] = None):
    """Elastic UP-scale: build a warm-spare serving replica off
    ``prototype_backend`` (``clone``: its own pool, the prototype's
    compiled programs — the ``serving.replicate_backend`` rule),
    optionally serving a pushed weight snapshot
    (:class:`~uccl_tpu.p2p.weight_push.WeightSnapshot` — its wire bytes
    were counted at fetch), and :meth:`~uccl_tpu.serving.Router.attach`
    it to the live router mid-run. The twin of ``Router.detach`` (the
    graceful down-scale): together they are the fleet-resize primitive
    the load-following control loop actuates. Returns the new
    ``ServingEngine`` (its stable replica id is on the router's
    ``attach`` instant)."""
    from uccl_tpu.serving.engine import ServingEngine

    eng = ServingEngine(prototype_backend.clone(weights),
                        **(engine_kw or {}))
    router.attach(eng)
    return eng

"""Continuous-batching serving engine (docs/SERVING.md).

Request scheduler + KV slot manager + serving metrics over the repo's
dense and MoE serving stacks: requests arrive at any time, share one fixed
KV slot pool, and each engine step admits, prefills, decodes and retires —
with every request's tokens bit-identical to the one-shot ``generate``
oracle.
"""

from uccl_tpu.serving.adapters import (  # noqa: F401
    AdapterStore, make_lora, materialize,
)
from uccl_tpu.serving.backend import (  # noqa: F401
    DenseBackend, MoEBackend, replicate_backend,
)
from uccl_tpu.serving.engine import ChunkEvent, ServingEngine  # noqa: F401
from uccl_tpu.serving.sampling import SamplingParams  # noqa: F401
from uccl_tpu.serving.metrics import (  # noqa: F401
    ServingMetrics, percentile, percentiles_ms,
)
from uccl_tpu.serving.health import (  # noqa: F401
    DEAD, HEALTHY, SUSPECT, FailureDetector, abandon_engine,
)
from uccl_tpu.serving.kv_tiers import (  # noqa: F401
    HostKVTier, KvTierServer, RemoteKVTier, TieredKVCache, TierRef,
)
from uccl_tpu.serving.prefix_cache import PrefixCache  # noqa: F401
from uccl_tpu.serving.request import Request, RequestState  # noqa: F401
from uccl_tpu.serving.router import Router, replica_signals  # noqa: F401
from uccl_tpu.serving.scheduler import (  # noqa: F401
    PRIORITY_CLASSES, FIFOScheduler, PriorityScheduler,
    TenantFairScheduler,
)
from uccl_tpu.serving.slots import SlotPool  # noqa: F401
from uccl_tpu.serving.spec import Drafter, NGramDrafter  # noqa: F401

# uccl_tpu.serving.disagg (the prefill/decode worker pair over p2p) is
# imported explicitly by its consumers — it pulls in the p2p runtime.

__all__ = [
    "ChunkEvent", "DenseBackend", "MoEBackend", "ServingEngine",
    "ServingMetrics", "percentile", "percentiles_ms", "PrefixCache",
    "Request", "RequestState", "FIFOScheduler", "PriorityScheduler",
    "TenantFairScheduler", "PRIORITY_CLASSES", "Router",
    "replica_signals", "SlotPool",
    "Drafter", "NGramDrafter", "replicate_backend",
    "SamplingParams", "AdapterStore", "make_lora", "materialize",
    "FailureDetector", "HEALTHY", "SUSPECT", "DEAD", "abandon_engine",
    "TieredKVCache", "HostKVTier", "KvTierServer", "RemoteKVTier",
    "TierRef",
]

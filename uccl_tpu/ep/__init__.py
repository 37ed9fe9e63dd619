"""Expert-parallel engine: MoE dispatch/combine over the mesh.

The analog of the reference's ``ep/`` pillar (DeepEP-compatible dispatch/combine
all-to-all, SURVEY.md §2.3). The reference replaces NVIDIA IBGDA with a GPU→CPU
command ring + CPU proxy posting RDMA (ep/include/uccl_ibgda.cuh:27,
ep/src/proxy.cpp:701); on TPU the fabric is compiler-driven, so dispatch/combine
lower to capacity-bucketed ``lax.all_to_all`` exchanges over the EP mesh axes —
the GShard-lineage formulation that keeps every shape static and every matmul on
the MXU — with optional fp8 payload packing on the wire (the analog of
internode_ll.cu's fp8+scales message format).

Surfaces:
* :mod:`uccl_tpu.ep.ops`    — per-shard routing/dispatch/combine for shard_map code.
* :mod:`uccl_tpu.ep.ll`     — packed low-latency path: ragged wire + grouped
  GEMMs over receive counts (the DeepEP LL contract, internode_ll.cu analog).
* :mod:`uccl_tpu.ep.pallas_a2a` — device-initiated all-to-all: the member-major
  exchange as ONE Pallas kernel issuing inter-chip remote DMAs (write-once
  per-source slots, credit-granted flow control) — selected via
  ``Buffer(..., wire="pallas")`` for both the normal and LL row formats;
  ``n_chunks=N`` chunk-pipelines it (double-buffered per-chunk kernels, so
  the MoE layer overlaps expert GEMMs with dispatch/combine DMAs).
* :class:`uccl_tpu.ep.Buffer` — DeepEP-shaped host API (dispatch / combine /
  low_latency_dispatch / low_latency_combine / get_dispatch_layout), including
  the overlap half of the contract: :class:`uccl_tpu.ep.EventOverlap`
  dataflow events (previous_event / async_finish), two-phase receive hooks
  (return_recv_hook), and :class:`uccl_tpu.ep.Config` tuning hints.
"""

from uccl_tpu.ep import ll, ops
from uccl_tpu.ep.buffer import Buffer, Config, EventOverlap, LowLatencyHandle
from uccl_tpu.ep.cross_pod import CrossPodMoE
from uccl_tpu.ep.elastic import ElasticBuffer, ElasticKVCache
from uccl_tpu.ep.engram import EngramTable, mesh_fetch

__all__ = [
    "ops",
    "ll",
    "pallas_a2a",
    "Buffer",
    "Config",
    "EventOverlap",
    "LowLatencyHandle",
    "CrossPodMoE",
    "ElasticBuffer",
    "ElasticKVCache",
    "EngramTable",
    "mesh_fetch",
]


def __getattr__(name):
    # the kernel module (and Pallas with it: most of a second of import) is
    # loaded when something names it, not with the package: the lax wire,
    # which every one-chip serving engine takes, never does
    if name == "pallas_a2a":
        import importlib

        return importlib.import_module("uccl_tpu.ep.pallas_a2a")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

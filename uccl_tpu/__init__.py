"""uccl_tpu — a TPU-native communication + parallelism framework.

A ground-up rebuild of the capabilities of uccl-project/uccl (see SURVEY.md) designed
for TPU hardware: JAX/XLA/Pallas for the device compute path, a C++ host runtime for
the DCN transfer engine, and `jax.sharding` meshes for multi-chip scale.

Three pillars (mirroring the reference's product surface, reference README.md:18-66):

1. ``uccl_tpu.collective`` — NCCL-shaped collectives API lowered to XLA collectives
   over the ICI mesh (the analog of the reference's ``collective/`` NCCL plugin).
2. ``uccl_tpu.p2p``        — NIXL-style transfer engine for KV-cache / weight movement
   over DCN (the analog of ``p2p/engine.{h,cc}``), C++ host runtime underneath.
3. ``uccl_tpu.ep``         — DeepEP-compatible MoE expert-parallel dispatch/combine
   (the analog of ``ep/``), as sharded ragged all-to-all on the mesh.

Plus ``uccl_tpu.parallel`` (mesh management, ring attention, Ulysses, pipeline — the
sequence/context-parallel layer SURVEY.md §5 requires), ``uccl_tpu.ops`` (Pallas
kernels), and ``uccl_tpu.models`` (flagship model families exercising every axis).
"""

from uccl_tpu.version import __version__

__all__ = ["__version__"]

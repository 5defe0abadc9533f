"""fluidframework_tpu — a TPU-native real-time collaborative data framework.

A ground-up rebuild of the capabilities of Fluid Framework (reference:
``adrianlee/FluidFramework``; see SURVEY.md — the reference mount was empty, so
citations are to stable public package names, e.g. ``@fluidframework/merge-tree``,
rather than file:line).

Architecture (TPU-first, NOT a port of the reference's TypeScript object graph):

- ``models/``   — the DDS layer: oracle (pure-Python, obviously-correct) collaborative
                  data structures with exact Fluid merge semantics. These are the
                  *specification* for the tensor kernels and the interactive client API.
- ``ops/``      — packed op-record schema + batched (doc x op x segment) JAX/XLA
                  kernels: the sequenced-op merge engine that applies totally-ordered
                  ops for thousands of documents in one jit'd step.
- ``parallel/`` — device mesh, shard_map'd merge step, ICI collectives (all-gather of
                  sequenced op batches = the "Broadcaster"), cross-replica digests.
- ``server/``   — the ordering service: Deli-style sequencer (Python + C++), local
                  in-process orderer ("tinylicious"), durable op log, summaries.
- ``runtime/``  — container runtime: op routing, outbox/batching, compression,
                  pending-state rebase, summarizer, GC, id-compressor.
- ``loader/``   — container lifecycle, DeltaManager (op pump), quorum/protocol.
- ``drivers/``  — service adapters (local, replay, file).
- ``testing/``  — mock in-memory sequencer (the MockContainerRuntimeFactory pattern),
                  seeded fuzz generators, convergence checkers.
"""

__version__ = "0.1.0"

# Persistent XLA compile cache, placeable from outside: when
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no code names
# another directory; otherwise the cache lives in the checkout, at a path
# that never moves (the path is part of the cache key — a temp, pid- or
# time-derived directory would never hit). Every entry point gets this
# through the import.
import os as _os  # noqa: E402

import jax as _jax  # noqa: E402

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))

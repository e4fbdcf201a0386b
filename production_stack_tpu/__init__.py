"""TPU Production Stack: a TPU-native LLM serving stack.

A from-scratch rebuild of the capabilities of vLLM Production Stack
(reference: bytedance-iaas/production-stack) for GKE TPU pods:

- ``engine/``   — a JAX/XLA-native serving engine (continuous batching,
  static-shape KV cache, OpenAI-compatible HTTP server). The reference
  delegates this layer to the external ``vllm/vllm-openai`` container
  (reference: helm/templates/deployment-vllm-multi.yaml:57-64); here it is
  a first-class, TPU-first component.
- ``models/``   — Llama-family decoder models as pure-JAX functions.
- ``ops/``      — TPU compute ops (RMSNorm, RoPE, attention; Pallas kernels).
- ``parallel/`` — device-mesh parallelism (dp/tp/sp) via jax.sharding.
- ``router/``   — the L7 OpenAI-compatible request router (reference:
  src/vllm_router/), with service discovery, session-affinity routing,
  stats, dynamic config, files/batches APIs.
- ``utils/``    — logging, singletons, misc helpers.
"""

import time

# the package's first import: what a start is counted from where the
# platform does not say when the process began (engine/efficiency.py
# ``process_start``)
IMPORTED_UNIX = time.time()

from production_stack_tpu.version import __version__  # noqa: E402

__all__ = ["__version__"]

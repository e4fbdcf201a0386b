"""Device-mesh construction for the serving meshes: dp / ep / tp.

The TPU-native replacement for the reference's NCCL-implied distributed
backend (reference: the /dev/shm mount for NCCL at
helm/templates/deployment-vllm-multi.yaml:197-228 and the
--tensor-parallel-size passthrough at :84-87): parallelism here is a
jax.sharding.Mesh over the slice's chips, with XLA inserting ICI
collectives from sharding annotations — no process groups, no shm.

Axes:
  dp — data parallel (the KV pool's block axis; forfeits the paged
       attention kernel, engine/runner.py's dp cliff)
  ep — expert parallel (MoE expert weights, ops/moe.py)
  tp — tensor parallel (megatron column/row sharding of matmuls)

tp stays innermost (ICI-nearest: its per-layer psums are the most
latency-sensitive collectives); ep sits just above it so expert
dispatch/combine also rides ICI.

Multi-replica scaling above a slice stays at the stack level (router over
engine replicas), exactly like the reference's L1/L3 split.
"""

import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

AXES = ("dp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    tp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.tp * self.ep

    @staticmethod
    def for_devices(n: int, tp: Optional[int] = None) -> "MeshConfig":
        """Factor n devices into (dp, tp), tp on the innermost
        (ICI-nearest) axis: tp = 2 where n is even unless given, dp
        the rest."""
        if tp is None:
            tp = 2 if n % 2 == 0 else 1
        if n % tp:
            raise ValueError(f"tp={tp} does not divide {n} devices")
        return MeshConfig(dp=n // tp, tp=tp)


def build_mesh(cfg: Optional[MeshConfig] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    cfg = cfg or MeshConfig.for_devices(len(devices))
    if cfg.size != len(devices):
        raise ValueError(
            f"mesh {cfg} needs {cfg.size} devices, have {len(devices)}")
    import numpy as np
    dev_array = np.asarray(devices).reshape(cfg.dp, cfg.ep, cfg.tp)
    return Mesh(dev_array, AXES)

from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.parallel.sharding import param_shardings

__all__ = ["MeshConfig", "build_mesh", "param_shardings"]

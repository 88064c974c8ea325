"""TPU-native distributed deep-learning framework.

A brand-new JAX/XLA framework with the capabilities of the reference
Batch AI Horovod tutorial (GKarmakar/DistributedDeepLearning): synchronous
data-parallel training of ImageNet-class vision models, a seeded synthetic
data mode, three API front-ends, rank-aware logging, rank-0
checkpoint/resume, and an images/sec throughput harness — designed
TPU-first: a `jax.sharding.Mesh` over ICI/DCN with XLA collectives instead
of Horovod/NCCL/MPI, `shard_map`/`pjit` instead of `mpirun`, and Pallas
kernels as the native tier.

Reference parity map lives in SURVEY.md §7 at the repo root.
"""

__version__ = "0.1.0"

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.parallel.mesh import MeshConfig, create_mesh
from distributeddeeplearning_tpu.utils.timer import Timer, timer

__all__ = [
    "TrainConfig",
    "MeshConfig",
    "create_mesh",
    "Timer",
    "timer",
    "__version__",
]

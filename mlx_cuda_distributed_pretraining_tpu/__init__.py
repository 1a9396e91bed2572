"""TPU-native LLM pretraining framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
``arthurcolle/mlx-cuda-distributed-pretraining`` (the MLX/CUDA reference):
Llama-family pretraining with flash/flex attention, a full optimizer stack
(AdamW/SGD/Lion/Muon/Shampoo/Hybrid), data/tensor/sequence parallelism over
``jax.sharding`` meshes, streaming data pipelines, checkpoint/resume in the
reference's ``runs/`` layout, KV-cached generation, and observability.

The compute path is JAX + Pallas TPU kernels; parallelism is SPMD over a
named device mesh with XLA collectives (psum / all_gather / ppermute) over
ICI — replacing the reference's thread-queue + JSON/HTTP/Modal RPC layer
(reference: distributed/hybrid_distributed.py, distributed/worker.py).
"""

__version__ = "0.1.0"

"""repro — VSS (Video Storage System, Haynes et al. 2021) rebuilt as the
storage subsystem of a multi-pod JAX training/inference framework.

Layers (bottom-up):
  repro.kernels   Pallas TPU kernels (+ jnp oracles) for codec/quality hot-spots,
                  and the jitted XLA warp of joint compression
  repro.codec     GOP-based tensor video codec (TVC) with quality tiers
  repro.core      the paper's storage manager: catalog, cost/quality models,
                  fragment selection (greedy/DP/Z3), LRU_VSS cache, deferred
                  compression, compaction, joint compression
  repro.models    model zoo for the 10 assigned architectures
  repro.data      VSS-backed input pipelines (tokens + synthetic video)
  repro.optim     AdamW, schedules, gradient compression
  repro.train     fault-tolerant training loop + VSS-backed checkpoints
  repro.serving   paged-KV serving engine on VSS pages
  repro.launch    production mesh, multi-pod dry-run, roofline extraction
"""

__version__ = "0.1.0"

"""Device kernels (Pallas ones validated in interpret mode on CPU).

- fabric_chain: the fabric's batched emulation engine (XLA, not Pallas):
  the fixpoint collapsed onto the PE input slots, and the PE ALU datapath
- hpwl: per-net bounding-box reduction for SA placement; compiles for
  TPU v5e
- minplus: tropical relaxation for batched routing wavefronts; compiles
  for TPU v5e
- flash_attention: LM prefill attention
- ssd_scan: Mamba-2 chunked state-space scan
"""
from . import ops, ref  # noqa: F401

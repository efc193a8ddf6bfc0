"""Pallas kernels (validated in interpret mode on CPU).

- fabric_step: batched CGRA fabric sweep (the paper's generated hardware);
  interpret mode only — Mosaic refuses its gathers for TPU
- fabric_chain: the served XLA emulation engine (not Pallas): the
  fixpoint collapsed onto the PE input slots
- hpwl: per-net bounding-box reduction for SA placement; compiles for
  TPU v5e
- minplus: tropical relaxation for batched routing wavefronts; compiles
  for TPU v5e
- flash_attention: LM prefill attention
- ssd_scan: Mamba-2 chunked state-space scan
"""
from . import ops, ref  # noqa: F401

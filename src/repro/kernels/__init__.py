"""Pallas TPU kernels for the perf-critical sparse contractions.

bsr_spmm: block-sparse adjacency x multi-vector with fused Ca/Ch scaling
          (the accelerated-HITS sweep hot path).
Validated in interpret=True mode against ref.py oracles; TPU is the target.
"""
from .bsr_spmm import bsr_converge_cols, bsr_scaled_matvec, resolve_interpret
from .ops import (DeviceBSR, bsr_converge, bsr_matvec, classify_exit,
                  hits_sweep_bsr, pad_empty_rows)

__all__ = [
    "bsr_scaled_matvec", "bsr_converge_cols", "resolve_interpret",
    "DeviceBSR", "bsr_converge", "bsr_matvec", "classify_exit",
    "hits_sweep_bsr", "pad_empty_rows",
]

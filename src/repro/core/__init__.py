"""repro.core — the paper's contribution, productized.

Quantifying (and then exploiting) the effect of matrix structure on sparse
matrix-vector multiply performance:

  formats      CSR / ELL / BELL / DIA / HYB sparse containers (pytrees)
  delta        EdgeDelta batched edge mutations for streaming matrices
  generators   FD 9-point stencil + R-MAT (paper §II-A) + sweep helpers
  structure    structure metrics: bandedness, locality, block density
  cache_model  Sandy Bridge L2/L3+prefetcher model -> the paper's 5 metrics
  traffic      TPU HBM<->VMEM movement model (hardware adaptation)
  partition    row-blocking (threads/chips) + column-blocking (VMEM cache)
  spmv         jnp reference kernels + their dispatch by container
"""
from . import cache_model, delta, formats, generators, partition, spmv, structure, traffic
from .cache_model import SANDY_BRIDGE, CacheMetrics, MachineModel, analytic_metrics
from .delta import EdgeDelta, csr_diff, csr_lookup
from .formats import BELL, CSR, DIA, ELL, HYB
from .generators import banded_matrix, fd_matrix, rmat_matrix, uniform_random_matrix
from .spmv import spmv
from .structure import StructureReport, analyze
from .traffic import TPU_V5E, TPUModel

__all__ = [
    "cache_model", "delta", "formats", "generators", "partition", "spmv",
    "structure", "traffic", "SANDY_BRIDGE", "CacheMetrics", "MachineModel",
    "analytic_metrics", "BELL", "CSR", "DIA", "ELL", "HYB", "EdgeDelta",
    "csr_diff", "csr_lookup", "banded_matrix",
    "fd_matrix", "rmat_matrix", "uniform_random_matrix",
    "analyze", "StructureReport", "TPU_V5E", "TPUModel",
]

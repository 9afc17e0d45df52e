"""Pallas TPU kernels for the paper's compute hot-spots.

  histogram         — visit-count one-hot reduction (engine super-steps)
  segment_spmv      — one-hot-MXU CSR push (power-iteration baseline)
  walk_step         — fused terminate/select/advance walk step
  multinomial_rows  — fused Binomial-termination + binomial-tree
                      aggregate multinomial over a degree bucket

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; interpret on CPU), ref.py (pure-jnp oracle).
"""
from repro.kernels.common import resolve_use_pallas
from repro.kernels.histogram import histogram
from repro.kernels.multinomial_rows import multinomial_rows
from repro.kernels.segment_spmv import segment_spmv
from repro.kernels.walk_step import walk_step

__all__ = ["histogram", "multinomial_rows", "resolve_use_pallas",
           "segment_spmv", "walk_step"]

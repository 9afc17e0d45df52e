"""Jitted public wrapper: interpret mode off-TPU; refused on TPU.

The kernel maps the whole CSR into VMEM and gathers from it with
`jnp.take`. Mosaic lowers only 2-D gathers within a vreg, so it refuses
this kernel for the chip ("Only 2D gather is supported"; pinned by
tests/test_tpu_compile.py). Rather than fall back to interpret mode or
the jnp path behind the caller's back, a compiled call raises.
"""
from __future__ import annotations

from repro.kernels.common import default_interpret
from repro.kernels.walk_step.walk_step import walk_step_pallas

TPU_REFUSED = (
    "walk_step does not compile for TPU: it gathers from the whole CSR in "
    "VMEM and Mosaic lowers only 2-D gathers ('Only 2D gather is "
    "supported'). Run engines that step walks (walks, ppr, the 3-phase "
    "tail) with use_pallas=False on TPU.")


def walk_step(pos, alive, u_term, u_edge, row_ptr, col_idx, out_deg, *,
              eps: float, **kw):
    kw.setdefault("interpret", default_interpret())
    if not kw["interpret"]:
        raise NotImplementedError(TPU_REFUSED)
    return walk_step_pallas(pos, alive, u_term, u_edge, row_ptr, col_idx,
                            out_deg, eps=eps, **kw)

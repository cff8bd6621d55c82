"""Pallas TPU kernels for the perf-critical compute layers.

* ``spmv_ell``        — hybrid-ELL SpMV (power-iteration / engine hot loop)
* ``frog_scatter``    — frog-count histogram (scatter-add, TPU-restructured)
* ``frog_step``       — fused plain walker superstep (gather deg → draw slot
                        → gather successor → tally deaths, one VMEM pass)
* ``flash_attention`` — causal GQA flash attention (+ sliding window)

Each has a jitted wrapper in ``ops.py`` and a pure-jnp oracle in ``ref.py``;
tests sweep shapes/dtypes and assert allclose in interpret mode. The
wrappers resolve the mode from the backend (``ops.resolve_interpret``):
interpreted on CPU, compiled on TPU, where the kernels in
``ops.TPU_REFUSED`` do not compile. The raw kernels take the resolved
mode as a required ``interpret=`` keyword.
See README.md for the step-cost model and dispatch flags.
"""
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.frog_scatter import frog_count
from repro.kernels.frog_step import frog_step
from repro.kernels.spmv_ell import spmv_ell_slab

__all__ = ["ops", "ref", "flash_attention", "frog_count", "frog_step",
           "spmv_ell_slab"]

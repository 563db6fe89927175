"""Pallas TPU kernels for the compute hot spots of the model zoo.

Each kernel ships: <name>.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit-ready public wrapper), ref.py (pure-jnp oracle).  Every entry
point compiles for the TPU by default (``interpret=False``); the CPU tests
pass ``interpret=True`` to run the same kernel bodies in the interpreter.

The paper's own contribution (Cannikin) is a scheduling/estimation layer —
it has no kernel; these cover the substrate it trains (DESIGN.md §6).
"""
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rwkv6_wkv import wkv, wkv_ref
from repro.kernels.ssm_scan import ssm_ref, ssm_scan

__all__ = [
    "flash_attention",
    "attention_ref",
    "wkv",
    "wkv_ref",
    "ssm_scan",
    "ssm_ref",
]

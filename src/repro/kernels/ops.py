"""Jit'd public wrappers for the Pallas kernels.

Every kernel takes ``interpret=None`` by default and resolves it from the
backend: the compiled kernel on TPU, the Pallas interpreter (which runs
the kernel bodies for correctness validation) everywhere else.
"""
from repro.kernels.ca_attention import ca_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_dispatch import grouped_moe_ffn
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.stage_block import stage_mlp_block

__all__ = ["ca_attention", "flash_attention", "grouped_moe_ffn", "ssd_scan",
           "stage_mlp_block"]

"""Pallas TPU kernels for the perf hot-spots the dry-run artifacts expose.

flash_attention — online-softmax attention; removes the dominant HBM
    score traffic of the jnp path (memory-roofline win for train/prefill).
flash_decode — its decode variant: one token's attention over a layer of
    the KV cache stacked over layers, read in place and only up to the
    filled length (the serving decode step's attention).
rmsnorm — fused norm (one HBM round trip).
ssm_scan — chunked diagonal linear recurrence (Mamba/mLSTM core), carried
    through VMEM scratch across the sequential time grid.

Kernels compile for the TPU by default (``interpret=False``). CPU
validation passes ``interpret=True`` and checks them against the ref.py
oracles (tests/test_kernels.py sweeps shapes and dtypes);
tests/test_tpu_compile.py compiles them for a described v5e.
"""
from .flash_attention.ops import flash_mha
from .flash_decode.ops import flash_decode_cache
from .rmsnorm.kernel import fused_rmsnorm
from .ssm_scan.ops import ssm_scan_batched

__all__ = ["flash_decode_cache", "flash_mha", "fused_rmsnorm",
           "ssm_scan_batched"]

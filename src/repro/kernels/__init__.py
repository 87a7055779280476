from repro.kernels.ops import (
    flash_attention,
    flash_decode,
    flash_decode_stacked,
    fused_rmsnorm,
    ssd_chunk_dual,
)

__all__ = ["flash_attention", "flash_decode", "flash_decode_stacked",
           "fused_rmsnorm", "ssd_chunk_dual"]

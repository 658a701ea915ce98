"""Pallas TPU kernels for the compute hot-spots (validated interpret=True).

  coded_combine / coded_admm_update — fused MDS gradient decode (+ eq. 5a
      x-update): the csI-ADMM agent-side hot spot (memory-bound reduce).
  flash_attention — blocked online-softmax attention (causal / sliding
      window / GQA via index maps) for the transformer archs.
  ssd_scan — Mamba-2 chunked state-space-duality scan (mamba2-1.3b).
  rglru_scan — RG-LRU linear recurrence via in-kernel doubling scan
      (recurrentgemma-9b).
  expert_gmm — grouped matmul over the experts a chip holds, its grid
      sized by the rows routed (DeepSeek-V3 expert layers, moonlight).

`ops` are the jitted public entry points; `ref` holds the pure-jnp oracles
the tests sweep against.
"""

from .ops import (
    coded_admm_update,
    coded_combine,
    expert_gmm,
    flash_attention,
    rglru_scan,
    ssd_scan,
)

__all__ = [
    "coded_combine",
    "coded_admm_update",
    "expert_gmm",
    "flash_attention",
    "ssd_scan",
    "rglru_scan",
]

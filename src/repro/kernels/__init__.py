"""Pallas TPU kernels for SPA-Cache hot spots.

The serve-path kernels compile through Mosaic for a TPU v5e at
full-model widths (tests/test_tpu_compile.py) and run in interpret mode
on the CPU against their oracles.

  proxy_score      — fused rank-r proxy projection + cosine drift scores
                     (batch grid axis; ``cosine_drift`` score-only form;
                     ``gather_norm`` fused gather+rms_norm epilogue;
                     paged forms pull identifier pages by DMA)
  sparse_attention — gathered-query flash attention vs full KV cache
                     (batch grid axis; banded stratified path via
                     scalar-prefetched per-q-block kv starts)
  scatter_update   — in-place row commits into cache buffers
                     (``scatter_update_multi``: K/V/H/proxy/scales in one
                     aliased call, every store a DMA; page gather/scatter
                     of the paged pool)
  rglru_scan       — chunked gated linear recurrence (RecurrentGemma)
  ssd_chunk        — Mamba-2 SSD chunked scan (state-space duality)

Each has a pure-jnp oracle in ref.py and a jit wrapper in ops.py.
``backend.py`` packages the serve-path kernels as a ``KernelBackend``
(XlaBackend | PallasBackend) that ``CacheStrategy`` threads through the
decode hot loop (DESIGN.md §4.5).
"""

"""Pallas TPU kernels for the ANN scoring hot path.

  l2_topk       — fused gather-score-topk partition scan (serving hot path)
  dedup_topk    — replica-aware merge: k rounds of best-entry extraction,
                  retiring every copy of the taken id (redundancy dedup,
                  paper §3.3)
  pq_adc        — PQ LUT scan as one-hot MXU contraction (IVFPQ)
  pq_adc_topk   — fused LUT scan + running top-k shortlist (quantized tier
                  stage 1: the [Q, N] ADC tile never leaves VMEM); optional
                  per-candidate/per-query offset operands carry the residual
                  PQ correction terms (core/pq.py residual ADC identity)
  kmeans_assign — fused distance+argmin (index build at 50M+ points)

Each kernel: <name>.py (pl.pallas_call + BlockSpec), oracle in ref.py,
jit'd public wrapper with padding + impl dispatch in ops.py. In-kernel top-k
is built from reductions and selects (_util.top_k_rounds), which Mosaic
lowers; tests/test_tpu_compile.py compiles the serve-path kernels for a
described v5e.
"""

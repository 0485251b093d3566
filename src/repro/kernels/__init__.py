# Pallas TPU kernels.  Dispatch is backend-aware (kernels/compat.py):
# interpret=None in every ops.py wrapper resolves to compiled kernels on
# TPU and interpret/reference mode on every other backend (on CPU the
# interpreter doubles as the test oracle execution).
#   flash_attention — fused attn: causal / sliding-window / softcap / GQA
#   ssd_scan        — Mamba-2 chunked SSD forward
#   topk_compress   — block-local top-k sparsification, per-block (valid, k)
#   quant_transfer  — int8 rowwise quantization of split-point activations

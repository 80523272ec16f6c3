"""ADAPTOR processing modules as Pallas TPU kernels.

Paper module -> kernel map:
  QKV_PM (Alg. 9)            -> qkv_proj
  QK_PM + softmax + SV_PM    -> flash_attention (fused, online softmax)
  paged KV decode            -> paged_attention (block-table gather fused
                                into the flash-decode grid)
  MLA (absorbed, latent)     -> mla_attention (online softmax over each
                                slot's live key blocks)
  FFN1/2/3_PM + bias + act   -> ffn (ffn1 / ffn1_gated) + tiled_matmul
  LN unit (Alg. 8)           -> layernorm (layernorm / rmsnorm)
  Fig. 4 tiling discipline   -> tiled_matmul (K-tiled accumulation)
  fixed-point path (C6)      -> int8_matmul

Each kernel: <name>.py (pl.pallas_call + BlockSpec), a pure-jnp oracle in
ref.py, and a jit'd wrapper in ops.py with planner-chosen block shapes.
Validated with interpret=True on CPU; TPU is the deployment target.
"""

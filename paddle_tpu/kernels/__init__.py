"""Hand-written Pallas TPU kernels for ops where XLA fusion is not enough.

The reference hand-fused its hot paths in CUDA (operators/fused/
multihead_matmul_op.cu, fused_embedding_seq_pool, bert_encoder_functor.cu);
here the same role is played by Pallas/Mosaic kernels. Most ops do NOT need
this — the whole-block jit executor already lets XLA fuse elementwise chains
into matmuls — so kernels live here only when they change the memory-traffic
complexity class (e.g. flash attention: O(S^2) HBM -> O(S)).

On the serving path: `prefill_attention` (a prefill's causal attention,
scores in VMEM), `decode_attention` (a decode step over the stored
caches), `moe_gmm` (grouped expert products and the prefill's row
moves), `ssm_update` (a recurrent state's one-token update in place, the
delta rule's a switch of it) and `gdn_chunk_scan` (a prefill's gated
delta rule chunk by chunk: scores, solve and state walk in VMEM) and
`rotary` (a prefill's rotary positions over q and k where they lie).
"""

from .flash_attention import fused_attention  # noqa: F401

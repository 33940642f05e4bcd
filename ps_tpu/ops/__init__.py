"""Hot ops the default lowering leaves on the table: flash attention
(ops/flash_attention.py, a Pallas TPU kernel) — the fused-softmax
attention that never materializes the [S, S] probability matrix in HBM —
and the fused sparse embedding update (ops/sparse_apply.py, plain JAX) —
gather→optimizer-apply→scatter of only the touched rows, which makes
sparse apply cost batch-sized instead of table-sized (README "Sparse
apply"). Beside them, imported where they are used: the dropless expert
layer (ops/moe.py) and LFM2's double-gated short convolution
(ops/gated_conv.py)."""

from ps_tpu.ops.flash_attention import flash_attention  # noqa: F401
from ps_tpu.ops.sparse_apply import fused_sparse_apply  # noqa: F401

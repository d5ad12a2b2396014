"""Hand-written Pallas TPU kernels for hot ops.

The reference's hand-tuned CUDA lives in paddle/fluid/operators/*.cu and
operators/math/ (fused LSTM cells, depthwise conv, warp softmax). On TPU
XLA fuses most of that automatically; the kernels here cover the cases
where explicit VMEM blocking beats XLA's default schedule:

  - flash_attention: online-softmax attention, O(S) VMEM per query block
    (never materializes the [Sq, Sk] score matrix in HBM)
  - fused layer_norm: one pass over rows, mean/var/normalize/affine fused
  - fused conv+bn+relu: blocked im2col GEMM with the folded-bn affine +
    relu epilogue applied in VMEM (the ResNet-50 inference hot chain)

Each has a jnp reference backward (custom_vjp), and `interpret=True` runs
on CPU for tests. Enable via FLAGS['use_pallas_kernels'] (auto-picked by
emitters when the backend is TPU).

Two more serve the decode engine, forward only, each beside the pure-jax
implementation it is routed against by the same flag:

  - paged_attention: decode attention read through per-sequence page
    tables; its work follows kv_lens and q_lens
  - moe_gmm: the grouped expert products of a sparse-expert layer, walking
    only the (row tile, expert) pairs that hold a live row and streaming
    each touched expert's weights once
"""
from .conv_bn_relu import fold_bn, fused_conv_bn_relu  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .layer_norm import fused_layer_norm  # noqa: F401

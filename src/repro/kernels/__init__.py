# Pallas TPU kernels for the SDE's compute hot spots:
#   onehot_matmul   — CountMin/AMS scatter-add as MXU one-hot matmuls
#   max_scatter     — HLL/Bloom/FM max-scatter (tiled VPU max sweep)
#   rhp_project     — RHP/SimHash sign-row add as an MXU matmul
#   sliding_dft     — batched StatStream sliding-DFT tick
#   pairwise_corr   — blocked Gram/correlation (AggregativeOperation)
#   flash_attention — streaming-softmax attention (prefill memory fix)
# ops.py = jit'd wrappers (Mosaic on TPU, interpret elsewhere); ref.py =
# jnp oracles.
from . import ops, ref  # noqa: F401

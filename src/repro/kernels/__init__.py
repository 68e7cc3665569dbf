# Pallas TPU kernels for the compute hot-spots.  Each kernel directory has:
#   kernel.py - pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
#   ops.py    - jit'd public wrapper (interpret=True off-TPU for validation)
#   ref.py    - pure-jnp oracle the kernel is tested against
#
# The kernels mirror the paper's kernel classes adapted to the LM stack:
#   matmul          - compute-bound  (paper: 64x64 MatMul -> MXU GEMM)
#   bitonic_sort    - cache-bound    (paper: 262KB sort -> in-VMEM bitonic)
#   stream_copy     - bandwidth      (paper: 16.8MB copy -> HBM streaming)
#   flash_attention - the LM-scale perf-critical kernel (VMEM-tiled online
#                     softmax; eliminates the score-tile HBM traffic the
#                     dry-run roofline exposes)
#   ragged_decode   - serving decode attention: one grid step per slot loops
#                     over that slot's live K/V blocks only, read ahead by
#                     manual DMA across slots, and writes the token's K/V in
#                     place — the fleet's hot path

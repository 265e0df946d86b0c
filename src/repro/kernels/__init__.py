"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships three artifacts:
  <name>.py — ``pl.pallas_call`` with explicit BlockSpec VMEM tiling (TPU
              target; every kernel takes ``interpret`` explicitly),
  ops.py    — jit'd public wrappers; they run the kernels compiled on an
              accelerator and in the Pallas interpreter on the CPU,
  ref.py    — pure-jnp oracles the tests assert against.

Kernels: flash_attention (GQA / causal / sliding-window), rglru (RG-LRU
chunked recurrence), rwkv6 (WKV-6 chunked recurrence), bucket_pack
(tensor-fusion gradient packing — the paper's fused-AllReduce staging copy),
fused_grad_sync (in-kernel compute+comm overlap: reduce-scatter-ready
chunked pack + all-gather unpack/cast halves around the wire collective).
"""

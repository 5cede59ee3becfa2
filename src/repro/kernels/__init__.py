"""Pallas TPU kernels for the framework's compute hot spots.

  ttl_scan         -- batched ExpectedCost-over-TTL scan (the paper's §3.2.2
                      control-plane hot spot; VPU prefix-sum workload)
  flash_attention  -- streaming fused attention for prefill/serving (MXU)
  ref              -- pure-jnp oracles for both
  ops              -- wrappers (compiled; interpret=True only when asked)
"""

from .ops import flash_attention, rwkv6_scan, ttl_scan, ttl_scan_from_histograms  # noqa: F401

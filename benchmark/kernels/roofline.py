"""Roofline arithmetic shared by the kernel readers."""
from __future__ import annotations


def least_seconds(ops, nbytes, peak):
    """(least time the chip could take, which bound sets it)."""
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")

"""What the algorithm needs, whatever implements it.

Reed-Solomon over a batch reads the k rows it computes from and writes
the rows it computes: m parity rows for an encode, the lost rows for a
rebuild. That is (k+out)/k bytes of HBM traffic per byte read, and
nothing else is needed. In its bit-matrix form (the one the kernels
use) an output byte is 8 bits, each a sum over the 8k input bits:
2 * 8k * 8 * out / k = 128 * out operations per byte read on the int8
unit. Bytes, not operations, bound it on a v5e: 1 GiB of 10+4 is
1.503e9 bytes, 1.84 ms at 819 GB/s, against 1.40 ms of int8 operations
at 393 TOP/s (ROADMAP Speed 4).
"""

from __future__ import annotations


def rs_bytes(in_bytes: float, k: int, out_rows: int) -> float:
    return in_bytes * (k + out_rows) / k


def rs_ops(in_bytes: float, k: int, out_rows: int) -> float:
    return in_bytes * 2 * 8 * 8 * out_rows


def least_seconds(in_bytes: float, k: int, out_rows: int, peaks: dict) -> tuple[float, str]:
    """(least time the chip could take to turn `in_bytes` in k rows into
    `out_rows` rows, which peak bounds it)."""
    by_bytes = rs_bytes(in_bytes, k, out_rows) / float(peaks["hbm_bytes_per_s"])
    by_ops = rs_ops(in_bytes, k, out_rows) / float(peaks["int8_ops_per_s"])
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "operations")

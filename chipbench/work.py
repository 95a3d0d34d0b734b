"""What the codec has to move for one stripe block, and the chip's peaks.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. The work is a function of the geometry alone.
"""

from __future__ import annotations

BLOCK = 1 << 20
DIGEST = 32

# Published peaks per chip, keyed by `device_kind` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth,
# 393 TOP/s int8, 197 TFLOP/s bf16. A device that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def shard_len(d: int) -> int:
    return -(-BLOCK // d)


def encode_bytes_per_block(d: int, p: int) -> int:
    """HBM bytes the encode + bitrot of one stripe block cannot avoid:
    d data shards in, p parity shards out, d+p digests out.
    8+8: 8*131072 + 8*131072 + 16*32 = 2,097,664.
    12+4: 12*87382 + 4*87382 + 16*32 = 1,398,624."""
    n = shard_len(d)
    return d * n + p * n + (d + p) * DIGEST


def encode_ops_per_block(d: int, p: int) -> int:
    """Bit-plane GF(2) matmul operation count, 2 * 8p * 8d * n: the other
    side of the roofline, reported in PERF.md, not yet a metric."""
    return 2 * (8 * p) * (8 * d) * shard_len(d)

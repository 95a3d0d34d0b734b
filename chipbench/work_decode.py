"""What a device reconstruct has to move for one stripe block: the decode
side of `chipbench/work.py`, kept with the benchmark for the same reason
(no PR that claims a gain can change the yardstick). A function of the
geometry and of how many shards the dispatch rebuilt, nothing else: the
zero blocks that pad a batch, and the survivors' digests that the kernel
computes and the read path drops, are waste, not work.

What it receives: d, the data shards of the set, and m, the shards a
dispatch rebuilt (the `missing` label of
`minio_tpu_decode_device_blocks_total`). The peaks are `work.peaks`."""

from __future__ import annotations

from chipbench.work import DIGEST, shard_len


def decode_bytes_per_block(d: int, m: int) -> int:
    """HBM bytes the rebuild + bitrot of one stripe block cannot avoid: d
    surviving shards in, m rebuilt shards out, d+m digests out.
    8 data shards, 1 rebuilt: 8*131072 + 131072 + 9*32 = 1,179,936.
    2 rebuilt: 1,311,040. 8 rebuilt: 2,097,664 (an encode's bytes)."""
    n = shard_len(d)
    return d * n + m * n + (d + m) * DIGEST


def decode_ops_per_block(d: int, m: int) -> int:
    """Bit-plane GF(2) matmul operation count, 2 * 8m * 8d * n: the other
    side of the roofline, reported in PERF.md, not a metric."""
    return 2 * (8 * m) * (8 * d) * shard_len(d)

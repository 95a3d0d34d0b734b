"""The encode result contract since PR 26: the dispatcher hands a waiter
(parity [k, p, n], digests) and copies no data byte back; the coder frames
data rows from the array it submitted. Counts and bytes, never times: the
shard files a streaming PUT writes are those of `encode_blocks_numpy`, data
rows on the arena plane ARE the ingest arena, `dispatch/frame` never runs,
and parity rows reach the drives C-contiguous whatever layout D2H gave."""

import io
import os

import numpy as np
import pytest

from minio_tpu import obs
from minio_tpu.erasure.coder import BLOCK_SIZE, ErasureCoder, encode_blocks_numpy
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.parallel import dispatcher as dmod
from minio_tpu.storage.xlstorage import XLStorage

RNG = np.random.default_rng(26)
BLOCKS = 4  # whole stripe blocks: the tail block never meets the dispatcher


def _gen(data, step=700_001):
    for i in range(0, len(data), step):
        yield data[i : i + step]


def _frame_calls() -> int:
    return obs.phases_snapshot()[("dispatch", "frame")][2]


def _reference_files(coder: ErasureCoder, data: bytes) -> list[bytes]:
    """Shard files of `data` (whole blocks) from the numpy codec alone."""
    d, per = coder.d, coder.shard_size
    flat = np.frombuffer(data, dtype=np.uint8)
    blocks = np.zeros((len(data) // BLOCK_SIZE, d * per), dtype=np.uint8)
    blocks[:, :BLOCK_SIZE] = flat.reshape(-1, BLOCK_SIZE)
    shards, digests = encode_blocks_numpy(
        coder._np, blocks.reshape(-1, d, per), coder.family
    )
    h1 = per // 2
    files = []
    for i in range(coder.t):
        out = bytearray()
        for b in range(shards.shape[0]):
            if coder.family == "cauchy":
                out += digests[b, i, 0].tobytes() + shards[b, i, :h1].tobytes()
                out += digests[b, i, 1].tobytes() + shards[b, i, h1:].tobytes()
            else:
                out += digests[b, i].tobytes() + shards[b, i].tobytes()
        files.append(bytes(out))
    return files


def _ondrive_files(tmp_path, tag: str, drives: int) -> list[bytes]:
    """part.1 of zc/obj from every drive, in erasure-index order."""
    files: list[bytes | None] = [None] * drives
    for i in range(drives):
        root = str(tmp_path / f"{tag}{i}")
        fi = XLStorage(root).read_version("zc", "obj")
        with open(os.path.join(root, "zc", "obj", fi.data_dir, "part.1"), "rb") as f:
            files[fi.erasure.index - 1] = f.read()
    return files


# (d, p, family, the plane iter_encode_zc takes, forced onto the numpy rung).
# "copy" is the legacy plane MINIO_TPU_ZEROCOPY=0 selects; 12+4 takes the
# arena with each block's 8-byte shard pad. 4+3 is nobody else's geometry:
# its process-wide dispatcher is this file's to demote and put back.
CASES = {
    "8+8-arena": (8, 8, "reedsolomon", "arena", False),
    "12+4-arena": (12, 4, "reedsolomon", "arena", False),
    "12+4-copy": (12, 4, "reedsolomon", "copy", False),
    "cauchy-4+2": (4, 2, "cauchy", "arena", False),
    "numpy-rung-4+3": (4, 3, "reedsolomon", "arena", True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_streaming_put_frames_data_rows_from_the_submitted_array(
    tmp_path, monkeypatch, case
):
    d, p, family, plane, numpy_rung = CASES[case]
    monkeypatch.setenv("MINIO_TPU_BACKEND", "jax")
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", "0" if plane == "copy" else "1")
    monkeypatch.setenv("MINIO_TPU_STREAM_BATCH_MB", "2")
    monkeypatch.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    coder = ErasureCoder(d, p, family=family)
    disp = dmod.get_dispatcher(coder._jax, coder.shard_size)
    if numpy_rung:
        with disp._cv:
            disp.stats["backend_level"] = dmod.LEVEL_NUMPY
        probe = (disp._probe_after, disp._probe_countdown)
        disp._probe_after = disp._probe_countdown = 10**9  # stay demoted
    try:
        data = RNG.integers(0, 256, size=BLOCKS * BLOCK_SIZE, dtype=np.uint8).tobytes()
        want = _reference_files(coder, data)
        frames0, st0 = _frame_calls(), disp.stats_snapshot()

        # the coder's batches, as the streaming PUT consumes them
        files = [bytearray() for _ in range(coder.t)]
        per_frame = 4 if family == "cauchy" else 2  # pieces per (block, shard)
        pad = d * coder.shard_size - BLOCK_SIZE  # 8 at 12+4, else 0
        for batch in coder.iter_encode_zc(_gen(data), 2 << 20):
            arena = [np.frombuffer(r, dtype=np.uint8) for r in batch.raw]
            for i in range(coder.t):
                vec = batch.shard_vecs[i]
                for piece in vec:
                    files[i] += piece
                if plane != "arena":
                    continue
                assert len(vec) % per_frame == 0
                rows = [np.frombuffer(v, dtype=np.uint8)
                        for j, v in enumerate(vec) if j % 2 == 1]
                # a data row IS the ingest arena; a parity row never is
                assert all(
                    any(np.shares_memory(r, a) for a in arena) == (i < d) for r in rows
                ), i
            if plane == "arena":
                # and together a block's data rows are its input, then its
                # zero pad, byte for byte
                assert all(len(r) == BLOCK_SIZE for r in batch.raw)
                for b, r in enumerate(batch.raw):
                    pieces = [
                        batch.shard_vecs[i][b * per_frame + 1 + 2 * h]
                        for i in range(d) for h in range(per_frame // 2)
                    ]
                    assert b"".join(bytes(x) for x in pieces) == bytes(r) + bytes(pad)
            batch.release()
        assert [bytes(f) for f in files] == want

        # the same object through a real streaming PUT, read off the drives
        tag = case.replace("+", "p") + "-"
        es = ErasureSet([XLStorage(str(tmp_path / f"{tag}{i}")) for i in range(d + p)])
        es.make_bucket("zc")
        es.put_object("zc", "obj", _gen(data), parity=p, family=family)
        assert _ondrive_files(tmp_path, tag, d + p) == want

        st1 = disp.stats_snapshot()
        assert st1["blocks"] - st0["blocks"] == 2 * BLOCKS  # both went through it
        served_numpy = st1["numpy_blocks"] - st0["numpy_blocks"]
        assert served_numpy == (2 * BLOCKS if numpy_rung else 0)
        assert _frame_calls() == frames0  # the concatenate is gone, on every rung
    finally:
        if numpy_rung:
            disp._probe_after, disp._probe_countdown = probe
            with disp._cv:
                disp.stats["backend_level"] = dmod.LEVEL_FUSED


def _fortran(parity):
    # same values, column-major strides: no row is contiguous
    return np.asfortranarray(parity)


def _row_padded(parity):
    # rows contiguous, the array not: what a tiled layout with padded rows gives
    wide = np.zeros(parity.shape[:2] + (parity.shape[2] + 128,), dtype=np.uint8)
    wide[:, :, : parity.shape[2]] = parity
    return wide[:, :, : parity.shape[2]]


@pytest.mark.parametrize("layout", [_fortran, _row_padded], ids=["fortran", "row-padded"])
def test_parity_rows_from_a_foreign_d2h_layout_reach_the_drives(layout):
    """PR 21's 500, pinned on the CPU: np.asarray of a TPU array can come
    back in the device's own layout. Until PR 26 the data+parity concatenate
    made parity rows row-major by accident; now the `unpack` phase does it,
    and only where a row is strided."""
    from minio_tpu.ops import rs_jax

    d, p, n, k = 4, 2, 1024, 3
    disp = dmod.TpuDispatcher(rs_jax.get_tpu_codec(d, p), n, window_s=0.0)
    real = disp._encode_and_hash
    handed = []

    def foreign(codec, blocks):
        parity, digests = real(codec, blocks)
        handed.append(layout(np.asarray(parity)))
        return handed[-1], np.asfortranarray(np.asarray(digests))

    disp._encode_and_hash = foreign
    blocks = RNG.integers(0, 256, size=(k, d, n), dtype=np.uint8)
    frames0 = _frame_calls()
    parity, digests = disp.encode(blocks)
    assert disp.stats["numpy_blocks"] == 0  # the XLA rung served it
    assert parity.shape == (k, p, n) and _frame_calls() == frames0
    assert all(parity[b, j].flags.c_contiguous for b in range(k) for j in range(p))
    # copied only where a row was strided
    assert np.shares_memory(parity, handed[0]) == (layout is _row_padded)

    coder = ErasureCoder(d, p)
    want, want_digests = encode_blocks_numpy(coder._np, blocks)
    vecs: list[list] = [[] for _ in range(d + p)]
    coder._frame_into(vecs, blocks, parity, digests)
    for i in range(d + p):
        sink = io.BytesIO()
        sink.writelines(vecs[i])  # refuses a buffer that is not C-contiguous
        assert sink.getvalue() == b"".join(
            want_digests[b, i].tobytes() + want[b, i].tobytes() for b in range(k)
        ), i

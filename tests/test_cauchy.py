"""Second codec family ("cauchy": Cauchy MDS + piggybacked sub-chunks)
— the cross-family matrix the ISSUE-14 tentpole requires:

- encode/decode byte-identity numpy vs XLA vs Pallas-interpret per family
- xl.meta `algorithm` round-trip and per-storage-class selection
- mixed-family objects on ONE erasure set (listing, GET, heal)
- old reedsolomon objects untouched after the default family flips
- unknown-family xl.meta rejected with the typed UnknownErasureFamily
- sub-chunk partial repair: schedule math, heal/degraded ingress savings,
  bitrot detection at sub-chunk granularity, MINIO_TPU_EC_REPAIR=0 off
  switch
"""

import os

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")

import shutil

import numpy as np
import pytest

from minio_tpu.erasure import bitrot_io
from minio_tpu.erasure.coder import (
    ErasureCoder,
    default_ec_family,
    family_stats_snapshot,
)
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.ops import cauchy, rs
from minio_tpu.storage import errors
from minio_tpu.storage.xlstorage import XLStorage

pytestmark = []


def _rig(tmp_path, tag, n=16, parity=8):
    es = ErasureSet(
        [XLStorage(str(tmp_path / tag / f"d{i}")) for i in range(n)],
        default_parity=parity,
    )
    es.make_bucket("fam")
    return es


def _drain(it) -> bytes:
    return b"".join(bytes(c) for c in it)


# ---------------------------------------------------------------------------
# codec-level matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d,p", [(4, 4), (8, 8), (6, 2), (2, 2)])
def test_cauchy_mds_any_survivor_subset(d, p):
    """[I; C] is MDS: every d-subset of shards decodes the data (small
    shapes exhaustively, big shapes sampled)."""
    import itertools
    import random

    c = cauchy.get_codec(d, p)
    data = np.random.default_rng(d * 31 + p).integers(
        0, 256, size=d * 97 - 5, dtype=np.uint8
    ).tobytes()
    shards = c.encode_data(data)
    subsets = list(itertools.combinations(range(d + p), d))
    if len(subsets) > 60:
        subsets = random.Random(7).sample(subsets, 60)
    for keep in subsets:
        sl = [shards[i] if i in keep else None for i in range(d + p)]
        rec = c.reconstruct(sl)
        for i in range(d + p):
            assert np.array_equal(rec[i], shards[i]), (keep, i)
    assert c.join(list(shards), len(data)) == data


@pytest.mark.parametrize("d,p", [(4, 4), (8, 8)])
def test_cauchy_encode_identity_numpy_xla_pallas(d, p):
    """The three cauchy encode backends agree bit-for-bit (same contract
    the rs family pins in test_rs_jax/test_pallas)."""
    rng = np.random.default_rng(1)
    per = 512
    blocks = rng.integers(0, 256, size=(4, d, per), dtype=np.uint8)
    ref = cauchy.get_codec(d, p)
    want = np.zeros((4, d + p, per), dtype=np.uint8)
    for i in range(4):
        want[i, :d] = blocks[i]
        ref.encode(want[i])
    xla = np.asarray(cauchy.get_tpu_codec(d, p).encode_blocks(blocks))
    assert np.array_equal(xla, want[:, d:])
    pls = np.asarray(cauchy.encode_blocks_pallas(ref, blocks, interpret=True))
    assert np.array_equal(pls, want[:, d:])
    # fused-style dispatch: parity + per-sub-chunk digests
    par, digs = cauchy.encode_and_hash_cauchy(
        cauchy.get_tpu_codec(d, p), blocks
    )
    assert np.array_equal(np.asarray(par), want[:, d:])
    from minio_tpu.ops.highwayhash import hash256_batch_numpy

    h = per // 2
    sub = want.reshape(4 * (d + p) * 2, h)
    assert np.array_equal(
        np.asarray(digs), hash256_batch_numpy(sub).reshape(4, d + p, 2, 32)
    )


def test_rs_decode_identity_numpy_xla():
    """rs decode parity check rides along: numpy reconstruct and the XLA
    bit-plane reconstruct agree on a degraded window."""
    from minio_tpu.ops import rs_jax

    d, p = 4, 4
    rng = np.random.default_rng(5)
    blocks = rng.integers(0, 256, size=(3, d, 256), dtype=np.uint8)
    ref = rs.get_codec(d, p)
    full = np.zeros((3, d + p, 256), dtype=np.uint8)
    for i in range(3):
        full[i, :d] = blocks[i]
        ref.encode(full[i])
    present, missing = (1, 2, 3, 4), (0,)
    surv = full[:, list(present[:d]), :]
    xla = np.asarray(
        rs_jax.get_tpu_codec(d, p).reconstruct_blocks(surv, present, missing)
    )
    assert np.array_equal(xla[:, 0, :], full[:, 0, :])


def test_cauchy_decode_flat_matches_listwise():
    d, p = 8, 8
    c = cauchy.get_codec(d, p)
    rng = np.random.default_rng(11)
    per = 130
    w = 5
    full = np.zeros((w, d + p, per), dtype=np.uint8)
    for i in range(w):
        full[i, :d] = rng.integers(0, 256, size=(d, per), dtype=np.uint8)
        c.encode(full[i])
    present = (1, 2, 3, 5, 6, 7, 8, 12)
    missing = (0, 4, 9)
    surv = np.stack([full[:, i, :] for i in present])
    out = c.reconstruct_flat(surv, present, missing)
    for mi, i in enumerate(missing):
        assert np.array_equal(out[mi], full[:, i, :]), i


def test_repair_schedule_reads_fraction():
    """The schedule's byte plan sits >= 25% under MDS repair at EC 8+8
    (the ISSUE acceptance bound) for EVERY lost data shard."""
    c = cauchy.get_codec(8, 8)
    shard = 128 * 1024
    mds = 8 * (bitrot_io.DIGEST_SIZE + shard)
    for i in range(8):
        sched = c.repair_schedule(i)
        assert sched is not None
        assert sched.reads(shard) <= 0.75 * mds, (i, sched.reads(shard))


def test_repair_schedule_exact():
    """Executing the schedule rebuilds the lost shard byte-identically,
    for every data shard and odd/even shard sizes."""
    for d, p in ((8, 8), (4, 4), (5, 2)):
        c = cauchy.get_codec(d, p)
        rng = np.random.default_rng(d)
        for per in (64, 33):
            full = np.zeros((d + p, per), dtype=np.uint8)
            full[:d] = rng.integers(0, 256, size=(d, per), dtype=np.uint8)
            c.encode(full)
            h1, _ = cauchy.sub_lens(per)
            for i in range(d):
                sched = c.repair_schedule(i)
                got = c.repair_data_shard(
                    sched, per,
                    {r: full[r][h1:] for r in sched.b_helpers},
                    full[sched.pb_parity][h1:],
                    {r: full[r][:h1] for r in sched.mates},
                )
                assert np.array_equal(got, full[i]), (d, p, per, i)


def test_xor_schedule_cheaper_than_vandermonde():
    """The greedy-rescaled Cauchy matrix costs fewer bit-plane XOR gates
    than the rs Vandermonde parity matrix (arXiv:1611.09968's metric)."""
    for d, p in ((8, 8), (4, 4)):
        ca = cauchy.xor_gates(cauchy.get_codec(d, p).parity_matrix)
        vd = cauchy.xor_gates(rs.get_codec(d, p).parity_matrix)
        assert ca < vd, (d, p, ca, vd)


def test_sub_chunk_frames_and_verify():
    blk = os.urandom(101)
    framed = bitrot_io.frame_block(blk, "cauchy")
    h1, h2 = bitrot_io.sub_lens(101)
    assert len(framed) == 101 + 2 * bitrot_io.DIGEST_SIZE
    assert bitrot_io.verify_block(framed, 101, family="cauchy") == blk
    # sub-chunk spans address the two frames independently
    off1, dl1, n1 = bitrot_io.sub_chunk_span(101, 0, 0)
    off2, dl2, n2 = bitrot_io.sub_chunk_span(101, 0, 1)
    assert (n1, n2) == (h1, h2)
    assert bitrot_io.verify_sub_chunk(framed[off1:off1 + dl1], n1) == blk[:h1]
    assert bitrot_io.verify_sub_chunk(framed[off2:off2 + dl2], n2) == blk[h1:]
    # a flipped byte in sub-chunk 2 is caught by ITS digest
    bad = bytearray(framed)
    bad[-1] ^= 1
    with pytest.raises(errors.FileCorrupt):
        bitrot_io.verify_sub_chunk(bytes(bad)[off2:off2 + dl2], n2)
    # rs framing unchanged
    assert bitrot_io.frame_block(blk, "reedsolomon")[32:] == blk


def test_unknown_family_typed_error():
    with pytest.raises(errors.UnknownErasureFamily):
        bitrot_io.check_family("zfec")
    with pytest.raises(errors.UnknownErasureFamily):
        ErasureCoder(4, 4, family="lrc")
    with pytest.raises(errors.UnknownErasureFamily):
        bitrot_io.frames_per_block("not-a-family")


# ---------------------------------------------------------------------------
# erasure-set wiring
# ---------------------------------------------------------------------------


def test_xlmeta_algorithm_roundtrip(tmp_path, monkeypatch):
    """algorithm lands in xl.meta, survives serialization, and GETs
    dispatch on it."""
    from minio_tpu.storage.datatypes import ErasureInfo

    ei = ErasureInfo(algorithm="cauchy", data_blocks=8, parity_blocks=8)
    assert ErasureInfo.from_dict(ei.to_dict()).algorithm == "cauchy"
    # absent key defaults to reedsolomon (pre-family xl.meta)
    legacy = ei.to_dict()
    del legacy["algo"]
    assert ErasureInfo.from_dict(legacy).algorithm == "reedsolomon"

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    assert default_ec_family() == "cauchy"
    es = _rig(tmp_path, "round", n=8, parity=4)
    body = os.urandom(300_000)
    es.put_object("fam", "o", body)
    fi, _ = es._cached_fileinfo("fam", "o", "")
    assert fi.erasure.algorithm == "cauchy"
    _, it = es.get_object("fam", "o")
    assert _drain(it) == body
    # malformed knob value falls back to reedsolomon on NEW writes
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "definitely-not-a-codec")
    assert default_ec_family() == "reedsolomon"


def test_mixed_families_one_set_and_default_flip(tmp_path, monkeypatch):
    """Objects of both families coexist on the same drives; flipping the
    default family leaves OLD objects' bytes, etag, stored algorithm,
    GET, and heal untouched."""
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "reedsolomon")
    es = _rig(tmp_path, "mixed", n=8, parity=4)
    old_body = os.urandom(2_500_000)
    old_oi = es.put_object("fam", "old-rs", old_body)

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    new_body = os.urandom(2_500_000)
    es.put_object("fam", "new-cauchy", new_body)

    fi_old, _ = es._cached_fileinfo("fam", "old-rs", "")
    fi_new, _ = es._cached_fileinfo("fam", "new-cauchy", "")
    assert fi_old.erasure.algorithm == "reedsolomon"
    assert fi_new.erasure.algorithm == "cauchy"

    # listing sees both
    keys = {k for k in es.walk_objects("fam")}
    assert {"old-rs", "new-cauchy"} <= keys

    # old object unchanged after the flip
    _, it = es.get_object("fam", "old-rs")
    assert _drain(it) == old_body
    oi2 = es.get_object_info("fam", "old-rs")
    assert oi2.etag == old_oi.etag

    # drive loss hits BOTH objects; each heals under its own family
    shutil.rmtree(tmp_path / "mixed" / "d2" / "fam" / "old-rs")
    shutil.rmtree(tmp_path / "mixed" / "d2" / "fam" / "new-cauchy")
    es.cache.clear()
    r1 = es.heal_object("fam", "old-rs")
    r2 = es.heal_object("fam", "new-cauchy")
    assert r1["healed"] and r1["family"] == "reedsolomon"
    assert r2["healed"] and r2["family"] == "cauchy"
    es.cache.clear()
    _, it = es.get_object("fam", "old-rs")
    assert _drain(it) == old_body
    _, it = es.get_object("fam", "new-cauchy")
    assert _drain(it) == new_body
    # healed shards re-verify under their family's framing
    for key in ("old-rs", "new-cauchy"):
        fi, metas, _, _ = es._quorum_fileinfo("fam", key, "", read_data=True)
        for dk, m in zip(es.disks, metas):
            if m is not None:
                dk.verify_file("fam", key, m)


def test_unknown_family_object_rejected(tmp_path, monkeypatch):
    """An xl.meta naming an unregistered family fails GET and heal with
    the typed error (never a frame misread)."""
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "reedsolomon")
    es = _rig(tmp_path, "unk", n=8, parity=4)
    es.put_object("fam", "o", os.urandom(200_000))
    metas, _ = es._read_all_fileinfo("fam", "o", "", read_data=True)
    for disk, m in zip(es.disks, metas):
        if m is not None:
            m.erasure.algorithm = "future-codec"
            disk.write_metadata("fam", "o", m)
    es.cache.clear()
    with pytest.raises(errors.UnknownErasureFamily):
        _, it = es.get_object("fam", "o")
        _drain(it)
    with pytest.raises(errors.UnknownErasureFamily):
        es.heal_object("fam", "o")


def test_heal_partial_repair_ingress(tmp_path, monkeypatch):
    """Single-drive heal at EC 8+8: the cauchy family reads >= 25% fewer
    survivor bytes than reedsolomon (the BENCH_r09 acceptance bound) and
    rebuilds byte-identically; MINIO_TPU_EC_REPAIR=0 disables the
    shortcut but not the heal."""
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    # the bound is the PLAN's: no hedge, so a slow host cannot add the
    # fallback's full frames to the cauchy side
    monkeypatch.setenv("MINIO_TPU_HEDGE", "0")
    ingress = {}
    body = os.urandom(3 << 20)
    for fam in ("reedsolomon", "cauchy"):
        monkeypatch.setenv("MINIO_TPU_EC_FAMILY", fam)
        es = _rig(tmp_path, fam)
        es.put_object("fam", "o", body)
        fi, _ = es._cached_fileinfo("fam", "o", "")
        lost = fi.erasure.distribution.index(1)  # data shard 0's drive
        shutil.rmtree(tmp_path / fam / f"d{lost}" / "fam" / "o")
        es.cache.clear()
        res = es.heal_object("fam", "o")
        assert res["healed"], res
        assert res["partialRepair"] == (fam == "cauchy")
        ingress[fam] = res["ingressBytes"]
        es.cache.clear()
        _, it = es.get_object("fam", "o")
        assert _drain(it) == body
    assert ingress["cauchy"] <= 0.75 * ingress["reedsolomon"], ingress

    # off switch: full-read heal, still correct
    monkeypatch.setenv("MINIO_TPU_EC_REPAIR", "0")
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    es = _rig(tmp_path, "repair-off")
    es.put_object("fam", "o", body)
    fi, _ = es._cached_fileinfo("fam", "o", "")
    lost = fi.erasure.distribution.index(1)
    shutil.rmtree(tmp_path / "repair-off" / f"d{lost}" / "fam" / "o")
    es.cache.clear()
    res = es.heal_object("fam", "o")
    assert res["healed"] and not res["partialRepair"]
    assert res["ingressBytes"] >= ingress["reedsolomon"] * 0.9
    es.cache.clear()
    _, it = es.get_object("fam", "o")
    assert _drain(it) == body


def test_degraded_ranged_get_partial_reads(tmp_path, monkeypatch):
    """Degraded ranged GET under one lost data drive: cauchy serves the
    range byte-identically while fetching measurably fewer survivor
    bytes than reedsolomon (the repair plan reads sub-chunk frames)."""
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    body = os.urandom(4 << 20)
    spent = {}
    for fam in ("reedsolomon", "cauchy"):
        monkeypatch.setenv("MINIO_TPU_EC_FAMILY", fam)
        es = _rig(tmp_path, f"dg-{fam}")
        es.put_object("fam", "o", body)
        fi, _ = es._cached_fileinfo("fam", "o", "")
        lost = fi.erasure.distribution.index(1)
        shutil.rmtree(tmp_path / f"dg-{fam}" / f"d{lost}" / "fam" / "o")
        es.cache.clear()
        before = family_stats_snapshot()[fam]["degraded_ingress_bytes"]
        # ranges inside the LOST shard's span of the first stripe block
        _, h = es.open_object("fam", "o")
        got = _drain(h.read(4096, 65536))
        assert got == body[4096 : 4096 + 65536]
        # and a full-object degraded read stays byte-identical
        _, it = es.get_object("fam", "o")
        assert _drain(it) == body
        spent[fam] = family_stats_snapshot()[fam]["degraded_ingress_bytes"] - before
    assert spent["cauchy"] < spent["reedsolomon"], spent


def test_streaming_put_cauchy_roundtrip(tmp_path, monkeypatch):
    """Chunk-iterator PUT (the streaming path) under the cauchy family:
    frames append per batch, bytes round-trip, shards verify."""
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    es = _rig(tmp_path, "stream", n=8, parity=4)
    body = os.urandom((3 << 20) + 54321)

    def chunks():
        mv = memoryview(body)
        for o in range(0, len(body), 700_001):
            yield bytes(mv[o : o + 700_001])

    oi = es.put_object("fam", "s", chunks())
    assert oi.size == len(body)
    fi, metas, _, _ = es._quorum_fileinfo("fam", "s", "", read_data=True)
    assert fi.erasure.algorithm == "cauchy"
    _, it = es.get_object("fam", "s")
    assert _drain(it) == body
    for dk, m in zip(es.disks, metas):
        if m is not None:
            dk.verify_file("fam", "s", m)


def test_multipart_family_pins_at_initiation(tmp_path, monkeypatch):
    """Multipart uploads pin the family at initiation; the completed
    object records it and serves byte-identically even when the default
    flips mid-upload."""
    from minio_tpu.erasure.multipart import MultipartManager

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    es = _rig(tmp_path, "mp", n=8, parity=4)
    mp = MultipartManager(es)
    up = mp.new_upload("fam", "big", {})
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "reedsolomon")  # flip mid-upload
    p1 = os.urandom(5 << 20)
    p2 = os.urandom(1 << 20)
    e1 = mp.put_part("fam", "big", up, 1, p1)
    e2 = mp.put_part("fam", "big", up, 2, p2)
    mp.complete("fam", "big", up, [(1, e1), (2, e2)])
    fi, _ = es._cached_fileinfo("fam", "big", "")
    assert fi.erasure.algorithm == "cauchy"
    _, it = es.get_object("fam", "big")
    assert _drain(it) == p1 + p2


def test_inline_object_cauchy(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")
    es = _rig(tmp_path, "inline", n=8, parity=4)
    body = b"small inline payload " * 40
    es.put_object("fam", "tiny", body)
    fi, _ = es._cached_fileinfo("fam", "tiny", "")
    assert fi.erasure.algorithm == "cauchy"
    _, it = es.get_object("fam", "tiny")
    assert _drain(it) == body
    # heal path verifies inline frames under the family's framing
    res = es.heal_object("fam", "tiny")
    assert res["type"] == "object"


def test_storage_class_family_mapping_via_s3(tmp_path, monkeypatch):
    """x-amz-storage-class maps to a family through the live S3 server:
    REDUCED_REDUNDANCY writes cauchy (MINIO_TPU_EC_FAMILY_RRS), default
    class stays on the node default."""
    from minio_tpu.client import S3Client

    from tests.test_s3_api import ServerThread

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "reedsolomon")
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY_RRS", "cauchy")
    drives = [str(tmp_path / "s3" / f"d{i}") for i in range(4)]
    st = ServerThread(drives)
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        assert cli.make_bucket("fam-bkt").status == 200
        body = os.urandom(400_000)
        r = cli.put_object(
            "fam-bkt", "rrs-obj", body,
            headers={"x-amz-storage-class": "REDUCED_REDUNDANCY"},
        )
        assert r.status == 200
        r = cli.put_object("fam-bkt", "std-obj", body)
        assert r.status == 200
        g = cli.get_object("fam-bkt", "rrs-obj")
        assert g.status == 200 and g.body == body
        fi_rrs = XLStorage(drives[0]).read_version("fam-bkt", "rrs-obj", "")
        fi_std = XLStorage(drives[0]).read_version("fam-bkt", "std-obj", "")
        assert fi_rrs.erasure.algorithm == "cauchy"
        assert fi_std.erasure.algorithm == "reedsolomon"
    finally:
        st.stop()


def test_family_metrics_series(tmp_path, monkeypatch):
    """/api/tpu exposes the per-family series, including
    minio_heal_ingress_bytes_total."""
    from minio_tpu.server import metrics as m

    class _Srv:
        store = None

    out = "\n".join(m._g_api_tpu(_Srv()))
    for series in (
        'minio_tpu_encode_blocks_total{family="cauchy"}',
        'minio_tpu_decode_blocks_total{family="reedsolomon"}',
        'minio_heal_ingress_bytes_total{family="cauchy"}',
        'minio_tpu_degraded_ingress_bytes_total{family="reedsolomon"}',
        'minio_tpu_repair_partial_blocks_total{family="cauchy"}',
    ):
        assert series in out, series


def test_obs_records_carry_family(monkeypatch):
    """tpu-type obs records gain a `family` field: the dispatcher's
    dispatch.batch record tags which code family the group served."""
    from minio_tpu import obs
    from minio_tpu.ops import cauchy as cauchy_ops
    from minio_tpu.parallel.dispatcher import get_dispatcher
    from minio_tpu.server.metrics import TracePubSub

    monkeypatch.setenv("MINIO_TPU_BACKEND", "numpy")
    prev = obs.publisher()
    pub = TracePubSub()
    obs.set_publisher(pub)
    sub = pub.subscribe()
    try:
        codec = cauchy_ops.get_tpu_codec(4, 2)
        disp = get_dispatcher(codec, 128)
        blocks = np.random.default_rng(3).integers(
            0, 256, size=(2, 4, 128), dtype=np.uint8
        )
        parity, digests = disp.encode(blocks, codec=codec)
        assert parity.shape == (2, 2, 128)
        assert digests.shape == (2, 6, 2, 32)
        import time as _time

        deadline = _time.monotonic() + 5.0
        fams = []
        while _time.monotonic() < deadline:
            rec = sub.q.get(timeout=5.0)
            if rec.get("name") == "dispatch.batch":
                fams.append(rec.get("family"))
                break
        assert fams == ["cauchy"], fams
    finally:
        pub.unsubscribe(sub)
        obs.set_publisher(prev)


def test_multipart_legacy_upload_defaults_to_rs(tmp_path, monkeypatch):
    """An upload whose metadata predates the __family pin (no __family
    key) can only have reedsolomon-framed parts — later parts must stay
    reedsolomon even if the node default flipped to cauchy, or one
    object would mix shard formats."""
    from minio_tpu.erasure.multipart import MP_VOLUME, MultipartManager

    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "reedsolomon")
    es = _rig(tmp_path, "mp-legacy", n=8, parity=4)
    mp = MultipartManager(es)
    up = mp.new_upload("fam", "obj", {})
    # simulate a pre-family upload marker: strip the pinned __family
    ukey = mp._upload_key("fam", "obj", up)
    es.update_object_metadata(
        MP_VOLUME, ukey, "", lambda md: md.pop("__family", None)
    )
    p1 = os.urandom(2 << 20)
    e1 = mp.put_part("fam", "obj", up, 1, p1)
    monkeypatch.setenv("MINIO_TPU_EC_FAMILY", "cauchy")  # flip mid-upload
    p2 = os.urandom(1 << 20)
    e2 = mp.put_part("fam", "obj", up, 2, p2)
    mp.complete("fam", "obj", up, [(1, e1), (2, e2)])
    fi, metas, _, _ = es._quorum_fileinfo("fam", "obj", "", read_data=True)
    assert fi.erasure.algorithm == "reedsolomon"
    _, it = es.get_object("fam", "obj")
    assert _drain(it) == p1 + p2
    for dk, m in zip(es.disks, metas):
        if m is not None:
            dk.verify_file("fam", "obj", m)

"""Served-traffic TPU integration: the full S3 server (router, SigV4
auth, erasure set, dispatcher) on the REAL chip — concurrent PutObject
traffic batched into the fused encode+hash mega-kernel, degraded GETs
through the fused decode kernel, and heals rebuilding on-device.

This is the north-star *composition* proof (SURVEY.md §7 batching-service
contract; reference hot loops cmd/erasure-encode.go:76-108 and
cmd/erasure-decode.go:262-300): not kernels in isolation but device
kernels carrying real S3 requests with correct etags and digests.

Runs only on the TPU lane: MINIO_TPU_TEST_TPU=1 pytest -m tpu.
"""

import hashlib
import json
import os
import shutil
import threading

import numpy as np
import pytest

tpu_only = pytest.mark.skipif(
    __import__("jax").default_backend() != "tpu",
    reason="served-traffic integration needs the real TPU backend",
)

pytestmark = [pytest.mark.tpu, tpu_only]

N_OBJECTS = 32
OBJ_SIZE = 2 << 20  # 2 full stripe blocks per object on EC 2+2


def _mkdata(i: int) -> bytes:
    return np.random.default_rng(1000 + i).integers(
        0, 256, size=OBJ_SIZE, dtype=np.uint8
    ).tobytes()


@pytest.fixture(scope="module")
def rig(tmp_path_factory):
    """In-process server over 4 drives (EC 2+2) with the jax/device
    backend — the dispatcher and kernel counters stay inspectable."""
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "jax")
    mp.setenv("MINIO_TPU_SCAN_INTERVAL",
              os.environ.get("MINIO_TPU_SCAN_INTERVAL", "0"))
    # the device-decode floor (default 64 shards/dispatch) is a batching-
    # economics threshold, not a correctness gate; at this rig's scale
    # (EC 2+2, 2-block objects) lower it so degraded GETs actually
    # exercise the decode mega-kernel composition
    mp.setenv("MINIO_TPU_DECODE_MIN_SHARDS", "8")
    base = tmp_path_factory.mktemp("tpu-served")
    from minio_tpu.client import S3Client
    from tests.test_s3_api import ServerThread

    st = ServerThread([str(base / f"d{i}") for i in range(4)])
    cli = S3Client(f"127.0.0.1:{st.port}")
    assert cli.make_bucket("tpu-traffic").status == 200
    yield {"st": st, "cli": cli, "base": base,
           "etags": {}, "drives": [base / f"d{i}" for i in range(4)]}
    st.stop()
    mp.undo()


def test_concurrent_puts_ride_fused_kernel(rig):
    """>=32 concurrent PUTs: every object lands with the md5 etag, and the
    dispatcher counters prove the fused mega-kernel carried the stripe
    blocks, batched across requests."""
    from minio_tpu.parallel.dispatcher import _dispatchers

    cli = rig["cli"]

    def snap():
        return {
            "blocks": sum(d.stats["blocks"] for d in _dispatchers.values()),
            "fused": sum(
                d.stats.get("fused", 0) for d in _dispatchers.values()
            ),
            "failures": sum(
                d.stats.get("fused_failures", 0)
                for d in _dispatchers.values()
            ),
            "max_batch": max(
                (d.stats["max_batch"] for d in _dispatchers.values()),
                default=0,
            ),
        }

    before = snap()
    results: dict[int, tuple[int, str]] = {}

    def put(i: int):
        data = _mkdata(i)
        r = cli.put_object("tpu-traffic", f"obj-{i}", data)
        results[i] = (r.status, r.headers.get("etag", "").strip('"'))

    threads = [
        threading.Thread(target=put, args=(i,)) for i in range(N_OBJECTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i in range(N_OBJECTS):
        status, etag = results[i]
        assert status == 200, f"obj-{i} -> {status}"
        want = hashlib.md5(_mkdata(i)).hexdigest()
        assert etag == want, f"obj-{i} etag {etag} != md5 {want}"
        rig["etags"][i] = etag

    after = snap()
    # every full stripe block of every object crossed the dispatcher
    assert after["blocks"] - before["blocks"] >= N_OBJECTS * 2, after
    assert after["fused"] > before["fused"], \
        f"mega-kernel never engaged: {before} -> {after}"
    assert after["failures"] == before["failures"], \
        f"kernel failures during serving: {before} -> {after}"
    # batching composed blocks from more than one request into a dispatch
    # (each PUT submits 2 blocks, so a >=4 batch spans >=2 requests)
    assert after["max_batch"] >= 4, \
        f"no cross-request batching observed: {before} -> {after}"


def test_served_get_roundtrip(rig):
    """Every object reads back byte-identical through the full stack
    (bitrot digests verified per shard block on the way out)."""
    cli = rig["cli"]
    for i in range(0, N_OBJECTS, 5):
        r = cli.get_object("tpu-traffic", f"obj-{i}")
        assert r.status == 200
        assert r.body == _mkdata(i), f"obj-{i} corrupt"
        assert r.headers.get("etag", "").strip('"') == rig["etags"].get(
            i, hashlib.md5(_mkdata(i)).hexdigest()
        )


def test_degraded_get_rides_decode_kernel(rig):
    """Kill one drive; GETs must reconstruct through the fused decode
    path on the chip and return correct bytes."""
    from minio_tpu.ops.bitrot_jax import decode_stats

    cli = rig["cli"]
    victim = rig["drives"][1] / "tpu-traffic"
    shutil.rmtree(victim)
    victim.mkdir()
    before = dict(decode_stats)
    for i in range(0, N_OBJECTS, 4):
        r = cli.get_object("tpu-traffic", f"obj-{i}")
        assert r.status == 200 and r.body == _mkdata(i), f"degraded obj-{i}"
    assert decode_stats["fused"] > before["fused"], decode_stats
    assert decode_stats["failures"] == before["failures"], decode_stats


def test_heal_rebuilds_on_device(rig):
    """Admin heal sweep rebuilds the shards lost in the previous test via
    the device reconstruct path; afterwards reads survive losing a
    DIFFERENT drive (proof the healed copies are real and verified)."""
    os.environ["MINIO_TPU_DEVICE_HEAL"] = "1"
    try:
        cli = rig["cli"]
        r = cli.request("POST", "/minio/admin/v3/heal/tpu-traffic")
        assert r.status == 200, r.body
        out = json.loads(r.body)
        assert len(out["healed"]) >= 1 and out["failed"] == 0, out
        # the healed drive now carries real shards: lose another drive
        other = rig["drives"][2] / "tpu-traffic"
        shutil.rmtree(other)
        other.mkdir()
        for i in (0, 8, 16):
            g = cli.get_object("tpu-traffic", f"obj-{i}")
            assert g.status == 200 and g.body == _mkdata(i)
        # re-heal so later tests see a clean set
        assert cli.request(
            "POST", "/minio/admin/v3/heal/tpu-traffic").status == 200
    finally:
        os.environ.pop("MINIO_TPU_DEVICE_HEAL", None)


def test_multipart_served_on_device(rig):
    """Multipart upload (the long-context analogue): each part is its own
    erasure stream through the dispatcher; completed object reads back
    whole and range reads map into the right part."""
    cli = rig["cli"]
    part_size = 5 << 20  # S3 minimum non-final part size
    parts_data = [
        np.random.default_rng(7000 + p).integers(
            0, 256, size=part_size, dtype=np.uint8
        ).tobytes()
        for p in range(2)
    ]
    r = cli.request("POST", "/tpu-traffic/mp-obj", query={"uploads": ""})
    assert r.status == 200
    uid = r.body.decode().split("<UploadId>")[1].split("<")[0]
    etags = []
    for pn, data in enumerate(parts_data, 1):
        r = cli.request(
            "PUT", "/tpu-traffic/mp-obj",
            query={"partNumber": str(pn), "uploadId": uid}, body=data,
        )
        assert r.status == 200, r.body
        etags.append(r.headers.get("etag", "").strip('"'))
    xml = "<CompleteMultipartUpload>" + "".join(
        f"<Part><PartNumber>{i}</PartNumber><ETag>{e}</ETag></Part>"
        for i, e in enumerate(etags, 1)
    ) + "</CompleteMultipartUpload>"
    r = cli.request("POST", "/tpu-traffic/mp-obj",
                    query={"uploadId": uid}, body=xml.encode())
    assert r.status == 200, r.body
    whole = b"".join(parts_data)
    g = cli.get_object("tpu-traffic", "mp-obj")
    assert g.status == 200 and g.body == whole
    # a range crossing the part boundary
    lo, hi = part_size - 1000, part_size + 1000
    g = cli.request("GET", "/tpu-traffic/mp-obj",
                    headers={"Range": f"bytes={lo}-{hi - 1}"})
    assert g.status == 206 and g.body == whole[lo:hi]


# ---------------------------------------------------------------- kernels
# Decode failure-pattern matrix + batch-padding edges: the kernel-level
# hardening half of the lane (reference cmd/erasure-decode_test.go's
# dataDown/parityDown matrix).


@pytest.mark.parametrize(
    "d,p,losses",
    [
        (2, 2, [(1,), (2,), (1, 2), (0, 3)]),
        (4, 2, [(0,), (5,), (1, 4), (2, 3)]),
        (6, 3, [(0,), (7,), (1, 6), (0, 3, 8), (1, 2, 4)]),
        (8, 8, [(2,), (9,), (0, 8), (1, 2, 3, 4), (0, 2, 9, 11, 13, 15),
                (0, 1, 2, 3, 4, 5, 6, 7)]),
    ],
    ids=["ec2+2", "ec4+2", "ec6+3", "ec8+8"],
)
def test_decode_failure_pattern_matrix(d, p, losses):
    """1..p losses across data/parity mixes: rebuilt shards byte-identical
    to the numpy codec, rebuilt digests match numpy HighwayHash."""
    import jax

    from minio_tpu.ops import fused_pallas as fp
    from minio_tpu.ops.highwayhash import hash256_batch_numpy
    from minio_tpu.ops.rs import get_codec

    B = 16
    n = 2 * fp.CHUNK_BYTES
    rng = np.random.default_rng(d * 100 + p)
    blocks = rng.integers(0, 256, size=(B, d, n), dtype=np.uint8)
    ref = get_codec(d, p)
    full = []
    for b in range(B):
        shards = ref.split(blocks[b].tobytes())
        ref.encode(shards)
        full.append(shards)
    for missing in losses:
        assert len(missing) <= p
        present = tuple(i for i in range(d + p) if i not in missing)[:d]
        surv = np.stack(
            [np.stack([full[b][i] for i in present]) for b in range(B)]
        )
        rebuilt_cm, digests = fp.fused_decode_hash_cm(
            jax.device_put(fp.pack_chunk_major(surv)), d, p,
            present, tuple(missing),
        )
        rebuilt = fp.unpack_chunk_major(np.asarray(rebuilt_cm))
        digs = np.asarray(digests)
        for b in range(B):
            for mi, idx in enumerate(missing):
                assert (rebuilt[b, mi] == full[b][idx]).all(), \
                    f"d={d} p={p} missing={missing} b={b} idx={idx}"
            want_m = hash256_batch_numpy(
                np.stack([full[b][i] for i in missing])
            )
            assert (digs[b, d:d + len(missing)] == want_m).all()


@pytest.mark.parametrize("k", [15, 17])
def test_batch_padding_edges(k):
    """Batches straddling the 16-block floor (15 pads up, 17 pads to 32)
    keep every real block byte-correct through the dispatcher."""
    from minio_tpu.ops.highwayhash import hash256_batch_numpy
    from minio_tpu.ops.rs import get_codec
    from minio_tpu.ops.rs_jax import get_tpu_codec
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    d, p = 4, 2
    n = 2 * 1024
    rng = np.random.default_rng(k)
    blocks = rng.integers(0, 256, size=(k, d, n), dtype=np.uint8)
    disp = TpuDispatcher(get_tpu_codec(d, p), n, window_s=0.001)
    parity, digests = disp.encode(blocks)
    assert parity.shape == (k, p, n) and digests.shape == (k, d + p, 32)
    assert disp.stats.get("fused_failures", 0) == 0
    ref = get_codec(d, p)
    for b in range(k):
        want = ref.split(blocks[b].tobytes())
        ref.encode(want)
        assert (parity[b] == want[d:]).all(), f"b={b}"
        assert (digests[b] == hash256_batch_numpy(want)).all(), f"b={b}"

"""The default EC 12+4 set of 16 drives with one node's four drives offline
(positions k, k+4, k+8, k+12), served over HTTP: whatever the key's rotation
the quadruple holds three data shards and one parity shard; a PUT is
acknowledged at exact write quorum with 12 shard files and none on an offline
drive, counted and queued for heal; its GET, with the drives still offline, is
the body and what the plain reference (`chipbench/reference_decode.py`)
rebuilds from those 12 files; the device rung rebuilds m = 3 shards a block;
`xla_decode` equals the reference at m = 3; a healthy GET books the `get`/
`native` phase once and its bytes under `path="native"`. CPU, seeded, small:
the device plane on XLA's CPU backend, as the chipbench rehearsals force it."""

import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench import reference, reference_decode  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402
from minio_tpu import fault  # noqa: E402
from minio_tpu.client import S3Client  # noqa: E402

from test_s3_api import ServerThread  # noqa: E402

BUCKET, MIB = "mixed", 1 << 20
QUADS = [(k, k + 4, k + 8, k + 12) for k in range(4)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One 16-drive server, storage class unset (12 + 4), and a seeded 8 MiB
    body: 8 stripe blocks, one read window of 128 shards, over the 64 that
    send it to the device rung."""
    pytest.importorskip("jax")
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "jax")
    mp.delenv("MINIO_STORAGE_CLASS_STANDARD", raising=False)
    mp.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    mp.setenv("MINIO_TPU_DRIVE_COOLDOWN_S", "0.01")  # one case's drives are back for the next
    mp.setenv("MINIO_TPU_HEDGE", "0")
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("mixed-drives")
    drives = [str(base / f"d{i:02d}") for i in range(16)]
    st = ServerThread(drives)
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        first_scrape = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
        assert cli.make_bucket(BUCKET).status == 200
        body = np.random.default_rng([2 ** 31 + 34, 12]).bytes(8 * MIB)
        yield st, cli, drives, body, first_scrape
    finally:
        fault.clear()
        st.stop()
        mp.undo()


def take_offline(cli, drives, which):
    for i in which:
        r = cli.admin("POST", "fault/inject", body={
            "boundary": "storage", "mode": "error", "target": drives[i]})
        assert r.status == 200, r.body
    assert cli.admin("POST", "cache/clear").status == 200


def scrape(cli) -> dict:
    return parse_metrics(cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode())


def total(series, name, **match):
    return sum(v for labels, v in series.get(name, [])
               if all(labels.get(k) == w for k, w in match.items()))


@pytest.mark.parametrize("rotation", range(16))
def test_every_rotation_loses_three_data_shards_and_one_parity_to_every_quadruple(rotation):
    """`hash_order` is a rotation of 1..16 and the parity positions are four
    in a row, so k, k+4, k+8, k+12 hold exactly one of them: every object of
    every seed loses 3 data + 1 parity, whichever node is down."""
    order = [(rotation + i) % 16 for i in range(1, 17)]
    for quad in QUADS:
        held = [order[i] for i in quad]
        assert sum(1 for s in held if s < 12) == 3 and sum(1 for s in held if s >= 12) == 1
    # four consecutive positions would lose 0 to 4 data shards by the rotation
    assert sum(1 for s in order[:4] if s < 12) in range(0, 5)


def test_the_references_shard_order_is_the_programs_hash_order():
    from minio_tpu.utils.hashing import hash_order

    for key in ("obj/0000", "c03/000017", "x"):
        assert [s - 1 for s in hash_order(f"{BUCKET}/{key}", 16)] \
            == reference_decode.shard_order(BUCKET, key, 16)


@pytest.mark.parametrize("offline", QUADS, ids=lambda o: "off-" + "-".join(map(str, o)))
def test_a_put_at_exact_quorum_keeps_12_shard_files_and_is_read_back_rebuilt(served, offline):
    _, cli, drives, body, _ = served
    fault.clear()
    time.sleep(0.05)  # past the breakers' cooldown: the next call probes
    key = f"obj/{offline[0]:04d}"
    take_offline(cli, drives, offline)
    try:
        before = scrape(cli)
        r = cli.request("PUT", f"/{BUCKET}/{key}", body=body, unsigned_payload=True)
        assert r.status == 200  # 12 online, write quorum 12
        mid = scrape(cli)
        got = cli.request("GET", f"/{BUCKET}/{key}")  # the drives still offline
        after = scrape(cli)
    finally:
        fault.clear()
    assert got.status == 200 and got.body == body
    assert got.headers.get("etag", "").strip('"') == r.headers.get("etag", "").strip('"')
    # twelve shard files, every frame the reference's, none on an offline drive
    files = reference_decode.read_shards(drives, BUCKET, key)
    order = reference_decode.shard_order(BUCKET, key, 16)
    assert sorted(files) == sorted(order[i] for i in range(16) if i not in offline)
    frames = reference.object_frames(body, 12, 4)
    assert all(files[i] == frames[i] for i in files)
    assert reference_decode.decode_object(files, 12, 4) == body
    # counted: four shards no drive took, the object queued for heal once
    assert total(mid, "minio_tpu_put_offline_shards_total") \
        - total(before, "minio_tpu_put_offline_shards_total") == 4
    assert total(mid, "minio_tpu_heal_mrf_pending") \
        - total(before, "minio_tpu_heal_mrf_pending") == 1
    assert total(after, "minio_tpu_heal_mrf_pending") == total(mid, "minio_tpu_heal_mrf_pending")
    # the GET rebuilt every block, three shards each, on a device rung,
    # and went down the windowed path
    assert total(after, "minio_tpu_decode_blocks_total") \
        - total(mid, "minio_tpu_decode_blocks_total") == 8
    name = "minio_tpu_decode_device_blocks_total"
    assert total(after, name, missing="3") - total(mid, name, missing="3") == 8
    assert total(after, name) - total(mid, name) == 8
    assert total(after, "minio_tpu_get_bytes_total", path="windowed") \
        - total(mid, "minio_tpu_get_bytes_total", path="windowed") == len(body)
    assert total(after, "minio_tpu_get_bytes_total", path="native") \
        == total(mid, "minio_tpu_get_bytes_total", path="native")


def test_a_set_with_five_drives_offline_refuses_the_put(served):
    """One more than the parity: quorum is 12 and 11 are online. The PUT
    fails, nothing is counted as an acknowledged partial write."""
    _, cli, drives, body, _ = served
    fault.clear()
    time.sleep(0.05)
    take_offline(cli, drives, (3, 7, 11, 15, 0))
    try:
        before = scrape(cli)
        r = cli.request("PUT", f"/{BUCKET}/refused", body=body, unsigned_payload=True)
        after = scrape(cli)
    finally:
        fault.clear()
    assert r.status >= 500
    assert total(after, "minio_tpu_put_offline_shards_total") \
        == total(before, "minio_tpu_put_offline_shards_total")
    assert reference_decode.read_shards(drives, BUCKET, "refused") == {}


def test_a_healthy_get_books_the_native_phase_once_and_its_bytes(served):
    _, cli, drives, body, first_scrape = served
    fault.clear()
    time.sleep(0.05)
    # every new row is there from the first scrape (the counters are the
    # process's: another test file's traffic may have moved them)
    rows = parse_metrics(first_scrape)
    assert {lab["path"] for lab, _ in rows["minio_tpu_get_bytes_total"]} == {"native", "windowed"}
    assert [lab for lab, _ in rows["minio_tpu_put_offline_shards_total"]] == [{}]
    assert rows["minio_tpu_heal_mrf_pending"] == [({}, 0.0)]  # this server's own queue
    assert any(lab == {"layer": "get", "phase": "native"}
               for lab, _ in rows["minio_tpu_phase_calls_total"])
    assert cli.request("PUT", f"/{BUCKET}/healthy", body=body,
                       unsigned_payload=True).status == 200
    assert cli.admin("POST", "cache/clear").status == 200
    before = scrape(cli)
    for _ in range(3):
        assert cli.request("GET", f"/{BUCKET}/healthy").body == body
    time.sleep(0.1)
    after = scrape(cli)

    def moved(name, **m):
        return total(after, name, **m) - total(before, name, **m)

    assert moved("minio_tpu_phase_calls_total", layer="get", phase="native") == 3
    assert moved("minio_tpu_phase_seconds_total", layer="get", phase="native") > 0
    assert moved("minio_tpu_phase_calls_total", layer="get", phase="start") == 0
    assert moved("minio_tpu_get_bytes_total", path="native") == 3 * len(body)
    assert moved("minio_tpu_get_bytes_total", path="windowed") == 0
    assert moved("minio_tpu_decode_blocks_total") == 0
    assert moved("minio_tpu_put_offline_shards_total") == 0
    assert len(reference_decode.read_shards(drives, BUCKET, "healthy")) == 16


@pytest.mark.parametrize("offline", QUADS[:2], ids=lambda o: "off-" + "-".join(map(str, o)))
def test_xla_decode_equals_the_reference_at_three_missing(offline):
    """The XLA rung alone, on the survivors of one window: 12 shards of 8
    blocks of 87,382 B in the `rows` layout the read path hands it, m = 3."""
    pytest.importorskip("jax")
    from minio_tpu.ops import bitrot_jax
    from minio_tpu.ops.rs_jax import get_tpu_codec

    d, p, key = 12, 4, "obj/0001"
    body = np.random.default_rng([34, offline[0]]).bytes(8 * MIB)
    data = reference.split(body, d)  # [8, 12, 87382]
    shards = np.concatenate([data, reference.encode(data, p)], axis=1)
    order = reference_decode.shard_order(BUCKET, key, 16)
    gone = {order[i] for i in offline}
    present = tuple(i for i in range(16) if i not in gone)[:d]
    missing = tuple(i for i in range(d) if i in gone)
    assert len(missing) == 3 and len(present) == 12
    rows = np.ascontiguousarray(shards[:, list(present), :].transpose(1, 0, 2))  # [d, W, per]
    before = bitrot_jax.decode_stats_snapshot()
    was = tuple(before["by_missing"].get(("xla", 3), (0, 0)))  # the rows are shared lists
    got = bitrot_jax.xla_decode(get_tpu_codec(d, p), rows.transpose(1, 0, 2), present, missing)
    after = bitrot_jax.decode_stats_snapshot()
    want = reference_decode.gf_apply(
        reference_decode.decode_matrix(d, p, list(present), list(missing)),
        shards[:, list(present), :])
    assert got.shape == (8, 3, 87382) and np.array_equal(got, want)
    assert np.array_equal(got, data[:, list(missing), :])
    # booked on the label the benchmark's configuration expects: rung xla, m = 3
    row = after["by_missing"]["xla", 3]
    assert (row[0] - was[0], row[1] - was[1]) == (1, 8)
    assert after["fused"] == before["fused"]

"""PUT, GET, HEAD and DELETE in one stream (PR 36): the plain key-space
reference (`chipbench/reference_keyspace.py`: an unversioned bucket as a
dictionary) against the server over a seeded sequence of 200 mixed operations
at 64 KiB to 1 MiB on 16 directories — every answer as it comes, and the state
at rest; and the phase clock of the operations: every row there at the first
scrape, each moved by the operation it names, one call an operation."""

import hashlib
import os
import random
import sys
import time

import numpy as np
import pytest

from minio_tpu import obs
from minio_tpu.client import S3Client
from minio_tpu.erasure import set as es_mod
from minio_tpu.storage.xlstorage import trash_stats
from tests.test_s3_api import ServerThread

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None
from chipbench import reference_keyspace  # noqa: E402
from chipbench.procs import parse_metrics, total  # noqa: E402
from chipbench.traffic import Request  # noqa: E402

OPS = ("get_object", "head_object", "put_object", "delete_object")
ROWS = [("op", p) for p in OPS] + [("stat", "info"), ("stat", "meta_read"),
                                    ("delete", "lock_wait"), ("delete", "drive_delete"),
                                    ("delete", "invalidate"), ("trash", "reclaim")]


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "numpy")
    mp.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    mp.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("mixeddrives")
    st = ServerThread([str(base / f"d{i:02d}") for i in range(16)])
    st.drives = [str(base / f"d{i:02d}") for i in range(16)]
    yield st
    st.stop()
    mp.undo()


def scrape(cli) -> dict:
    return parse_metrics(cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode())


def test_every_row_is_there_at_the_first_scrape(server):
    """Before any object request: the phase rows of the four operations, of a
    stat, a DELETE and the trash, and the trash's counters, all exported."""
    assert set(ROWS) <= {(layer, p) for layer, names in obs.PHASES.items() for p in names}
    text = S3Client(f"127.0.0.1:{server.port}").request(
        "GET", "/minio/metrics/v3/api/tpu").body.decode()
    for layer, name in ROWS:
        for series in ("seconds", "cpu_seconds", "calls"):
            assert f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{name}"}}' in text
    for name in ("minio_tpu_trash_moved_total", "minio_tpu_trash_moved_bytes_total",
                 "minio_tpu_trash_reclaimed_total", "minio_tpu_trash_reclaimed_bytes_total",
                 "minio_tpu_trash_failed_total", "minio_tpu_trash_pending",
                 "minio_tpu_stat_drives_asked_total"):
        assert f"\n{name} " in text


def test_each_operation_moves_its_own_rows_one_call_an_operation(server):
    cli = S3Client(f"127.0.0.1:{server.port}")
    assert cli.make_bucket("phases").status == 200
    body = os.urandom(300_000)
    before, trash0, asked0 = obs.phases_snapshot(), trash_stats(), es_mod.stat_drives_asked_snapshot()

    def moved(layer, name):
        now = obs.phases_snapshot()[layer, name]
        return now[2] - before[layer, name][2], now[0] - before[layer, name][0]

    assert cli.request("PUT", "/phases/k", body=body, unsigned_payload=True).status == 200
    assert cli.request("GET", "/phases/k").body == body
    for _ in range(3):
        r = cli.request("HEAD", "/phases/k")
        assert r.status == 200 and r.headers["content-length"] == "300000"
    assert cli.request("HEAD", "/phases/never").status == 404  # whatever the answer: a call
    assert cli.request("DELETE", "/phases/k").status == 204
    assert [moved("op", p)[0] for p in OPS] == [1, 4, 1, 1]
    assert all(moved("op", p)[1] > 0 for p in OPS)
    # a HEAD's stat, and the look-up a PUT's and a DELETE's handler make of their key first
    stats, _ = moved("stat", "info")
    reads, read_s = moved("stat", "meta_read")
    assert stats >= 4 and 1 <= reads <= stats and read_s > 0
    # three HEADs of one key: the FileInfo cache answers the later ones, so fewer fan-outs
    assert reads < stats
    assert es_mod.stat_drives_asked_snapshot() - asked0 == 16 * reads
    # the DELETE: its lock, its 16 drive calls and their join, the caches
    assert [moved("delete", p)[0] for p in ("lock_wait", "drive_delete", "invalidate")] == [1, 1, 1]
    assert moved("delete", "drive_delete")[1] > moved("delete", "lock_wait")[1]
    # and what it moved aside is reclaimed, on the reclaimers' threads, with their CPU booked
    deadline = time.monotonic() + 5
    while trash_stats()["reclaimed"] < trash0["reclaimed"] + 16 and time.monotonic() < deadline:
        time.sleep(0.01)
    now = trash_stats()
    assert now["moved"] - trash0["moved"] == 16 == now["reclaimed"] - trash0["reclaimed"]
    assert now["moved_bytes"] - trash0["moved_bytes"] == now["reclaimed_bytes"] \
        - trash0["reclaimed_bytes"] > 300_000  # 16 shard files of 300,000 B at 12+4, framed
    assert moved("trash", "reclaim")[0] == 16
    assert obs.phases_snapshot()["trash", "reclaim"][1] > before["trash", "reclaim"][1]
    # the scrape says the same
    tpu = scrape(cli)
    assert total(tpu, "minio_tpu_trash_pending") == now["pending"]
    assert total(tpu, "minio_tpu_phase_calls_total", layer="op", phase="head_object") \
        == obs.phases_snapshot()["op", "head_object"][2]


def test_200_mixed_operations_answer_as_the_plain_key_space_does(server):
    """A seeded walk of warp's deck by one client, sizes drawn from 64 KiB
    (inline in xl.meta, nothing to move aside) to 1 MiB (a data directory a
    drive): every answer is compared with the dictionary at that moment, and
    at rest every key is asked for again and looked for on the drives."""
    cli = S3Client(f"127.0.0.1:{server.port}")
    assert cli.make_bucket("keyspace").status == 200
    rng = random.Random(2 ** 31 + 36)
    sizes = (64 << 10, 128 << 10, 300_000, 1 << 20)
    bodies = {n: np.random.default_rng([36, n]).bytes(n) for n in sizes}
    md5 = {n: hashlib.md5(b).hexdigest() for n, b in bodies.items()}
    model = reference_keyspace.Bucket()
    records, gone, clock = [], [], 0
    deck = ["GET"] * 9 + ["HEAD"] * 6 + ["PUT"] * 3 + ["DELETE"] * 2
    trash0 = trash_stats()

    def ask(op, key, size=0):
        nonlocal clock
        r = cli.request(op, f"/keyspace/{key}", body=bodies[size] if op == "PUT" else b"",
                        unsigned_payload=op == "PUT")
        clock += 1
        answered = r.status == (204 if op == "DELETE" else 200)
        rec = Request(0, op, key, 0, clock - 0.5, clock, 200 if answered else r.status, True, 0)
        rec.etag = r.headers.get("etag", "").strip('"') if op != "PUT" else md5[size]
        rec.length = {"GET": len(r.body), "HEAD": int(r.headers.get("content-length", -1)),
                      "PUT": size, "DELETE": -1}[op]
        records.append(rec)
        return r

    for i in range(6):  # a bucket that holds something before the deck is walked
        size = sizes[i % 4]
        assert ask("PUT", f"obj/{i:04d}", size).status == 200
        model.put(f"obj/{i:04d}", md5[size], size)
    done = puts = 0
    while done < 200:
        hand = list(deck)
        rng.shuffle(hand)
        for op in hand:
            if op == "PUT":
                key, size = f"c00/{puts:06d}", rng.choice(sizes)
                r = ask(op, key, size)
                assert r.status == 200 and r.headers["etag"].strip('"') == md5[size]
                model.put(key, md5[size], size)
                puts += 1
            elif op == "DELETE":
                key = rng.choice(sorted(model.objects))
                assert ask(op, key).status == 204
                model.delete(key)
                gone.append(key)
            else:
                # mostly a key that exists, now and then one that was deleted
                key = rng.choice(gone) if gone and rng.random() < 0.15 \
                    else rng.choice(sorted(model.objects))
                r, want = ask(op, key), model.head(key)
                if want is None:
                    assert r.status == 404, (op, key)
                else:
                    assert r.status == 200 and r.headers["etag"].strip('"') == want[0]
                    assert int(r.headers["content-length"]) == want[1]
                    assert r.body == (bodies[want[1]] if op == "GET" else b"")
            done += 1
    assert done >= 200 and len(gone) >= 20
    # the replay of the records gives the same bucket, and found no answer wrong
    replayed, wrong = reference_keyspace.replay(records)
    assert wrong == [] and replayed.objects == model.objects and replayed.deleted == model.deleted
    # at rest: every key the model holds answers, every deleted key is gone from every drive
    for key, (etag, size) in model.objects.items():
        r = cli.request("HEAD", f"/keyspace/{key}")
        assert (r.status, r.headers["etag"].strip('"'), int(r.headers["content-length"])) \
            == (200, etag, size)
        assert cli.request("GET", f"/keyspace/{key}").body == bodies[size]
    for key in model.deleted:
        assert cli.request("GET", f"/keyspace/{key}").status == 404
        assert cli.request("HEAD", f"/keyspace/{key}").status == 404
        assert not any(os.path.lexists(os.path.join(d, "keyspace", key)) for d in server.drives)
    # and what the DELETEs moved aside is gone: one entry a drive for every DELETE of an
    # object that had a data directory (64 KiB and 128 KiB live inline, in xl.meta)
    deadline = time.monotonic() + 10
    while trash_stats()["pending"] > 0 and time.monotonic() < deadline:
        time.sleep(0.02)
    now = trash_stats()
    big = sum(1 for r in records if r.op == "DELETE"
              and next(p.length for p in records if p.op == "PUT" and p.key == r.key) > 128 << 10)
    assert now["moved"] - trash0["moved"] == 16 * big == now["reclaimed"] - trash0["reclaimed"]
    assert now["failed"] == trash0["failed"]
    assert all(os.listdir(os.path.join(d, ".minio.sys", "trash")) == [] for d in server.drives)

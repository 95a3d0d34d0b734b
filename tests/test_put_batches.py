"""`minio_tpu_put_batches_total{plane}` on `/api/tpu`: the batches a
streaming PUT's encode yielded, by its plane. The default 16-drive set is
12 + 4, whose d does not divide a stripe block: its PUTs take the pooled
arena (each block at a stride of d × shard_size) and book `arena` only;
`MINIO_TPU_ZEROCOPY=0` keeps the legacy staging copies and books `copy`.
Both rows are there from the first scrape. Served over HTTP in-process,
on the CPU plane (`MINIO_TPU_BACKEND=numpy`, the native pass off, so the
Python streaming plane is the one under test)."""

import hashlib
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench.procs import parse_metrics  # noqa: E402
from minio_tpu.client import S3Client  # noqa: E402

from test_s3_api import ServerThread  # noqa: E402

BUCKET, MIB = "batches", 1 << 20
BATCHES = "minio_tpu_put_batches_total"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "numpy")
    mp.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    mp.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    mp.delenv("MINIO_STORAGE_CLASS_STANDARD", raising=False)
    mp.delenv("MINIO_TPU_STREAM_BATCH_MB", raising=False)
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("batch-drives")
    st = ServerThread([str(base / f"d{i:02d}") for i in range(16)])
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        first = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
        assert cli.make_bucket(BUCKET).status == 200
        yield cli, first
    finally:
        st.stop()
        mp.undo()


def scrape(cli) -> dict:
    return parse_metrics(cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode())


def total(series, name, **match):
    return sum(v for labels, v in series.get(name, [])
               if all(labels.get(k) == w for k, w in match.items()))


def test_both_rows_are_there_from_the_first_scrape(served):
    _, first = served
    rows = parse_metrics(first)[BATCHES]
    assert sorted(labels["plane"] for labels, _ in rows) == ["arena", "copy"]


@pytest.mark.parametrize("zerocopy,plane", [("1", "arena"), ("0", "copy")])
def test_a_12_4_streaming_put_books_its_plane_only(served, monkeypatch, zerocopy, plane):
    """8 MiB + 5 B: one batch of 8 whole blocks and the tail's batch, both
    booked under the plane that made them; the ETag is md5 of the body and
    the body reads back. The arena plane stages nothing."""
    cli, _ = served
    monkeypatch.setenv("MINIO_TPU_ZEROCOPY", zerocopy)
    body = np.random.default_rng([2 ** 31 + 37, int(zerocopy)]).bytes(8 * MIB + 5)
    key = f"obj-{plane}"
    before = scrape(cli)
    r = cli.request("PUT", f"/{BUCKET}/{key}", body=body, unsigned_payload=True)
    assert r.status == 200
    after = scrape(cli)
    other = "copy" if plane == "arena" else "arena"
    assert total(after, BATCHES, plane=plane) - total(before, BATCHES, plane=plane) == 2
    assert total(after, BATCHES, plane=other) == total(before, BATCHES, plane=other)
    staged = (total(after, "minio_tpu_ingest_copies_total", site="staging")
              - total(before, "minio_tpu_ingest_copies_total", site="staging"))
    assert (staged == 0) == (plane == "arena")
    etag = {k.lower(): v for k, v in r.headers.items()}["etag"].strip('"')
    assert etag == hashlib.md5(body).hexdigest()
    got = cli.get_object(BUCKET, key)
    assert got.status == 200 and got.body == body

"""The closed-loop GET generator of a set that has lost drives: what a
restore job or a data loader sends while a node is out. Set-up (in
`warm_up`, so it counts in `setup_s`) PUTs `objects` seeded bodies of
`object_mib` one after another (`obj/0000`...), then enacts the state the
configuration states — its `offline_drives` are taken offline by the
server's own storage fault rule, `POST /minio/admin/v3/fault/inject
{"boundary":"storage","mode":"error","target":<drive directory>}`, so every
object keeps all its shard files and those drives' are out of reach — and
clears the read cache once. Then `clients` threads GET whole objects back to
back, client c's i-th GET being object (seed + 7c + i) mod `objects`, and
compare every body byte for byte, and its ETag, with what was PUT.

Every seed gives the same sizes, the same offline drives and the same count
of GETs per object to within one lap; the seed turns the bytes and the order
in which a client walks the objects, never the work.

Warm-up, after the faults are set: one GET alone (the first device
reconstruct traces, lowers and compiles its kernel on the thread of the GET
that meets it: eight at once would each do so), then the clients' own loop
until every object has been read and the counter the traffic file names
under `warm.first_calls` (unset: `minio_tpu_decode_first_calls_total`, on
`/api/tpu`) has stood still for `warm.quiet_s`, so that every kernel shape
the load provokes is built inside `setup_s`. A program that exports no such
counter (an older commit under these files) gets one lap and no wait.

What it receives (`chipbench/run.py`): the traffic file, the endpoint, the
bucket and the seed; then, before `prepare()`, `config` — the content of
`configs/<config>.json`, whose `deployment.offline_drives` it enacts — and
`drives`, the server's drive directories in the order of its command line.
What the checks read of it: `sent(record)`, `bodies`, `md5s`, `offline`
(the drive positions), `offline_files` ({(object, drive position): (size,
mtime_ns)} of the offline drives' shard files as set-up left them).
Parameters, all from the traffic file: `clients`, `object_mib`, `objects`,
`warm`. No jax, no numpy beyond body generation.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

from chipbench.procs import check, scrape
from chipbench.reference_decode import shard_path
from chipbench.traffic import MIB, Request

FIRST_CALLS = "minio_tpu_decode_first_calls_total"


class Generator:
    """`prepare()`, `warm_up()`, `start()` once each; `records()` grows until
    `stop()`."""

    def __init__(self, spec: dict, endpoint: str, bucket: str, seed: int, timeout: float = 300.0):
        self.endpoint, self.bucket, self.seed, self.timeout = endpoint, bucket, seed, timeout
        self.clients = spec["clients"]
        self.object_bytes = spec["object_mib"] * MIB
        self.objects = spec["objects"]
        self.warm = spec["warm"]
        self.config: dict | None = None
        self.drives: list[str] | None = None
        self.bodies: list[bytes] = []
        self.md5s: list[str] = []
        self.offline: list[int] = []
        self.offline_files: dict[tuple[int, int], tuple[int, int]] = {}
        self._records: list[list[Request]] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def prepare(self) -> None:
        import numpy as np

        check(self.config is not None and self.drives is not None,
              "the harness gave the generator no configuration or drives")
        dep = self.config["deployment"]
        self.offline = list(dep["offline_drives"])
        check(len(self.drives) == dep["drives"] and all(0 <= i < dep["drives"]
                                                        for i in self.offline),
              f"offline drives {self.offline} are not drives of a set of {len(self.drives)}")
        self.bodies = [np.random.default_rng([self.seed, 0x6E7, i]).bytes(self.object_bytes)
                       for i in range(self.objects)]
        self.md5s = [hashlib.md5(b).hexdigest() for b in self.bodies]

    def sent(self, r: Request) -> tuple[bytes, str]:
        """The body PUT under a record's key and its md5: what a GET of it
        must return."""
        return self.bodies[r.body], self.md5s[r.body]

    @staticmethod
    def key(obj: int) -> str:
        return f"obj/{obj:04d}"

    def object_for(self, client: int, i: int) -> int:
        """The object of a client's i-th GET: the seed turns the order, not
        the set."""
        return (self.seed + 7 * client + i) % self.objects

    def _request(self, client: int, op: str, obj: int) -> Request:
        from minio_tpu.client import S3Client

        key = self.key(obj)
        t0 = time.monotonic()
        try:
            r = S3Client(self.endpoint).request(
                op, f"/{self.bucket}/{key}", body=self.bodies[obj] if op == "PUT" else b"",
                unsigned_payload=op == "PUT", timeout=self.timeout)
        except OSError as e:
            return Request(client, op, key, obj, t0, time.monotonic(), 0, False, 0,
                           f"{type(e).__name__}: {e}")
        good = r.status == 200 and r.headers.get("etag", "").strip('"') == self.md5s[obj] \
            and (op == "PUT" or r.body == self.bodies[obj])
        return Request(client, op, key, obj, t0, time.monotonic(), r.status, good,
                       self.object_bytes if r.status == 200 else 0,
                       "" if r.status == 200 else r.body[:200].decode("utf-8", "replace"))

    # -- the deployment's state

    def take_offline(self) -> None:
        """The configuration's offline drives, by the server's storage fault
        rule (removing files would not hold: the program heals them back),
        then the read cache cleared once."""
        from minio_tpu.client import S3Client

        cli = S3Client(self.endpoint)
        for i in self.offline:
            r = cli.admin("POST", "fault/inject", body={
                "boundary": "storage", "mode": "error", "target": self.drives[i]})
            check(r.status == 200, f"fault/inject for drive {i} -> {r.status} {r.body[:200]!r}")
        r = cli.admin("POST", "cache/clear")
        check(r.status == 200, f"cache/clear -> {r.status} {r.body[:200]!r}")
        for obj in range(self.objects):
            for i in self.offline:
                path = shard_path(self.drives[i], self.bucket, self.key(obj))
                check(path is not None, f"{self.key(obj)} has no shard file on drive {i}")
                st = os.stat(path)
                self.offline_files[obj, i] = (st.st_size, st.st_mtime_ns)

    def _first_calls(self) -> float | None:
        """The program's count of first device reconstructs, or None where
        it exports none."""
        rows = scrape(int(self.endpoint.rsplit(":", 1)[1]), "/api/tpu").get(
            self.warm.get("first_calls", FIRST_CALLS))
        return None if rows is None else sum(v for _, v in rows)

    def warm_up(self, seen, want: set[int]) -> tuple[list[Request], list]:
        """Set-up PUTs, the drives offline, then GETs until no new kernel
        shape shows. No ladder: a GET meets no batch bucket."""
        records = [self._request(0, "PUT", obj) for obj in range(self.objects)]
        if any(r.status != 200 for r in records):
            return records, []
        self.take_offline()
        records.append(self._request(0, "GET", self.object_for(0, 0)))
        if records[-1].status != 200:
            return records, []
        self.start()
        t0 = last_change = time.monotonic()
        calls = self._first_calls()
        while True:
            time.sleep(0.5)
            now, new = time.monotonic(), self._first_calls()
            if new != calls:
                calls, last_change = new, now
            lap = len({r.body for r in self.records()}) >= self.objects
            if lap and (calls is None or now - last_change >= self.warm["quiet_s"]):
                break
            if now - t0 >= self.warm["max_s"] or any(r.status != 200 for r in self.records()):
                break
        self.stop()
        return records + self.records(), []

    # -- the loop

    def _client(self, c: int, mine: list, stop: threading.Event) -> None:
        i = 0
        while not stop.is_set():
            mine.append(self._request(c, "GET", self.object_for(c, i)))
            i += 1

    def start(self) -> None:
        """A fresh loop: the warm-up runs one of its own before the harness's."""
        self._stop = threading.Event()
        self._records = [[] for _ in range(self.clients)]
        self._threads = [threading.Thread(target=self._client, daemon=True,
                                          args=(c, self._records[c], self._stop))
                         for c in range(self.clients)]
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        """Every client finishes the request it has in flight."""
        self._stop.set()
        for t in self._threads:
            t.join(self.timeout + 30)

    def records(self) -> list[Request]:
        return [r for per in self._records for r in list(per)]

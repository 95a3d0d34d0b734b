"""A closed-loop GET generator, as small as one can be: the generator of the
test fixture `tests/chipbench/fixtures/added_cell`, which shows that a cell
with other traffic arrives as new files and appended entries. Set-up PUTs
`objects` seeded bodies of `object_mib` (in `warm_up`, so they count in
`setup_s`); then `clients` threads GET whole objects back to back and
compare every body, byte for byte, and its ETag with the one PUT. Every
seed gives the same sizes and counts; the seed turns the bytes and the order
in which a client walks the objects. All drives stay healthy, so these GETs
never reach the device: it is a fixture, not a cell of the benchmark.

It reads what the harness gives every generator (`chipbench/run.py`):
`config`, the configuration file's content, and `drives`, the server's drive
directories, both set before `prepare()`.
"""

from __future__ import annotations

import hashlib
import threading
import time

from chipbench.traffic import MIB, Request


class Generator:
    def __init__(self, spec: dict, endpoint: str, bucket: str, seed: int, timeout: float = 300.0):
        self.endpoint, self.bucket, self.seed, self.timeout = endpoint, bucket, seed, timeout
        self.clients = spec["clients"]
        self.object_bytes = spec["object_mib"] * MIB
        self.objects = spec["objects"]
        self.config: dict | None = None
        self.drives: list[str] | None = None
        self.bodies: list[bytes] = []
        self.md5s: list[str] = []
        self._records: list[list[Request]] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def prepare(self) -> None:
        import numpy as np

        if self.config is None or len(self.drives or []) != self.config["deployment"]["drives"]:
            raise RuntimeError("the harness gave the generator no configuration or drives")
        self.bodies = [np.random.default_rng([self.seed, 0x6E7, i]).bytes(self.object_bytes)
                       for i in range(self.objects)]
        self.md5s = [hashlib.md5(b).hexdigest() for b in self.bodies]

    def sent(self, r: Request) -> tuple[bytes, str]:
        return self.bodies[r.body], self.md5s[r.body]

    def object_for(self, client: int, i: int) -> int:
        return (self.seed + 7 * client + i) % self.objects

    def _request(self, client: int, op: str, obj: int) -> Request:
        from minio_tpu.client import S3Client

        key = f"obj/{obj:04d}"
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

    def warm_up(self, seen, want: set[int]) -> tuple[list[Request], list]:
        """The objects the window reads; no ladder: a GET from healthy drives
        meets no batch bucket."""
        return [self._request(0, "PUT", obj) for obj in range(self.objects)], []

    def _client(self, c: int) -> None:
        mine, i = self._records[c], 0
        while not self._stop.is_set():
            mine.append(self._request(c, "GET", self.object_for(c, i)))
            i += 1

    def start(self) -> None:
        self._records = [[] for _ in range(self.clients)]
        self._threads = [threading.Thread(target=self._client, args=(c,), daemon=True)
                         for c in range(self.clients)]
        for t in self._threads:
            t.start()

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(self.timeout + 30)

    def records(self) -> list[Request]:
        return [r for per in self._records for r in list(per)]

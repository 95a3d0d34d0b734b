"""The closed-loop PUT generator: what `mc admin speedtest`'s PUT phase
sends. `clients` threads, one S3 connection per request each; every client
PUTs a fresh key with one of `distinct_bodies` seeded bodies of `object_mib`
and sends its next request when the last one came back. Every seed gives
the same sizes and counts: the seed changes the bytes and the order in which
a client walks the bodies, never the work.

Warm-up ladder (`"ladder"`): every batch bucket the window can meet must
have been through the device once before it. The dispatcher batches only
what is queued while its one thread is busy, so "n PUTs at once" does not
give bucket n. A rung `[a, b]` therefore sends a+b bodies up to their last
`tail_kib`, releases the last bytes of the first `a` together, and
`stagger_ms` later those of the other `b`: these `b` objects are submitted
while the first dispatch holds the thread, and leave together as one batch.

Parameters, all from the traffic file: `clients`, `object_mib`,
`distinct_bodies`, `unsigned_payload`, `ladder` {`rungs`, `stagger_ms`,
`tail_kib`}. No jax, no numpy beyond body generation; md5s are made in
set-up, so the window spends nothing on generation and a PUT's ETag check
is a string compare.
"""

from __future__ import annotations

import hashlib
import http.client
import threading
import time

from chipbench.traffic import MIB, Request


def make_bodies(seed: int, n: int, nbytes: int) -> tuple[list[bytes], list[str]]:
    """`n` bodies and their md5s, all from the seed."""
    import numpy as np

    bodies = [np.random.default_rng([seed, 0xB0D1, i]).bytes(nbytes) for i in range(n)]
    return bodies, [hashlib.md5(b).hexdigest() for b in bodies]


class Generator:
    """`prepare()`, `warm_up()`, `start()` once each; `records()` grows until
    `stop()`."""

    def __init__(self, spec: dict, endpoint: str, bucket: str, seed: int, timeout: float = 300.0):
        self.endpoint, self.bucket, self.seed, self.timeout = endpoint, bucket, seed, timeout
        self.clients = spec["clients"]
        self.object_mib = spec["object_mib"]
        self.object_bytes = self.object_mib * MIB
        self.distinct_bodies = spec["distinct_bodies"]
        self.unsigned_payload = spec["unsigned_payload"]
        self.ladder = spec["ladder"]
        self.bodies: list[bytes] = []
        self.md5s: list[str] = []
        self._records: list[list[Request]] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def prepare(self) -> None:
        self.bodies, self.md5s = make_bodies(self.seed, self.distinct_bodies, self.object_bytes)

    def sent(self, r: Request) -> tuple[bytes, str]:
        """The body a PUT carried and its md5: what a read of its key must return."""
        return self.bodies[r.body], self.md5s[r.body]

    def body_for(self, client: int, i: int) -> int:
        """The body of a client's i-th PUT: the seed turns the order, not
        the set, and every body is as long."""
        return (self.seed + 7 * client + i) % self.distinct_bodies

    def put(self, client: int, key: str, body: int) -> Request:
        """One PUT, timed from send to the last byte of the response."""
        from minio_tpu.client import S3Client

        cli = S3Client(self.endpoint)
        t0 = time.monotonic()
        try:
            r = cli.request("PUT", f"/{self.bucket}/{key}", body=self.bodies[body],
                            unsigned_payload=self.unsigned_payload, timeout=self.timeout)
            t1 = time.monotonic()
            good = r.status == 200 and r.headers.get("etag", "").strip('"') == self.md5s[body]
            return Request(client, "PUT", key, body, t0, t1, r.status, good,
                           self.object_bytes if r.status == 200 else 0,
                           "" if r.status == 200 else r.body[:200].decode("utf-8", "replace"))
        except OSError as e:
            return Request(client, "PUT", key, body, t0, time.monotonic(), 0, False, 0,
                           f"{type(e).__name__}: {e}")

    # -- the ladder

    def put_held(self, slot: int, key: str, body: int, ready: threading.Event,
                 gate: threading.Event) -> Request:
        """One warm-up PUT whose last `tail_kib` wait for `gate`: what
        `S3Client.request` does, with the body sent in two pieces."""
        from minio_tpu import client as s3

        data = self.bodies[body]
        cli = s3.S3Client(self.endpoint)
        path = f"/{self.bucket}/{key}"
        payload = s3.UNSIGNED_PAYLOAD if self.unsigned_payload else data
        signed = s3.sign_request("PUT", f"http://{cli.host}:{cli.port}{path}", {}, payload,
                                 cli.access_key, cli.secret_key, cli.region)
        cut = max(0, len(data) - self.ladder.get("tail_kib", 64) * 1024)
        t0 = time.monotonic()
        conn = http.client.HTTPConnection(cli.host, cli.port, timeout=self.timeout)
        try:
            conn.putrequest("PUT", path, skip_host=True, skip_accept_encoding=True)
            for k, v in signed.items():
                conn.putheader(k, v)
            conn.putheader("Content-Length", str(len(data)))
            conn.endheaders()
            conn.send(memoryview(data)[:cut])
            ready.set()
            gate.wait(self.timeout)
            conn.send(memoryview(data)[cut:])
            r = conn.getresponse()
            out = r.read()
            good = r.status == 200 and (r.getheader("etag") or "").strip('"') == self.md5s[body]
            return Request(slot, "PUT", key, body, t0, time.monotonic(), r.status, good,
                           len(data) if r.status == 200 else 0,
                           "" if r.status == 200 else out[:200].decode("utf-8", "replace"))
        except OSError as e:
            ready.set()
            return Request(slot, "PUT", key, body, t0, time.monotonic(), 0, False, 0,
                           f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    def rung(self, groups: list[int], stagger_s: float, base: int) -> list[Request]:
        """One rung of the ladder: sum(groups) PUTs, all but their tails
        sent; then group after group is released, `stagger_s` apart."""
        n = sum(groups)
        out: list = [None] * n
        ready = [threading.Event() for _ in range(n)]
        gates = [threading.Event() for _ in groups]
        of_group = [g for g, size in enumerate(groups) for _ in range(size)]

        def go(i):
            out[i] = self.put_held(i, f"warm/{base:03d}-{i:02d}", self.body_for(i, base),
                                   ready[i], gates[of_group[i]])

        ts = [threading.Thread(target=go, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for ev in ready:
            ev.wait(self.timeout)
        for g, gate in enumerate(gates):
            if g:
                time.sleep(stagger_s)
            gate.set()
        for t in ts:
            t.join()
        return out

    def warm_up(self, seen, want: set[int]) -> tuple[list[Request], list]:
        """Climb the ladder. `seen()` gives the batch buckets the dispatcher
        has met; a rung whose bucket is in `want` is tried again, with the
        next stagger, until the bucket shows. -> (records, [[bucket, ms]])."""
        records, tries = [], []
        staggers = self.ladder["stagger_ms"]
        for i, groups in enumerate(self.ladder["rungs"]):
            # the bucket this rung is for: its last group's blocks, as a power of two
            aim = 1 << (groups[-1] * self.object_mib - 1).bit_length()
            for attempt, ms in enumerate(staggers if aim in want else staggers[:1]):
                records += self.rung(groups, ms / 1e3, 100 + 10 * i + attempt)
                tries.append([aim, ms])
                if aim not in want or aim in seen():
                    break
        return records, tries

    # -- the loop

    def _client(self, c: int) -> None:
        mine, i = self._records[c], 0
        while not self._stop.is_set():
            mine.append(self.put(c, f"c{c:02d}/{i:06d}", self.body_for(c, i)))
            i += 1

    def start(self) -> None:
        self._records = [[] for _ in range(self.clients)]
        self._threads = [threading.Thread(target=self._client, args=(c,), daemon=True)
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

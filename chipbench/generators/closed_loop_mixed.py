"""The closed-loop mixed generator: what MinIO's `warp mixed` sends. A bucket
is prepared with `prepared_objects` objects of `object_mib`; then `clients`
threads run back to back, with no rate and no think time, and EVERY client
does every kind of operation: it walks a seeded shuffle of a deck —
`deck` = {"GET": 9, "HEAD": 6, "PUT": 3, "DELETE": 2}, warp's 45 / 30 / 15 /
10 of a hundred as 20 cards (HEAD is what warp calls STAT) — and shuffles it
again at its end, so every 20 operations of every client hold the shares
exactly and no seed has a luckier mix.

- GET, HEAD: a key drawn uniformly from the pool of objects that exist; the
  whole object; body, ETag and size compared with what was PUT under the key.
- PUT: a fresh key `c<cc>/<iiiiii>`, one of `distinct_bodies` seeded bodies,
  unsigned payload (the streaming plane). The key enters the pool when the
  PUT is acknowledged.
- DELETE: a key drawn uniformly from the pool among those with no request in
  flight, taken out of the pool BEFORE the DELETE is sent; 204 expected.

So no request races a DELETE of its own key, every answer has exactly one
right value, and nothing fails in a sound run. The pool grows by one object
in twenty operations and cannot drain. Every seed gives the same sizes, the
same deck and the same counts; the seed turns the bytes, the shuffles and
the draws, never the work.

What the harness counts (`chipbench/run.py`): `status == 200` is
acknowledged, everything else failed. A record therefore carries 200 where
the server answered what S3 states for the operation (200 for GET, HEAD and
PUT, 204 for DELETE) and the raw code otherwise (0 for a DELETE answered 200,
which is not what S3 states and must not read as acknowledged); the raw code
is kept beside it (`MixedRequest.raw_status`) with what the answer said
(`etag`, `length`),
which `chipbench/reference_keyspace.py` replays. `nbytes` is the object's
bytes for GET and PUT and 0 for HEAD and DELETE: `s3_mib_s` is the object
bytes moved, and the operations that carry none move it by the time they
take.

Set-up, all inside `warm_up` and so inside `setup_s`: first the sibling's
ladder (`closed_loop_put.rung`, keys `warm/...`, which stay outside the pool),
a rung for each batch bucket the window meets — one 10-block PUT alone in
bucket 16, two released together in 32, four in 64 — because the set-up PUTs
alone do not meet them reliably: eight clients that PUT at once leave
together, seven or eight to a batch, or one by one. Then the crowd's bucket
(`crowd_rung`: 128, seven or eight PUTs in one batch), which a window meets
once in a dozen runs, when a stall lets the clients' PUTs pile up, and would
then trace and lower inside it for seconds: `groups` [4, 12] releases four PUTs
to hold the dispatch thread and twelve behind them, of which any seven that
leave together will do, and is tried with the ladder's staggers in turn until
the bucket shows, `tries` times at most (it is not among the cell's
`warm_buckets`: a set-up that cannot provoke it goes on). Then the `clients`
threads PUT the prepared objects `obj/0000`... (object i by client i mod clients, body
(seed + i) mod `distinct_bodies`), and the read cache is cleared once. The
harness then runs the loop until quiet.

What it receives (`chipbench/run.py`): the traffic file, the endpoint, the
bucket and the seed; then, before `prepare()`, `config` and `drives`. What
the checks read of it: `sent(record)`, `bodies`, `md5s`, `object_bytes`,
`deck`, `clients`, `setup_keys`. Parameters, all from the traffic file:
`clients`, `object_mib`, `prepared_objects`, `distinct_bodies`, `deck`,
`unsigned_payload`, `ladder`, `crowd_rung` (absent or null: none). No jax, no numpy beyond body generation.
"""

from __future__ import annotations

import random
import threading
import time

from chipbench import plugins
from chipbench.procs import check
from chipbench.traffic import MIB, Request

# what S3 states a sound answer's status is
EXPECTED = {"GET": 200, "HEAD": 200, "PUT": 200, "DELETE": 204}


class MixedRequest(Request):
    """`traffic.Request`, with the server's own code and what the answer said:
    `status` is 200 where `raw_status` is what S3 states for `op`. (A plain
    subclass: `plugins.load` registers no module, which `@dataclass` needs.)"""

    def __init__(self, *fields, raw_status: int = 0, etag: str = "", length: int = -1):
        super().__init__(*fields)
        self.raw_status = raw_status
        self.etag = etag      # the answer's ETag (GET, HEAD, PUT)
        self.length = length  # GET: bytes of the body; HEAD: Content-Length; PUT: bytes sent


class Pool:
    """The keys that exist, for uniform draws: a list and each key's place in
    it (a removal swaps the last key in). `busy` counts the requests in flight
    per key: a DELETE draws among the keys with none."""

    def __init__(self):
        self.mu = threading.Lock()
        self.keys: list[str] = []
        self.at: dict[str, int] = {}
        self.body: dict[str, int] = {}
        self.busy: dict[str, int] = {}

    def add(self, key: str, body: int) -> None:
        with self.mu:
            self.at[key] = len(self.keys)
            self.keys.append(key)
            self.body[key] = body

    def borrow(self, rng: random.Random) -> tuple[str, int]:
        """A key for a GET or a HEAD; `give_back` when the answer is in."""
        with self.mu:
            key = self.keys[rng.randrange(len(self.keys))]
            self.busy[key] = self.busy.get(key, 0) + 1
            return key, self.body[key]

    def give_back(self, key: str) -> None:
        with self.mu:
            if self.busy[key] == 1:
                del self.busy[key]
            else:
                self.busy[key] -= 1

    def take(self, rng: random.Random) -> tuple[str, int]:
        """A key for a DELETE, out of the pool for good: drawn uniformly among
        those with no request in flight (at most `clients` - 1 are busy)."""
        with self.mu:
            check(len(self.keys) > len(self.busy), "the pool has no key at rest to delete")
            while True:
                key = self.keys[rng.randrange(len(self.keys))]
                if key not in self.busy:
                    break
            i, last = self.at.pop(key), self.keys.pop()
            if last != key:
                self.keys[i], self.at[last] = last, i
            return key, self.body.pop(key)

    def __len__(self) -> int:
        with self.mu:
            return len(self.keys)


class Generator:
    """`prepare()`, `warm_up()`, `start()` once each; `records()` grows until
    `stop()`."""

    def __init__(self, spec: dict, endpoint: str, bucket: str, seed: int, timeout: float = 300.0):
        self.endpoint, self.bucket, self.seed, self.timeout = endpoint, bucket, seed, timeout
        self.clients = spec["clients"]
        self.object_bytes = spec["object_mib"] * MIB
        self.prepared = spec["prepared_objects"]
        self.unsigned_payload = spec["unsigned_payload"]
        self.deck = dict(spec["deck"])
        self.crowd = spec.get("crowd_rung")
        check(set(self.deck) == set(EXPECTED) and self.deck["PUT"] > self.deck["DELETE"] > 0,
              f"deck {self.deck}: the four operations, more PUTs than DELETEs")
        # the sibling's bodies and its ladder rungs, as they stand
        self.ladder = plugins.load("generators", "closed_loop_put").Generator(
            spec, endpoint, bucket, seed, timeout)
        self.config: dict | None = None
        self.drives: list[str] | None = None
        self.bodies: list[bytes] = []
        self.md5s: list[str] = []
        self.pool = Pool()
        self._records: list[list[MixedRequest]] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def prepare(self) -> None:
        check(self.config is not None and self.drives is not None,
              "the harness gave the generator no configuration or drives")
        self.ladder.prepare()
        self.bodies, self.md5s = self.ladder.bodies, self.ladder.md5s

    # -- what the checks read

    @property
    def setup_keys(self) -> list[str]:
        return [f"obj/{i:04d}" for i in range(self.prepared)]

    def sent(self, r: Request) -> tuple[bytes, str]:
        """The body PUT under a record's key and its md5."""
        return self.bodies[r.body], self.md5s[r.body]

    def hand(self, client: int, lap: int) -> list[str]:
        """A client's lap-th walk of the deck: every card, in a seeded order."""
        cards = [op for op in sorted(self.deck) for _ in range(self.deck[op])]
        random.Random(f"{self.seed}/deck/{client}/{lap}").shuffle(cards)
        return cards

    # -- one request

    def request(self, client: int, op: str, key: str, body: int) -> MixedRequest:
        """One operation, timed from send to the last byte of the response,
        its answer compared with what was PUT under the key."""
        from minio_tpu.client import S3Client

        t0 = time.monotonic()
        try:
            r = S3Client(self.endpoint).request(
                op, f"/{self.bucket}/{key}", body=self.bodies[body] if op == "PUT" else b"",
                unsigned_payload=self.unsigned_payload and op == "PUT", timeout=self.timeout)
        except OSError as e:
            return MixedRequest(client, op, key, body, t0, time.monotonic(), 0, False, 0,
                                f"{type(e).__name__}: {e}")
        t1 = time.monotonic()
        sound = r.status == EXPECTED[op]
        etag = r.headers.get("etag", "").strip('"')
        length = {"GET": len(r.body), "PUT": self.object_bytes, "DELETE": -1,
                  "HEAD": int(r.headers.get("content-length", -1))}[op]
        good = sound and (op == "DELETE" or (
            etag == self.md5s[body] and length == self.object_bytes
            and (op != "GET" or r.body == self.bodies[body])))
        return MixedRequest(
            client, op, key, body, t0, t1,
            200 if sound else (0 if r.status == 200 else r.status), good,
            self.object_bytes if sound and op in ("GET", "PUT") else 0,
            "" if sound else f"{op} -> {r.status} {r.body[:160].decode('utf-8', 'replace')}",
            raw_status=r.status, etag=etag, length=length)

    def _do(self, client: int, op: str, i: int, rng: random.Random) -> MixedRequest:
        if op == "PUT":
            key, body = f"c{client:02d}/{i:06d}", (self.seed + 7 * client + i) % len(self.bodies)
            r = self.request(client, op, key, body)
            if r.status == 200:
                self.pool.add(key, body)
            return r
        if op == "DELETE":
            return self.request(client, op, *self.pool.take(rng))
        key, body = self.pool.borrow(rng)
        try:
            return self.request(client, op, key, body)
        finally:
            self.pool.give_back(key)

    # -- set-up

    def warm_up(self, seen, want: set[int]) -> tuple[list[Request], list]:
        """The ladder, a rung for each batch bucket the cell wants; the crowd's
        rung; the prepared objects, PUT by all clients at once; the cache
        cleared once."""
        from minio_tpu.client import S3Client

        climbed, tries = self.ladder.warm_up(seen, want)
        if self.crowd and all(r.status == 200 for r in climbed):
            aim = 1 << (self.crowd["groups"][-1] * self.object_bytes // MIB - 1).bit_length()
            staggers = self.ladder.ladder["stagger_ms"]
            for attempt in range(self.crowd["tries"]):
                if aim in seen():
                    break
                ms = staggers[attempt % len(staggers)]
                climbed += self.ladder.rung(self.crowd["groups"], ms / 1e3, 200 + attempt)
                tries.append([aim, ms])
        # the ladder's records, as this generator's: what was sent and acknowledged
        records: list[Request] = [MixedRequest(
            r.client, r.op, r.key, r.body, r.sent, r.done, r.status, r.ok, r.nbytes, r.error,
            raw_status=r.status, etag=self.md5s[r.body] if r.ok else "", length=r.nbytes)
            for r in climbed]
        if any(r.status != 200 for r in records):
            return records, tries
        made: list[list[MixedRequest]] = [[] for _ in range(self.clients)]

        def populate(c: int) -> None:
            for i in range(c, self.prepared, self.clients):
                r = self.request(c, "PUT", f"obj/{i:04d}", (self.seed + i) % len(self.bodies))
                made[c].append(r)
                if r.status != 200:
                    return
                self.pool.add(r.key, r.body)

        ts = [threading.Thread(target=populate, args=(c,)) for c in range(self.clients)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        records += [r for per in made for r in per]
        if all(r.status == 200 for r in records):
            r = S3Client(self.endpoint).admin("POST", "cache/clear")
            check(r.status == 200, f"cache/clear -> {r.status} {r.body[:200]!r}")
        return records, tries

    # -- the loop

    def _client(self, c: int) -> None:
        mine, rng = self._records[c], random.Random(f"{self.seed}/draw/{c}")
        lap = puts = 0
        while True:
            for op in self.hand(c, lap):
                if self._stop.is_set():
                    return
                mine.append(self._do(c, op, puts, rng))
                puts += op == "PUT"
            lap += 1

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

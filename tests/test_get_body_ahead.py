"""A GET's body is produced ahead of its socket (PR 35,
`server/object_handlers.py:send_body_ahead`): between the read path's iterator
and the response's writer stands a queue of pieces, at most one read window
(8 MiB) ahead of what was written. Held here by events and counts, never by a
time: bytes, order and Content-Length of whole and ranged bodies on both read
paths; the next() for piece k+1 starts before the write of piece k ends; the
producer is never more than the budget ahead of `_tx`; a hang-up, a write
error and a cancellation stop the pull, wait for the next() in flight,
close the iterator and free the namespace lock; a read-path error after the
headers aborts the response and leaves nobody waiting; a one-piece and an
empty body cost the pool what the serial loop cost it; the two phases and the
counter are on `/api/tpu` from the first scrape. CPU, seeded."""

import asyncio
import http.client
import os
import sys
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench.procs import parse_metrics  # noqa: E402
from minio_tpu import obs  # noqa: E402
from minio_tpu.client import S3Client  # noqa: E402
from minio_tpu.erasure import set as eset  # noqa: E402
from minio_tpu.erasure.quorum import QuorumError  # noqa: E402
from minio_tpu.server import object_handlers as oh  # noqa: E402

from test_s3_api import ServerThread  # noqa: E402

MIB = 1 << 20
BUDGET = oh.BODY_AHEAD_BYTES
BUCKET = "bodyahead"
WAIT_S = 20.0  # no test waits this long unless it is about to fail


# ---- stand-ins: an iterator that tells what happens to it, a client ---------


class Pieces:
    """What `handle.read()` gives the front end, with its life on record:
    every next() as it starts and ends, whether two ever ran at once, how
    far production is ahead of `request["_tx"]`, and what close() found."""

    def __init__(self, sizes, request, raise_at=None, gate=None):
        self.sizes, self.request = sizes, request
        self.raise_at, self.gate = raise_at, gate or {}
        self.events: list = []
        self.started = [threading.Event() for _ in range(len(sizes) + 1)]
        self.finished = [threading.Event() for _ in range(len(sizes) + 1)]
        self.produced = self.calls = self.running = 0
        self.overlapped = self.closed_while_running = False
        self.closed = 0
        self.max_ahead = 0
        self._gen = self._run()

    def _run(self):
        try:
            for k, n in enumerate(self.sizes):
                if k in self.gate:  # a read window that takes its time
                    assert self.gate[k].wait(WAIT_S)
                if k == self.raise_at:
                    raise QuorumError("window lost quorum")
                yield bytes([k % 251]) * n
        finally:
            self.events.append(("finally",))

    def body(self) -> bytes:
        return b"".join(bytes([k % 251]) * n for k, n in enumerate(self.sizes))

    def __iter__(self):
        return self

    def __next__(self):
        k = self.calls
        self.calls += 1
        self.overlapped |= self.running > 0
        self.running += 1
        self.events.append(("next_start", k))
        self.started[k].set()
        try:
            piece = next(self._gen)
            self.produced += len(piece)
            self.max_ahead = max(self.max_ahead, self.produced - self.request["_tx"])
            return piece
        finally:
            self.running -= 1
            self.events.append(("next_end", k))
            self.finished[k].set()

    def close(self):
        self.closed_while_running |= self.running > 0
        self.closed += 1
        self.events.append(("close",))
        self._gen.close()


async def until(event: threading.Event) -> None:
    """A thread's event, awaited without holding the loop."""
    deadline = time.monotonic() + WAIT_S
    while not event.is_set():
        assert time.monotonic() < deadline, "waited for an event that never came"
        await asyncio.sleep(0.001)


class Client:
    """The response: `write(piece)` may wait for something before it takes
    the piece (`before(k)`), and fails at piece `fail_at`."""

    def __init__(self, events, before=None, fail_at=None):
        self.events, self.before, self.fail_at = events, before, fail_at
        self.got: list[bytes] = []

    async def write(self, piece):
        k = len(self.got)
        self.events.append(("write_start", k))
        if self.before is not None:
            await self.before(k)
        if k == self.fail_at:
            raise ConnectionResetError("Cannot write to closing transport")
        self.got.append(bytes(piece))
        self.events.append(("write_end", k))


class CountingPool(ThreadPoolExecutor):
    submissions = 0

    def submit(self, fn, /, *args, **kwargs):
        self.submissions += 1
        return super().submit(fn, *args, **kwargs)


def send(it, client, request, pieces=None, pool=None):
    """`send_body_ahead` to its end on a loop of its own, bounded."""
    pieces = {"1": 0, "0": 0} if pieces is None else pieces

    async def main():
        own = pool or ThreadPoolExecutor(max_workers=4)
        try:
            await asyncio.wait_for(
                oh.send_body_ahead(own, it, client, request, pieces), WAIT_S)
        finally:
            if pool is None:
                own.shutdown(wait=True)

    asyncio.run(main())
    return pieces


def phase_calls(*names):
    snap = obs.phases_snapshot()
    return [snap["get", n][2] for n in names]


# ---- the mechanism, on stand-ins --------------------------------------------


def test_the_next_piece_is_in_production_before_the_last_ones_write_ends():
    request = {}
    it = Pieces([MIB] * 64, request)

    async def before(k):  # the client takes piece k only once next() k+1 began
        await until(it.started[k + 1])
        await until(it.finished[k + 1])
        await asyncio.sleep(0.005)  # and, as a rule, is on the queue

    client = Client(it.events, before)
    w0 = phase_calls("body_wait", "body_write")
    pieces = send(it, client, request)
    assert b"".join(client.got) == it.body() and request["_tx"] == 64 * MIB
    ev = it.events
    for k in range(63):
        assert ev.index(("next_start", k + 1)) < ev.index(("write_end", k))
    assert not it.overlapped  # one thread at a time advances the iterator
    assert it.calls == 65 and it.closed == 1 and not it.closed_while_running
    assert pieces["1"] + pieces["0"] == 64 and pieces["1"] > pieces["0"]
    assert [a - b for a, b in zip(phase_calls("body_wait", "body_write"), w0)] == [64, 64]
    assert it.max_ahead <= BUDGET


@pytest.mark.parametrize("sizes", [[MIB] * 24, [MIB // 2] + [MIB] * 20 + [MIB // 3],
                                   [300_000] * 40], ids=["whole", "ranged", "small-pieces"])
def test_a_slow_client_stalls_the_producer_one_window_ahead_and_no_further(sizes):
    request = {}
    it = Pieces(sizes, request)
    full = 0
    while sum(sizes[:full + 1]) + max(sizes[:full + 1]) <= BUDGET:
        full += 1
    full += 1  # the piece that fills the budget: no room for another as large
    stalled = []

    async def before(k):
        if k == 0:  # the client takes nothing: production runs up to the budget
            await until(it.finished[full - 1])
            await asyncio.sleep(0.1)
            stalled.append((it.calls, it.produced))

    client = Client(it.events, before)
    send(it, client, request)
    assert stalled == [(full, sum(sizes[:full]))] and sum(sizes[:full]) <= BUDGET
    assert b"".join(client.got) == it.body() and request["_tx"] == sum(sizes)
    assert BUDGET - max(sizes) < it.max_ahead <= BUDGET


@pytest.mark.parametrize("how", ["write-error", "cancelled"])
def test_a_hang_up_waits_for_the_next_in_flight_then_closes_the_iterator(how):
    request = {}
    release = threading.Event()
    it = Pieces([MIB] * 64, request, gate={6: release})
    pieces = {"1": 0, "0": 0}
    seen = {}

    async def main():
        loop = asyncio.get_running_loop()
        stuck = asyncio.Event()

        async def before(k):
            if k == 3:  # pieces 0..2 left; next() 6 is inside its read window
                await until(it.started[6])
                seen["running"] = it.running
                loop.call_later(0.05, release.set)
                if how == "cancelled":
                    stuck.set()
                    await asyncio.sleep(WAIT_S)

        client = Client(it.events, before, fail_at=3 if how == "write-error" else None)
        with ThreadPoolExecutor(max_workers=4) as pool:
            task = asyncio.create_task(oh.send_body_ahead(pool, it, client, request, pieces))
            if how == "cancelled":
                await asyncio.wait_for(stuck.wait(), WAIT_S)
                task.cancel()
            with pytest.raises(ConnectionResetError if how == "write-error"
                               else asyncio.CancelledError):
                await asyncio.wait_for(task, WAIT_S)
            seen["calls_at_return"] = it.calls

    asyncio.run(main())
    assert seen["running"] == 1
    ev = it.events
    # the next() in flight ended before anything was closed; then the
    # iterator's `finally` ran; no next() started after the hang-up
    assert ev.index(("next_end", 6)) < ev.index(("close",)) < ev.index(("finally",))
    assert it.closed == 1 and not it.closed_while_running and it.running == 0
    assert it.calls == seen["calls_at_return"] == 7
    assert request["_tx"] == 3 * MIB < it.produced  # what left, not what was queued
    assert pieces["1"] + pieces["0"] == 4


def test_a_read_path_error_after_piece_3_surfaces_where_the_body_stops():
    request = {}
    it = Pieces([MIB] * 16, request, raise_at=3)
    client = Client(it.events)
    with pytest.raises(QuorumError, match="window lost quorum"):
        send(it, client, request)
    assert b"".join(client.got) == it.body()[:3 * MIB] and request["_tx"] == 3 * MIB
    assert it.calls == 4 and it.closed == 1 and it.running == 0
    assert ("finally",) in it.events


@pytest.mark.parametrize("sizes,submissions", [([], 1), ([70_000], 2), ([MIB] * 64, 65)],
                         ids=["empty", "one-piece", "64-pieces"])
def test_a_body_costs_the_pool_what_the_serial_loop_cost_it(sizes, submissions):
    """The parent's loop: one submission a piece and one for the end."""
    request = {}
    it = Pieces(sizes, request)
    client = Client(it.events)
    with CountingPool(max_workers=4) as pool:
        pieces = send(it, client, request, pool=pool)
        # (as many where the writer asks for every piece at a full budget;
        # one where production never met it)
        assert 1 <= pool.submissions <= submissions == it.calls
    assert b"".join(client.got) == it.body() and request["_tx"] == sum(sizes)
    assert pieces["1"] + pieces["0"] == len(sizes) and it.closed == 1


def test_many_bodies_at_once_lose_no_wake_up_and_no_byte():
    """More bodies than pool threads, threads switched every 10 us, clients
    that now and then stall long enough for the budget to fill: a pull that
    returned at a full budget is always submitted again (a lost wake-up
    would leave a body waiting until the bound), and no count is torn."""
    sizes = [64 << 10] * 200  # 12.5 MiB a body: past the budget
    bodies = []

    async def one(i, pool, pieces):
        request = {}
        it = Pieces(sizes, request)

        async def before(k):
            await asyncio.sleep(0.02 if k % 67 == i else 0)

        client = Client(it.events, before)
        await oh.send_body_ahead(pool, it, client, request, pieces)
        bodies.append((it, client, request))

    async def main():
        pieces = {"1": 0, "0": 0}
        with CountingPool(max_workers=4) as pool:
            await asyncio.wait_for(
                asyncio.gather(*(one(i, pool, pieces) for i in range(16))), 4 * WAIT_S)
            assert pool.submissions > 16  # budgets did fill, and pulls were resubmitted
        return pieces

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pieces = asyncio.run(main())
    finally:
        sys.setswitchinterval(was)
    assert len(bodies) == 16 and pieces["1"] + pieces["0"] == 16 * len(sizes)
    for it, client, request in bodies:
        assert b"".join(client.got) == it.body() and request["_tx"] == sum(sizes)
        assert not it.overlapped and it.max_ahead <= BUDGET
        assert it.calls == len(sizes) + 1 and it.closed == 1 and not it.closed_while_running


def test_the_budget_is_one_read_window_of_stripe_blocks():
    """The shipped read window (MINIO_TPU_READ_WINDOW, 8 blocks) of the 1 MiB
    stripe block: a constant of the code, with no variable of its own."""
    from minio_tpu.erasure.coder import BLOCK_SIZE

    assert BUDGET == 8 * BLOCK_SIZE == 8 * MIB
    assert not [k for k in os.environ if "BODY_AHEAD" in k]


# ---- served: real read paths, a real socket ---------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_BACKEND", "numpy")
    mp.setenv("MINIO_TPU_SCAN_INTERVAL", "0")
    mp.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
    mp.delenv("MINIO_COMPRESSION_ENABLE", raising=False)
    base = tmp_path_factory.mktemp("bodyahead-drives")
    st = ServerThread([str(base / f"d{i}") for i in range(4)])
    try:
        cli = S3Client(f"127.0.0.1:{st.port}")
        first = cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode()
        assert cli.make_bucket(BUCKET).status == 200
        body = np.random.default_rng([2 ** 31 + 35, 1]).bytes(64 * MIB)
        for key, data in (("big", body), ("one", body[:70_000]), ("empty", b"")):
            assert cli.request("PUT", f"/{BUCKET}/{key}", body=data,
                               unsigned_payload=True).status == 200
        yield st, cli, body, first
    finally:
        # the segment cache is the process's: 64 MiB reads leave entries in
        # its disk tier that a later file of this worker would count as its own
        cli.admin("POST", "cache/clear")
        st.stop()
        mp.undo()


def scrape(cli) -> dict:
    return parse_metrics(cli.request("GET", "/minio/metrics/v3/api/tpu").body.decode())


def total(series, name, **match):
    return sum(v for labels, v in series.get(name, [])
               if all(labels.get(k) == w for k, w in match.items()))


def test_the_two_phases_and_both_labels_are_on_the_first_scrape(served):
    *_, first = served
    for name in ("body_wait", "body_write"):
        for series in ("seconds", "cpu_seconds", "calls"):
            assert f'minio_tpu_phase_{series}_total{{layer="get",phase="{name}"}} ' in first
    assert 'minio_tpu_get_pieces_total{ahead="1"} 0\n' in first + "\n"
    assert 'minio_tpu_get_pieces_total{ahead="0"} 0\n' in first + "\n"
    assert {"body_wait", "body_write"} <= set(obs.PHASES["get"])


@pytest.mark.parametrize("native", ["1", "0"], ids=["native", "windowed"])
@pytest.mark.parametrize("rng", [None, (500_000, 64 * MIB - 300_001), (MIB, 3 * MIB - 1)],
                         ids=["whole", "ranged-partial-ends", "ranged-aligned"])
def test_a_served_body_arrives_byte_for_byte_and_in_order(served, monkeypatch, native, rng):
    _, cli, body, _ = served
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", native)
    assert cli.admin("POST", "cache/clear").status == 200
    before = scrape(cli)
    headers = {"Range": f"bytes={rng[0]}-{rng[1]}"} if rng else None
    r = cli.get_object(BUCKET, "big", headers=headers)
    want = body[rng[0]:rng[1] + 1] if rng else body
    assert r.status == (206 if rng else 200)
    assert int(r.headers["content-length"]) == len(want) and r.body == want
    after = scrape(cli)
    path = "native" if native == "1" else "windowed"
    assert total(after, "minio_tpu_get_bytes_total", path=path) \
        - total(before, "minio_tpu_get_bytes_total", path=path) == len(want)
    # every piece of the body went through the writer's two phases, whichever
    # path produced it: a stripe block a piece
    blocks = (rng[1] // MIB - rng[0] // MIB + 1) if rng else 64
    moved = total(after, "minio_tpu_get_pieces_total") \
        - total(before, "minio_tpu_get_pieces_total")
    if native == "1" and rng and rng[0] % MIB:
        # (a native span is cut into 1 MiB pieces from ITS start, so a range
        # that begins inside a block ends every span on a short piece)
        assert blocks <= moved <= blocks + 4
    else:
        assert moved == blocks
    for name in ("body_wait", "body_write"):
        assert total(after, "minio_tpu_phase_calls_total", layer="get", phase=name) \
            - total(before, "minio_tpu_phase_calls_total", layer="get", phase=name) == moved
        assert total(after, "minio_tpu_phase_cpu_seconds_total", layer="get", phase=name) == 0


@pytest.mark.parametrize("key,size", [("one", 70_000), ("empty", 0)])
def test_small_bodies_are_served_as_before_from_the_drives_and_from_the_cache(served, key, size):
    _, cli, body, _ = served
    assert cli.admin("POST", "cache/clear").status == 200
    for _ in range(2):  # the second from the data cache's handle
        r = cli.get_object(BUCKET, key)
        assert r.status == 200 and r.body == body[:size]
        assert int(r.headers["content-length"]) == size


@pytest.fixture
def recorded(monkeypatch):
    """Every `send_body_ahead` of the server, with what it was given and
    how it ended."""
    calls = []
    real = oh.send_body_ahead

    async def recording(pool, it, resp, request, pieces):
        call = {"it": it, "request": request, "ended": threading.Event(), "error": None}
        calls.append(call)
        try:
            await real(pool, it, resp, request, pieces)
        except BaseException as e:
            call["error"] = e
            raise
        finally:
            call["ended"].set()

    monkeypatch.setattr(oh, "send_body_ahead", recording)
    return calls


def presigned(cli, key):
    url = urllib.parse.urlsplit(cli.presign("GET", BUCKET, key, expires=600))
    return f"{url.path}?{url.query}"


@pytest.mark.parametrize("native", ["1", "0"], ids=["native", "windowed"])
def test_a_client_that_hangs_up_mid_body_leaves_the_lock_free(served, monkeypatch, recorded,
                                                              native):
    st, cli, body, _ = served
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", native)
    assert cli.admin("POST", "cache/clear").status == 200
    assert cli.request("PUT", f"/{BUCKET}/hangup", body=body, unsigned_payload=True).status == 200
    conn = http.client.HTTPConnection("127.0.0.1", st.port, timeout=WAIT_S)
    conn.request("GET", presigned(cli, "hangup"))
    resp = conn.getresponse()
    assert resp.status == 200 and resp.read(3 * MIB) == body[:3 * MIB]
    conn.sock.shutdown(2)
    conn.close()
    call = recorded[-1]
    assert call["ended"].wait(WAIT_S)  # the request ended
    it = call["it"]
    assert it.gi_frame is None and not it.gi_running  # closed: its `finally`s ran
    assert call["error"] is not None
    assert 3 * MIB <= call["request"]["_tx"] < len(body)  # what left, not 64 MiB
    # the namespace read lock is free: a PUT to the key goes through at once
    r = cli.request("PUT", f"/{BUCKET}/hangup", body=b"again", timeout=WAIT_S)
    assert r.status == 200
    assert cli.get_object(BUCKET, "hangup").body == b"again"


def test_a_read_path_error_after_the_headers_aborts_the_response(served, monkeypatch, recorded):
    st, cli, body, _ = served
    assert cli.admin("POST", "cache/clear").status == 200
    assert cli.request("PUT", f"/{BUCKET}/torn", body=body[:16 * MIB],
                       unsigned_payload=True).status == 200
    real = eset.ErasureSet._read_range_inner

    def torn(self, *a, **kw):
        for k, piece in enumerate(real(self, *a, **kw)):
            if k == 3:
                raise QuorumError("window 5 lost quorum")
            yield piece

    monkeypatch.setattr(eset.ErasureSet, "_read_range_inner", torn)
    conn = http.client.HTTPConnection("127.0.0.1", st.port, timeout=WAIT_S)
    try:
        conn.request("GET", presigned(cli, "torn"))
        resp = conn.getresponse()
        assert resp.status == 200 and int(resp.headers["Content-Length"]) == 16 * MIB
        with pytest.raises(http.client.IncompleteRead) as short:
            resp.read()
        assert short.value.partial == body[:3 * MIB]  # the pieces before it, then the abort
    finally:
        conn.close()
    # the repo's own client reports the short body as a connection error
    with pytest.raises(ConnectionResetError, match="cut short after 3145728 bytes"):
        cli.get_object(BUCKET, "torn")
    call = recorded[-1]
    assert call["ended"].wait(WAIT_S) and isinstance(call["error"], QuorumError)
    assert call["request"]["_tx"] == 3 * MIB and call["it"].gi_frame is None
    monkeypatch.undo()
    r = cli.request("PUT", f"/{BUCKET}/torn", body=b"again", timeout=WAIT_S)
    assert r.status == 200  # the lock is free

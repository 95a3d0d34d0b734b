"""Span core: contextvar-propagated request ids + typed trace spans.

Zero-cost-when-idle contract (the reference checks NumSubscribers before
building a record): ``span()`` returns the shared ``NOOP_SPAN`` singleton
— no Span object, no field dict copy, no clock read — unless a publisher
is attached AND it has subscribers. Code on the hot path may therefore
open spans unconditionally.

``phase()`` is the always-on sibling: it adds wall seconds, thread CPU
seconds and one call to a process-global table whether or not anyone
subscribes (``phases_snapshot()``; exported on ``/api/tpu``), publishes
the same ``Span`` record when someone does, and — for the leaf phases
of the threads that feed the device (a streaming PUT's request thread,
the dispatch thread's work, the device decode's and a reconstructing
GET's own) — enters a ``jax.profiler.TraceAnnotation`` so the phase lands
in a device trace on the profiler's own clock.

The request context is a ``contextvars.ContextVar`` so it survives both
``await`` hops and executor hops (``ContextPool``/``bind_context`` copy
the context across thread boundaries; storage-REST carries it in an
``x-minio-reqid`` header / grid payload field between nodes).
"""

from __future__ import annotations

import contextvars
import itertools
import os
import socket
import sys
import threading
import time
from contextlib import contextmanager

TYPE_S3 = "s3"
TYPE_INTERNAL = "internal"
TYPE_STORAGE = "storage"
TYPE_TPU = "tpu"
TYPE_HEAL = "heal"
TYPE_SCANNER = "scanner"
TYPE_FAULT = "fault"
TYPE_SANITIZER = "sanitizer"
TYPE_PLACEMENT = "placement"
TYPE_REBALANCE = "rebalance"
TYPE_DIAG = "diag"
TRACE_TYPES = frozenset(
    {TYPE_S3, TYPE_INTERNAL, TYPE_STORAGE, TYPE_TPU, TYPE_HEAL,
     TYPE_SCANNER, TYPE_FAULT, TYPE_SANITIZER, TYPE_PLACEMENT,
     TYPE_REBALANCE, TYPE_DIAG}
)

# (request_id, parent_span_id); spans nest by swapping the second slot
_CTX: contextvars.ContextVar[tuple[str, int] | None] = contextvars.ContextVar(
    "minio_tpu_trace_ctx", default=None
)

_span_ids = itertools.count(1)

# the publishing TracePubSub (server/metrics.py) — module-level because
# spans open deep in layers (dispatcher, storage wrappers) that have no
# server reference; one process serves one node
_publisher = None

NODE = socket.gethostname()


def set_publisher(pub) -> None:
    global _publisher
    _publisher = pub


def publisher():
    return _publisher


def active() -> bool:
    p = _publisher
    return p is not None and p.active


def new_request_id() -> str:
    """An ``x-amz-request-id`` value: 16 uppercase hex chars (the
    reference's mustGetRequestID is a time-based variant of the same)."""
    return os.urandom(8).hex().upper()


def set_request(request_id: str):
    """Install `request_id` as the current trace context; returns the
    token for ``reset_request``. Used at plane entries (S3 entry,
    storage-REST server side); everything below inherits via contextvar
    propagation."""
    return _CTX.set((request_id, 0))


def reset_request(token) -> None:
    _CTX.reset(token)


@contextmanager
def request_context(request_id: str):
    token = _CTX.set((request_id, 0))
    try:
        yield
    finally:
        _CTX.reset(token)


def current_request_id() -> str:
    ctx = _CTX.get()
    return ctx[0] if ctx is not None else ""


def bind_context(fn):
    """Wrap `fn` so it runs under a snapshot of the CURRENT context —
    for handing work to executors that don't propagate contextvars
    (``loop.run_in_executor`` does not)."""
    ctx = contextvars.copy_context()
    return lambda *a, **kw: ctx.run(fn, *a, **kw)


def publish(record: dict) -> None:
    """Publish a pre-built record if anyone is listening (cheap guard
    for non-span record sites like the dispatcher's batch records)."""
    p = _publisher
    if p is not None and p.active:
        p.publish(record)


def _record(trace_type: str, name: str, req_id: str, span_id: int,
            parent_id: int, dur_s: float, error: str = "") -> dict:
    return {
        "time": time.time(),
        "type": trace_type,
        "name": name,
        "reqId": req_id,
        "spanId": span_id,
        "parentId": parent_id,
        "node": NODE,
        "durationNs": int(dur_s * 1e9),
        "error": error,
    }


class Span:
    """One timed, typed trace record; context-manager only (see the
    ``span`` miniovet rule). Publishes on exit with the error captured
    from a propagating exception; never swallows it."""

    __slots__ = (
        "trace_type", "name", "fields", "req_id", "span_id", "parent_id",
        "_t0", "_token",
    )

    def __init__(self, trace_type: str, name: str, fields: dict):
        self.trace_type = trace_type
        self.name = name
        self.fields = fields
        ctx = _CTX.get()
        self.req_id = ctx[0] if ctx is not None else ""
        self.parent_id = ctx[1] if ctx is not None else 0
        self.span_id = next(_span_ids)
        self._t0 = 0.0
        self._token = None

    def __enter__(self) -> "Span":
        self._token = _CTX.set((self.req_id, self.span_id))
        self._t0 = time.perf_counter()
        return self

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self._t0
        if self._token is not None:
            try:
                _CTX.reset(self._token)
            except ValueError:
                # generator spans may enter and exit under different
                # context COPIES (each executor hop snapshots its own);
                # the copy dies with the task, so a failed reset leaks
                # nothing
                pass
        p = _publisher
        if p is not None and p.active:
            rec = _record(
                self.trace_type, self.name, self.req_id, self.span_id,
                self.parent_id, dur,
                "" if exc is None else f"{type(exc).__name__}: {exc}",
            )
            rec.update(self.fields)
            p.publish(rec)
        return False  # propagate exceptions


class _NoopSpan:
    """Shared do-nothing span for the no-subscribers path; identity is
    asserted by the zero-overhead test."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def set(self, **fields) -> None:
        pass


NOOP_SPAN = _NoopSpan()


def span(trace_type: str, name: str, **fields):
    """A span of `trace_type` (one of TRACE_TYPES) for use in a ``with``
    statement. Returns NOOP_SPAN unless tracing is active."""
    p = _publisher
    if p is None or not p.active:
        return NOOP_SPAN
    return Span(trace_type, name, fields)


# -- phases: the always-on aggregate --------------------------------------

# every (layer, phase) the program books, pre-seeded so a scrape never
# sees the table change size. `dispatch` phases are leaves that tile the
# dispatch thread's time (parallel/dispatcher.py); `put` phases run on the
# request thread of a streaming PUT (erasure/set.py, erasure/coder.py),
# except `drive_io`, which the drive pool's threads book. Nothing books
# `dispatch`/`frame` any more (it was the data + parity concatenate): the
# row stays at zero so that readers of the table keep finding it.
# `get` phases run on the thread that serves a GET on the reconstructing
# (windowed) read path (erasure/set.py): `start` (once per such read:
# the first window's shard reads submitted), `read_wait` (a window's shard
# reads until every block has d shards, hedges included), `stack`
# (a decode group's survivors written once, a strided copy per shard and
# run, in the layout its rung takes: the mega-kernel's chunk-major input
# with its pad rows zeroed, else [d, W, per]), `decode_wait` (the whole
# reconstruct_data_flat call; the `decode` leaves tile it), `join` (the
# per-block gather-join copy), `cache_fill` (the block offered to the
# range-segment cache), `respond` (yield -> the front end's producer
# calls the next next(): the executor hop and, since the body is produced
# one read window ahead of the socket, the time the producer stood at a
# full budget; wall only, the thread may change), and
# `shard_io` on the read pool's threads (a run of frames: read_file + verify_run).
# `native` is the healthy GET's own: the native span reads of one read
# (pread + bitrot verify + assembly in one C++ pass, 16 MiB a span), summed
# and booked as ONE call when that read's native part ends.
# `body_wait` and `body_write` are the front end's (server/object_handlers.py
# get_object), booked per piece of EVERY GET body on the event loop, wall
# only: the response's writer waiting for the next piece (production that
# ran under no write) and inside `await resp.write(piece)`.
# `decode` phases are the leaves of one device reconstruct
# (ops/bitrot_jax.py, erasure/coder.py): `pad` (survivors made
# block-major and zero-padded to the kernel's batch), `pack` (neither
# runs for a group `stack` laid out packed), `h2d`,
# `kernel` (call -> ready; a first call's trace-and-lower too), `d2h`,
# `unpack`; `host` is a group rebuilt by the native/numpy GF apply.
# `op` phases are the S3 operation as its handler sees it (server/app.py):
# wall from the parsed, authorized request to the finished response, one
# row per kind of object request, booked on the event loop, wall only —
# seconds / calls is "per operation", calls / window the operations rate.
# `stat` is `ErasureSet.get_object_info` on the I/O pool's thread: `info`
# the whole call — a HEAD, and whoever else stats an object: a PUT's and a
# DELETE's handler look the key up first (tier sweep, object lock) —
# `meta_read` the quorum read of xl.meta on every drive of the set, which
# only a miss of the FileInfo cache reaches. `delete` phases tile `delete_object`
# (erasure/set.py) on the same thread: `lock_wait` (the namespace write
# lock; a PhaseClock around the bare acquisition), `drive_delete` (`delete_version` on every drive and their join),
# `invalidate` (the caches, the broadcast). `trash`/`reclaim` is one entry
# of `<drive>/.minio.sys/trash` removed by that drive's reclaimer thread
# (storage/xlstorage.py), off every request's path.
PHASES = {
    "dispatch": ("wait", "window", "assemble", "pack", "h2d", "kernel",
                 "d2h", "unpack", "frame", "numpy", "fanout"),
    "put": ("ingest", "stage", "encode_wait", "frame", "md5",
            "drive_write", "commit", "drive_io"),
    "get": ("start", "read_wait", "stack", "decode_wait", "join",
            "cache_fill", "respond", "shard_io", "native",
            "body_wait", "body_write"),
    "decode": ("pad", "pack", "h2d", "kernel", "d2h", "unpack", "host"),
    "op": ("get_object", "head_object", "put_object", "delete_object"),
    "stat": ("info", "meta_read"),
    "delete": ("lock_wait", "drive_delete", "invalidate"),
    "trash": ("reclaim",),
}
_PHASE_TYPES = {"dispatch": TYPE_TPU, "put": TYPE_INTERNAL,
                "get": TYPE_INTERNAL, "decode": TYPE_TPU,
                "op": TYPE_INTERNAL, "stat": TYPE_INTERNAL,
                "delete": TYPE_INTERNAL, "trash": TYPE_STORAGE}
# the phases that go to the profiler: leaves of the threads that feed the
# device, so that an idle gap of a device trace is named by the work the
# host was doing. An enclosing phase (`put`/`encode_wait`,
# `get`/`decode_wait`) would win every idle gap and say nothing, and so
# would a wait for work (`dispatch`/`wait`, `window`: the dispatch thread
# with nothing to do); the pools' threads (`drive_io`, `shard_io`) would
# bury it in events; the event loop's (`body_wait`, `body_write`, every
# `op` row) span awaits during which the loop serves other requests; a
# stopwatch across a yield (`get`/`respond`, `start`) may end on another
# thread than it began on, which a TraceAnnotation cannot.
_ANNOTATED = {
    "dispatch": frozenset(PHASES["dispatch"]) - {"wait", "window"},
    "put": frozenset({"ingest", "stage", "frame", "md5", "drive_write", "commit"}),
    "decode": frozenset(PHASES["decode"]),
    "get": frozenset(PHASES["get"])
    - {"decode_wait", "shard_io", "body_wait", "body_write", "start", "respond"},
    "stat": frozenset({"meta_read"}),
    "delete": frozenset(PHASES["delete"]) - {"lock_wait"},
    "trash": frozenset(PHASES["trash"]),
}
_phase_mu = threading.Lock()
# (layer, name) -> [wall seconds, thread CPU seconds, calls]
_phase_table: dict[tuple[str, str], list] = {
    (layer, name): [0.0, 0.0, 0]
    for layer, names in PHASES.items() for name in names
}


def phases_snapshot() -> dict[tuple[str, str], tuple[float, float, int]]:
    """{(layer, phase): (wall_s, cpu_s, calls)} since process start, taken
    under the one lock the writers hold, so no row is ever torn."""
    with _phase_mu:
        return {k: (v[0], v[1], v[2]) for k, v in _phase_table.items()}


def _phase_book(row: list, wall_s: float, cpu_s: float) -> None:
    with _phase_mu:
        row[0] += wall_s
        row[1] += cpu_s
        row[2] += 1


class PhaseClock:
    """A stopwatch for phase time that no ``with`` block can hold — a
    generator's ingest between two yields. ``book()`` adds the wall and
    thread CPU seconds since the last ``restart()`` (or construction) as
    one call, and publishes the record a ``phase()`` would when tracing is
    active; ``restart()`` after the yield leaves the consumer's time out.
    An annotated (layer, name) makes each stretch from ``restart()`` to
    ``book()`` one TraceAnnotation, as ``PhaseSum`` does: only a stretch
    that stays on one thread may be."""

    __slots__ = ("_layer", "_name", "_row", "_ann", "_t0", "_c0")

    def __init__(self, layer: str, name: str):
        self._layer, self._name = layer, name
        self._row = _phase_table[(layer, name)]
        self._ann = None
        self.restart()

    def restart(self) -> None:
        if _annotated(self._layer, self._name):
            self._ann = _trace_annotation(f"{self._layer}.{self._name}")
            if self._ann is not None:
                self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.monotonic()

    def book(self, cpu: bool = True) -> None:
        """`cpu=False` books wall seconds only: for a stretch that may end
        on another thread than it began on (a generator resumed by an
        executor), where a thread's CPU clock says nothing."""
        wall = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        _phase_book(
            self._row, wall, time.thread_time() - self._c0 if cpu else 0.0
        )
        p = _publisher
        if p is not None and p.active:
            req_id, parent_id = _CTX.get() or ("", 0)
            p.publish(_record(
                _PHASE_TYPES[self._layer], f"{self._layer}.{self._name}",
                req_id, next(_span_ids), parent_id, wall,
            ))


class PhaseSum:
    """Several stretches of one phase booked as ONE call: the native span
    reads of a healthy GET, a `with` block each, between which the
    generator yields to the front end and may change threads. Each stretch
    adds its wall and thread CPU seconds (and is a TraceAnnotation like a
    `phase()` leaf); `book()` books the sums as one call, so a reader of
    the table divides by calls for "per GET". Nothing is booked where no
    stretch ran."""

    __slots__ = ("_row", "_label", "_ann", "_wall", "_cpu", "_n", "_t0", "_c0")

    def __init__(self, layer: str, name: str):
        self._row = _phase_table[(layer, name)]
        self._label = f"{layer}.{name}"
        self._ann = None
        self._wall = self._cpu = 0.0
        self._n = 0
        self._t0 = self._c0 = 0.0

    def __enter__(self) -> "PhaseSum":
        self._ann = _trace_annotation(self._label)
        if self._ann is not None:
            self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._wall += time.monotonic() - self._t0
        self._cpu += time.thread_time() - self._c0
        self._n += 1
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False

    def book(self) -> None:
        if self._n:
            _phase_book(self._row, self._wall, self._cpu)
            self._wall = self._cpu = 0.0
            self._n = 0


def _annotated(layer: str, name: str) -> bool:
    """Whether the (layer, phase) goes to the profiler (`_ANNOTATED`)."""
    return name in _ANNOTATED.get(layer, ())


def _trace_annotation(label: str):
    """A profiler TraceMe, only where jax is already loaded: the phase
    clock must never be what imports jax into a CPU-plane process. With no
    trace being recorded a TraceMe costs one atomic load."""
    jax = sys.modules.get("jax")
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    return cls(label) if cls is not None else None


class Phase:
    """One timed entry of a (layer, phase) row; context-manager only, like
    ``Span`` (the ``span`` miniovet rule covers both)."""

    __slots__ = ("_row", "_name", "_into", "_span", "_ann", "_t0", "_c0")

    def __init__(self, layer: str, name: str, into: dict | None, fields: dict):
        self._row = _phase_table[(layer, name)]
        self._name = name
        self._into = into
        p = _publisher
        self._span = (
            Span(_PHASE_TYPES[layer], f"{layer}.{name}", fields)
            if p is not None and p.active else None
        )
        # only leaves go to the profiler (_ANNOTATED): a reader that names
        # a device-idle gap by the host event covering most of it would
        # see nothing but an enclosing request-thread phase
        self._ann = (
            _trace_annotation(f"{layer}.{name}")
            if _annotated(layer, name) else None
        )
        self._t0 = self._c0 = 0.0

    def __enter__(self) -> "Phase":
        if self._span is not None:
            self._span.__enter__()
        if self._ann is not None:
            self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        wall = time.monotonic() - self._t0
        cpu = time.thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        _phase_book(self._row, wall, cpu)
        if self._into is not None:
            self._into[self._name] = self._into.get(self._name, 0.0) + wall
        return False  # propagate exceptions


def phase(layer: str, name: str, into: dict | None = None, **fields) -> Phase:
    """A phase of PHASES[layer] for use in a ``with`` statement. Always
    books wall seconds, thread CPU seconds and a call; `into`, when given,
    also gets the wall seconds added under `name` (the dispatcher sums one
    dispatch's phases that way). An unknown (layer, name) is a KeyError."""
    return Phase(layer, name, into, fields)

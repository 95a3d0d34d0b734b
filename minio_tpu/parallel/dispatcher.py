"""TPU batching dispatcher — many requests, one device dispatch.

The north-star architecture (SURVEY.md §7, BASELINE.json): concurrent
PutObject calls each produce independent fixed-shape 1 MiB stripe blocks;
instead of one device call per block, a dispatcher thread packs every
block that arrives within a short window into a single fused
encode+bitrot dispatch ([B, d, n] -> parity + digests) and fans results
back to the waiting request threads. The reference's analogue is the
per-request AVX loop (cmd/erasure-encode.go:76) — batching is what the
accelerator changes about the architecture.

Result contract: a waiter gets (parity [k, p, n], digests) — what the
dispatch computed, as fresh arrays with C-contiguous rows — and frames
data rows from the [k, d, n] array it submitted. The dispatcher copies no
data byte back: the bucket arena is its own scratch and recycles after
every dispatch, whatever the waiters still hold.

Latency contract: a block waits at most `window` (default 2 ms) before
dispatch; an idle queue dispatches immediately. p99 PUT latency gains the
window; throughput gains the full batch width of the MXU/VPU.

Priority lanes (qos/): foreground blocks (S3 PUT/GET handlers) and
background blocks (heal, scanner, decommission, rebalance — marked via
``qos.background_context()``) queue separately. Batch assembly always
drains foreground first; background work rides along only in leftover
batch capacity, capped at a fraction of the batch so a bg-heavy dispatch
cannot stretch foreground latency, with starvation protection: a
background block older than ``MINIO_TPU_QOS_BG_MAX_AGE_MS`` promotes to
the foreground lane so saturating PUT traffic cannot park heals forever.
The ``fg_deferred_behind_bg`` stat witnesses the invariant that no
foreground block ever waits behind background batch slots (it stays 0).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from .. import obs
from ..fault import registry as fault_registry
from ..ops import runtime
from ..qos.context import (
    PRI_BACKGROUND,
    PRI_FOREGROUND,
    current_priority,
    in_prefetch,
)

# backend degradation ladder (fault/ tpu boundary): fused Pallas
# mega-kernel -> row-major XLA -> pure-numpy CPU. Repeated device faults
# demote; background probe batches re-promote once the device answers
# again. The numpy rung is byte-identical to the device rungs (the
# golden tests pin all three), so degraded mode changes latency, never
# payloads.
LEVEL_FUSED = 2
LEVEL_XLA = 1
LEVEL_NUMPY = 0

# fixed histogram edges (seconds) for the metrics-v3 /api/tpu group: the
# queue-wait edges bracket the 2 ms batch window, the device edges the
# sub-ms..100 ms kernel range; both run on to 32 s because on the chip a
# 256-block dispatch takes 1.6 s and its items wait 0.8 s (PERF.md §5)
_SLOW_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
QUEUE_WAIT_BUCKETS = (
    0.0005, 0.001, 0.002, 0.004, 0.008, 0.016, 0.05, 0.1, 0.5) + _SLOW_BUCKETS
DEVICE_TIME_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.5) + _SLOW_BUCKETS
# dispatched bucket sizes (blocks) — power-of-two padded, so the edges
# ARE the possible sizes; pre-seeded so the /api/tpu occupancy series
# can split pad waste from real batching from the first scrape
BUCKET_BLOCK_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
# the ladder's rungs as the first-call series label them
RUNGS = ("fused", "xla", "numpy")
# one dispatch's phases (obs.PHASES["dispatch"], `wait`/`window` aside):
# time against the device — relayout for it, transfers, the jitted call —
# and host work. device_s and host_s are these sums. `frame` was the
# data+parity concatenate; nothing runs under it since results are parity
# only, and its row stays so that the exported table keeps its shape.
DEVICE_PHASES = ("pack", "h2d", "kernel", "d2h", "unpack", "frame")
HOST_PHASES = ("assemble", "numpy", "fanout")


def _hist_add(hist: list[int], edges: tuple, v: float) -> None:
    for i, edge in enumerate(edges):
        if v <= edge:
            hist[i] += 1
            return
    hist[-1] += 1


class TpuDispatcher:
    """Batches fixed-shape [d, n] encode requests for one (d, p, n) shape."""

    def __init__(self, codec, n: int, window_s: float | None = None,
                 max_shards: int = 4096):
        from ..ops.bitrot_jax import encode_and_hash

        self.codec = codec
        self.n = n
        self.window = (
            float(os.environ.get("MINIO_TPU_BATCH_WINDOW_MS", "2")) / 1e3
            if window_s is None
            else window_s
        )
        # clamp to a power of two so _bucket padding can never overshoot
        # the HBM shard cap _collect enforces
        mb = max(1, max_shards // (codec.data_shards + codec.parity_shards))
        p2 = 1
        while p2 * 2 <= mb:
            p2 *= 2
        self.max_blocks = p2
        # background lane policy: bg blocks fill at most this many slots of
        # any one dispatch, and a bg block older than max_age promotes to
        # the foreground lane (starvation protection). Malformed env
        # values fall back to defaults — a QoS tuning typo must not take
        # down the encode plane (the dispatcher builds lazily on first PUT)
        try:
            frac = float(os.environ.get("MINIO_TPU_QOS_BG_FRACTION", "0.5"))
        except ValueError:
            frac = 0.5
        self.bg_max_blocks = max(1, min(self.max_blocks, int(self.max_blocks * frac)))
        try:
            self.bg_max_age = (
                float(os.environ.get("MINIO_TPU_QOS_BG_MAX_AGE_MS", "50")) / 1e3
            )
        except ValueError:
            self.bg_max_age = 0.05
        self._fused_enabled = (
            os.environ.get("MINIO_TPU_FUSED_CM", "1") != "0"
        )
        # transient device failures back off and re-probe instead of
        # disabling the kernel until restart (VERDICT r2 weak #3)
        self._fused_cooldown = 0   # dispatches to skip before re-probing
        self._fused_backoff = 8    # next cooldown length, doubles to a cap
        self._encode_and_hash = encode_and_hash
        # degradation ladder state: consecutive device (XLA-or-worse)
        # failures past the threshold demote to the numpy rung; a probe
        # batch every `probe_after` dispatches re-promotes. Malformed env
        # values fall back — a chaos tuning typo must not kill encodes.
        try:
            self._demote_threshold = int(
                os.environ.get("MINIO_TPU_BACKEND_DEMOTE_FAULTS", "3")
            )
        except ValueError:
            self._demote_threshold = 3
        try:
            self._probe_after = int(
                os.environ.get("MINIO_TPU_BACKEND_PROBE_AFTER", "16")
            )
        except ValueError:
            self._probe_after = 16
        self._device_fault_streak = 0
        self._probe_countdown = self._probe_after
        self._shape = f"{codec.data_shards}+{codec.parity_shards}"
        # lazy per-family numpy codecs: the rung only pays when reached
        self._np_codec: dict[str, object] = {}
        self._cv = threading.Condition()
        # lanes hold (blocks, fut, priority, t_enqueue); unconsumed items
        # stay at the head, so no separate carry slot is needed
        self._fg: deque = deque()
        self._bg: deque = deque()
        # every key pre-seeded: observers (aggregate_stats, metrics) read
        # this dict from other threads, and a lazily-inserted key would
        # race their iteration ("dict changed size during iteration")
        self.stats = {
            "dispatches": 0, "blocks": 0, "max_batch": 0,
            "fg_blocks": 0, "bg_blocks": 0, "bg_forced": 0,
            "bg_batch_max": 0, "fg_deferred_behind_bg": 0,
            # prefetch lane: cache read-ahead blocks riding the bg lane
            # (cache/prefetch.py marks them via qos.prefetch_context)
            "prefetch_blocks": 0,
            "fused": 0, "fused_failures": 0,
            # degradation ladder (metrics-v3 /api/fault): current rung,
            # device-fault streak witnesses, demote/promote transitions.
            # The gauge is a FAULT signal: 2 = healthy (fused serving, or
            # fused benignly inapplicable — disabled, unsupported shape),
            # 1 = fused faulted out (XLA serving), 0 = device gone (numpy)
            "backend_level": LEVEL_FUSED,
            "device_faults": 0, "demotions": 0, "promotions": 0, "probes": 0,
            "numpy_blocks": 0,
            # dispatch timing (metrics-v3 /api/tpu): sums of this
            # dispatcher's phases (DEVICE_PHASES / HOST_PHASES) + per-item
            # queue wait
            "occupancy_pct_sum": 0.0, "host_s": 0.0, "device_s": 0.0,
            "queue_wait_s": 0.0,
            "queue_wait_hist": [0] * (len(QUEUE_WAIT_BUCKETS) + 1),
            "device_time_hist": [0] * (len(DEVICE_TIME_BUCKETS) + 1),
            # zero-copy batch assembly: dispatched bucket sizes, blocks
            # of pure pad, and exact-fit dispatches that skipped the
            # bucket arena entirely (the caller's array went straight
            # to the device — the streaming-PUT steady state)
            "pad_blocks": 0, "arena_direct": 0,
            "bucket_hist": [0] * (len(BUCKET_BLOCK_BUCKETS) + 1),
            # first dispatch of each (rung, bucket, family) in this
            # process: {(rung, bucket): n} and the `kernel`-phase seconds
            # it took (trace-and-lower + compile or cache load + the run),
            # counted when that dispatch ENDS — the bucket histogram moves
            # when it starts. Keys appear under _cv; observers copy under it
            "first_calls": {}, "first_call_s": {},
        }
        self._dispatched: set[tuple[str, int, str]] = set()
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"tpu-dispatch-{codec.data_shards}+{codec.parity_shards}",
        )
        self._thread.start()

    def stats_snapshot(self) -> dict:
        """Consistent copy of the stats dict for observers (metrics,
        admin, QoS): the dispatcher thread mutates `stats` under `_cv`,
        so a snapshot taken under the same lock can never observe a
        torn histogram or a mid-batch counter mix (miniovet races
        pass)."""
        with self._cv:
            return {
                k: (v.copy() if isinstance(v, (list, dict)) else v)
                for k, v in self.stats.items()
            }

    def submit(
        self, blocks: np.ndarray, priority: int | None = None, codec=None
    ) -> Future:
        """blocks: [k, d, n] -> Future of (parity [k, p, n], digests).

        The result holds what the dispatch computed and nothing the
        caller already has: data row i of block b is the caller's own
        ``blocks[b, i]``, parity row j is ``parity[b, j]``, and the
        digests cover all t = d + p rows in erasure-index order. Both
        result arrays are fresh arrays of this dispatch (never views of
        the bucket arena) with C-contiguous rows, so a waiter may hand
        rows to the drives as writev buffers. The caller keeps `blocks`
        alive and unchanged until it has framed its data rows.

        priority: PRI_FOREGROUND / PRI_BACKGROUND; None resolves from the
        qos context (background planes run under ``background_context()``).

        codec: the family codec encoding this entry (defaults to the
        dispatcher's founding reedsolomon codec). Both code families ride
        ONE queue stream — each batch entry carries its family tag, and
        the dispatch loop groups same-family entries into shared device
        calls. Digest shape is the family's: [k, t, 32] for reedsolomon,
        [k, t, 2, 32] (per sub-chunk) for cauchy.
        """
        if priority is None:
            priority = current_priority()
        if codec is None:
            codec = self.codec
        fut: Future = Future()
        # request id captured at submit time (contextvar — costs one read
        # only while someone is tracing) so the batch record can name the
        # requests it served
        req_id = obs.current_request_id() if obs.active() else ""
        item = (blocks, fut, priority, _monotonic(), req_id,
                priority == PRI_BACKGROUND and in_prefetch(), codec)
        with self._cv:
            (self._bg if priority == PRI_BACKGROUND else self._fg).append(item)
            self._cv.notify()
        return fut

    def encode(self, blocks: np.ndarray, priority: int | None = None, codec=None):
        return self.submit(blocks, priority, codec).result()

    # -- worker ------------------------------------------------------------

    @staticmethod
    def _drain_locked(dq: deque, batch: list, room: int, force: bool = False) -> int:
        """Move whole items from `dq` into `batch` while they fit `room`
        blocks; an oversize head stays queued (next dispatch) unless
        `force` and the batch is still empty — the first item of a
        dispatch may exceed the cap, exactly like the old carry logic.
        Returns blocks taken. Caller holds self._cv."""
        took = 0
        while dq:
            k = dq[0][0].shape[0]
            if k > room - took and not (force and not batch):
                break
            batch.append(dq.popleft())
            took += k
        return took

    def _promote_aged_locked(self, now: float) -> None:
        """Starvation protection: background items older than bg_max_age
        move to the foreground lane (they have waited long enough that
        'leftover capacity only' would become 'never')."""
        while self._bg and now - self._bg[0][3] > self.bg_max_age:
            item = self._bg.popleft()
            self._fg.append(item)
            self.stats["bg_forced"] += item[0].shape[0]

    def _collect(self) -> list[tuple]:
        batch: list[tuple] = []
        total = 0
        with obs.phase("dispatch", "wait"), self._cv:
            while not self._fg and not self._bg:
                self._cv.wait()
            self._promote_aged_locked(_monotonic())
            total += self._drain_locked(
                self._fg, batch, self.max_blocks - total, force=True
            )
        # the straggler window opens only on evidence of CONCURRENT
        # foreground traffic (>= 2 genuinely-foreground items queued
        # together, the old single-queue contract — age-promoted bg items
        # don't count). Pending or promoted bg work must not hold a lone
        # fg block hostage for the window — that would be exactly the
        # "foreground delayed by background" regression this lane exists
        # to prevent; bg fills leftover capacity below either way.
        native_fg = sum(1 for it in batch if it[2] == PRI_FOREGROUND)
        if native_fg > 1 and total < self.max_blocks:
            with obs.phase("dispatch", "window"):
                total = self._straggler_window(batch, total)
        with self._cv:
            # late fg arrivals still beat queued bg work — drained first
            # under the same lock that grants bg its leftover slots
            self._promote_aged_locked(_monotonic())
            total += self._drain_locked(
                self._fg, batch, self.max_blocks - total, force=not batch
            )
            if self._fg:
                room = 0  # fg still queued (capacity-bound): bg gets nothing
            else:
                room = min(self.max_blocks - total, self.bg_max_blocks)
            took_bg = self._drain_locked(
                self._bg, batch, room, force=not batch
            )
            total += took_bg
            if took_bg:
                self.stats["bg_batch_max"] = max(
                    self.stats["bg_batch_max"], took_bg
                )
                if self._fg:
                    # defensive witness for the acceptance invariant; by
                    # construction this never fires
                    self.stats["fg_deferred_behind_bg"] += 1
        return batch

    def _straggler_window(self, batch: list, total: int) -> int:
        deadline = _monotonic() + self.window
        while total < self.max_blocks:
            timeout = deadline - _monotonic()
            if timeout <= 0:
                break
            with self._cv:
                if not self._fg:
                    self._cv.wait(timeout)
                self._promote_aged_locked(_monotonic())
                took = self._drain_locked(
                    self._fg, batch, self.max_blocks - total
                )
                total += took
                if self._fg and took == 0:
                    # head item cannot fit the remaining room, which
                    # never grows: stop burning the window (and the
                    # CPU — waiting here would spin on every notify)
                    break
        return total

    @staticmethod
    def _bucket(k: int) -> int:
        """Pad batch sizes to power-of-two buckets: the jitted encode+hash
        is shape-specialized, and arbitrary batch sizes would recompile the
        (expensive) hash chain per novel size."""
        b = 1
        while b < k:
            b <<= 1
        return b

    def _fused_cm(self, all_blocks: np.ndarray, took: dict):
        """Chunk-major mega-kernel dispatch when shapes allow (ops/
        fused_pallas.py): one kernel, data read from HBM once. Returns
        (parity_cm, digests) still on the device, both ready, or None to
        fall back to the row-major XLA path (non-TPU backend,
        unsupported shape, MINIO_TPU_FUSED_CM=0, or a kernel failure —
        the fallback must be real, not just a shape gate)."""
        if not self._fused_enabled:
            return None
        if self._fused_cooldown > 0:
            self._fused_cooldown -= 1
            return None
        import jax

        from ..ops import fused_pallas as fp

        b, d, n = all_blocks.shape
        p = self.codec.parity_shards
        if not fp.supports(d, p, b, n):
            return None
        try:
            rule = fault_registry.check(
                "tpu", self._shape, "kernel", modes=("kernel-fail",)
            )
            if rule is not None:
                # injected Pallas-kernel failure: caught below, so the
                # ladder's first demotion rung (fused -> XLA) engages
                raise RuntimeError("injected TPU kernel fault")
            with obs.phase("dispatch", "pack", into=took):
                packed = fp.pack_chunk_major(all_blocks)
            with obs.phase("dispatch", "h2d", into=took):
                data_cm = jax.block_until_ready(jax.device_put(packed))
                # freeing a 256 MiB buffer takes milliseconds: it belongs
                # to the phase that is done with it, not to no phase
                del packed
            # call -> ready: launch overhead + execution, and on a
            # bucket's first call trace-and-lower and the compile
            with obs.phase("dispatch", "kernel", into=took):
                out = jax.block_until_ready(
                    fp.fused_encode_hash_cm(data_cm, d, p)
                )
            self._fused_backoff = 8  # healthy again: reset the backoff
            with self._cv:
                self.stats["fused"] += 1
            return out
        # miniovet: ignore[error-taint] -- this IS the degradation ladder:
        # a fused-rung failure falls to the XLA rung (byte-identical
        # results), is counted in fused_failures, and backs off
        except Exception as e:  # noqa: BLE001 — lowering/device failure: XLA path
            runtime.report_rung_failure("fused", f"{self._shape}x{b}x{n}", e)
            # back off exponentially and re-probe: one transient device
            # hiccup must not degrade the server until restart
            self._fused_cooldown = self._fused_backoff
            self._fused_backoff = min(self._fused_backoff * 2, 1024)
            with self._cv:
                self.stats["fused_failures"] += 1
            return None

    # -- degradation ladder ------------------------------------------------

    def _tpu_fault_hook(self) -> None:
        """Device-boundary fault injection (fault/ registry): slow-batch
        stalls the dispatch, device-lost raises so the whole device rung
        (XLA included) fails and the ladder demotes."""
        rule = fault_registry.check(
            "tpu", self._shape, "dispatch", modes=("device-lost", "slow-batch")
        )
        if rule is None:
            return
        if rule.mode == "slow-batch":
            fault_registry.sleep_latency(rule)
            return
        raise RuntimeError("injected TPU device loss")

    def _device_fault(self, err: Exception) -> None:
        self._device_fault_streak += 1
        demoted = False
        with self._cv:
            self.stats["device_faults"] += 1
            if (
                self.stats["backend_level"] != LEVEL_NUMPY
                and self._device_fault_streak >= self._demote_threshold
            ):
                self.stats["backend_level"] = LEVEL_NUMPY
                self.stats["demotions"] += 1
                demoted = True
        if demoted:
            self._probe_countdown = self._probe_after
            fault_registry.emit(
                "backend.demote", shape=self._shape, to="numpy",
                fault=f"{type(err).__name__}: {err}",
            )

    def _probe_device(self) -> bool:
        """Synthetic probe batch through the device (XLA) rung; the
        materialization IS the probe verdict. User traffic keeps riding
        numpy until a probe succeeds — a flapping device never fails a
        live request."""
        with self._cv:
            self.stats["probes"] += 1
        try:
            self._tpu_fault_hook()
            blocks = np.zeros((1, self.codec.data_shards, self.n), dtype=np.uint8)
            parity, digests = self._encode_and_hash(self.codec, blocks)
            np.asarray(parity)
            np.asarray(digests)
            return True
        # miniovet: ignore[error-taint] -- ladder probe: False means "stay
        # demoted"; the synthetic batch exists to absorb this failure
        except Exception as e:  # noqa: BLE001 — device still gone
            runtime.report_rung_failure("probe", self._shape, e)
            return False

    def _encode_numpy(self, blocks: np.ndarray, family: str = "reedsolomon"):
        """Pure-CPU rung: numpy GF parity + numpy HighwayHash digests,
        byte-identical to the device rungs (golden tests pin all three).
        [k, d, n] -> (parity [k, p, n], family-shaped digests)."""
        ref = self._np_codec.get(family)
        if ref is None:
            if family == "cauchy":
                from ..ops.cauchy import get_codec
            else:
                from ..ops.rs import get_codec
            ref = self._np_codec[family] = get_codec(
                self.codec.data_shards, self.codec.parity_shards
            )
        from ..erasure.coder import encode_blocks_numpy

        shards, digests = encode_blocks_numpy(ref, blocks, family)
        return shards[:, self.codec.data_shards:], digests

    def _loop(self) -> None:
        while True:
            batch = self._collect()
            t_start = _monotonic()
            # per-item queue wait: submit -> dispatch start (each family
            # group recomputes its own max for the obs record)
            with self._cv:
                for it in batch:
                    wait = max(t_start - it[3], 0.0)
                    self.stats["queue_wait_s"] += wait
                    _hist_add(
                        self.stats["queue_wait_hist"], QUEUE_WAIT_BUCKETS,
                        wait,
                    )
            # ONE stream, two families: entries carry their family tag;
            # same-family entries fuse into shared device calls, and a
            # mixed batch dispatches as consecutive per-family groups
            # (matrix weights differ — they cannot share one matmul).
            groups: dict[str, list[tuple]] = {}
            for it in batch:
                groups.setdefault(
                    getattr(it[6], "family", "reedsolomon"), []
                ).append(it)
            for family, items in groups.items():
                self._dispatch_group(items, family)

    def _dispatch_group(self, batch: list[tuple], family: str) -> None:
        t_start = _monotonic()
        arena_lease = None
        # this dispatch's phases, name -> wall seconds; device_s and
        # host_s are sums over it, never a stopwatch of their own
        took: dict[str, float] = {}
        try:
            codec = batch[0][6]
            max_wait = max(
                (max(t_start - it[3], 0.0) for it in batch), default=0.0
            )
            with obs.phase("dispatch", "assemble", into=took):
                all_blocks, k, arena_lease = self._assemble(batch, family)
            n = all_blocks.shape[2]
            level = self.stats["backend_level"]
            if level == LEVEL_NUMPY:
                # degraded: traffic serves on CPU; every probe_after
                # dispatches a synthetic batch probes the device and
                # re-promotes on success
                self._probe_countdown -= 1
                if self._probe_countdown <= 0:
                    with obs.phase("dispatch", "numpy", into=took):
                        alive = self._probe_device()
                    if alive:
                        level = LEVEL_XLA
                        with self._cv:
                            self.stats["backend_level"] = level
                            self.stats["promotions"] += 1
                        self._device_fault_streak = 0
                        fault_registry.emit(
                            "backend.promote", shape=self._shape
                        )
                    else:
                        self._probe_countdown = self._probe_after
            was_fused = False
            parity = digests = None
            bucket = int(all_blocks.shape[0])
            # the DEVICE_PHASES cover ONLY time spent against the device
            # (successful or faulted attempts) — the numpy rung and the
            # probe are host work (phase `numpy`), so the host-vs-device
            # split stays honest in degraded mode
            if level != LEVEL_NUMPY:
                try:
                    parity, digests, was_fused, bucket = (
                        self._encode_on_device(
                            all_blocks, k, codec, family, took
                        )
                    )
                    self._device_fault_streak = 0
                    # gauge semantics: XLA is a DEGRADATION signal only
                    # when the fused rung is faulted out (cooldown); a
                    # benign fused skip (unsupported shape, big bucket,
                    # MINIO_TPU_FUSED_CM=0, cauchy family) reads healthy
                    with self._cv:
                        if self._fused_cooldown > 0:
                            self.stats["backend_level"] = LEVEL_XLA
                        else:
                            self.stats["backend_level"] = LEVEL_FUSED
                # miniovet: ignore[error-taint] -- error-as-value into
                # the ladder: _device_fault(e) records the fault,
                # demotes past the streak threshold, and the batch is
                # re-served byte-identically on the numpy rung below
                except Exception as e:  # noqa: BLE001 — serve degraded
                    # the device rung failed mid-batch: waiters get
                    # numpy results instead of errors, the ladder
                    # counts the fault and demotes past the threshold
                    runtime.report_rung_failure(
                        "device", f"{self._shape}x{all_blocks.shape[0]}x{n}", e
                    )
                    self._device_fault(e)
                    was_fused = False
                    parity = None
            rung = "fused" if was_fused else "xla"
            if parity is None:
                rung = "numpy"
                with obs.phase("dispatch", "numpy", into=took):
                    parity, digests = self._encode_numpy(all_blocks[:k], family)
                with self._cv:
                    self.stats["numpy_blocks"] += k
            device_s = sum(took.get(name, 0.0) for name in DEVICE_PHASES)
            occupancy = 100.0 * k / max(bucket, 1)
            with obs.phase("dispatch", "fanout", into=took):
                from ..erasure.coder import family_stats_add

                family_stats_add(family, "encode_blocks", k)
                with self._cv:
                    self.stats["dispatches"] += 1
                    self.stats["blocks"] += k
                    self.stats["max_batch"] = max(self.stats["max_batch"], k)
                    self.stats["occupancy_pct_sum"] += occupancy
                    self.stats["device_s"] += device_s
                    _hist_add(
                        self.stats["device_time_hist"], DEVICE_TIME_BUCKETS,
                        device_s,
                    )
                    if (rung, bucket, family) not in self._dispatched:
                        self._dispatched.add((rung, bucket, family))
                        key = (rung, bucket)
                        calls, secs = (
                            self.stats["first_calls"], self.stats["first_call_s"]
                        )
                        calls[key] = calls.get(key, 0) + 1
                        secs[key] = secs.get(key, 0.0) + took.get("kernel", 0.0)
                    for it in batch:
                        kk = it[0].shape[0]
                        if it[2] == PRI_BACKGROUND:
                            self.stats["bg_blocks"] += kk
                            if it[5]:
                                self.stats["prefetch_blocks"] += kk
                        else:
                            self.stats["fg_blocks"] += kk
                off = 0
                for it in batch:
                    blocks, fut = it[0], it[1]
                    kk = blocks.shape[0]
                    fut.set_result(
                        (parity[off : off + kk], digests[off : off + kk])
                    )
                    off += kk
            host_s = sum(took.get(name, 0.0) for name in HOST_PHASES)
            with self._cv:
                self.stats["host_s"] += host_s
            if obs.active():
                req_ids = sorted({it[4] for it in batch if it[4]})
                obs.publish({
                    "time": time.time(),
                    "type": obs.TYPE_TPU,
                    "name": "dispatch.batch",
                    "reqId": req_ids[0] if len(req_ids) == 1 else "",
                    "reqIds": req_ids,
                    "node": obs.trace.NODE,
                    "durationNs": int((host_s + device_s) * 1e9),
                    "deviceNs": int(device_s * 1e9),
                    "hostNs": int(host_s * 1e9),
                    "phaseNs": {
                        name: int(sec * 1e9) for name, sec in took.items()
                    },
                    "queueWaitMaxNs": int(max_wait * 1e9),
                    "blocks": k,
                    "bucket": bucket,
                    "occupancyPct": round(occupancy, 1),
                    "fused": was_fused,
                    "family": family,
                    "shape": f"{self.codec.data_shards}+"
                             f"{self.codec.parity_shards}",
                    "error": "",
                })
        except Exception as e:  # noqa: BLE001 — fail all waiters
            for it in batch:
                if not it[1].done():
                    it[1].set_exception(e)
        finally:
            # waiters get parity and digests only: fresh arrays of this
            # dispatch (the D2H result / numpy-rung output), never arena
            # views. Data rows they frame from the array they submitted,
            # not from this arena — so it recycles here unconditionally
            if arena_lease is not None:
                arena_lease.release()

    def _assemble(self, batch: list[tuple], family: str):
        """The batch's entries as one [bucket, d, n] array, padded to the
        power-of-two bucket -> (all_blocks, real blocks k, arena lease or
        None). The caller releases the lease after the dispatch."""
        from ..erasure import bufpool

        # malformed input is a CALLER error: it must propagate to
        # the waiters, never count as a device fault or get
        # "served degraded" by the numpy rung
        for it in batch:
            if it[0].shape[1] != self.codec.data_shards:
                raise ValueError(
                    f"blocks have d={it[0].shape[1]}, codec "
                    f"expects {self.codec.data_shards}"
                )
        d = self.codec.data_shards
        n = batch[0][0].shape[2]
        k = sum(it[0].shape[0] for it in batch)
        bucket = self._bucket(k)
        arena_lease = None
        if (
            bucket < 16 and family == "reedsolomon" and self._fused_enabled
            and self._fused_cooldown == 0
        ):
            from ..ops import fused_pallas as fp

            # low-concurrency batches pad up to the mega-kernel's
            # floor rather than losing the fused path (VERDICT r2)
            if fp.supports(d, self.codec.parity_shards, 16, n):
                bucket = 16
        if len(batch) == 1 and k == bucket:
            # exact-fit single entry (the streaming-PUT steady state:
            # ingest arenas are sized to the bucket): the caller's
            # array — often a view of the pooled ingest arena — goes
            # straight to the device. No concat, no pad, no arena.
            all_blocks = batch[0][0]
            with self._cv:
                self.stats["arena_direct"] += 1
        else:
            # pre-sized bucket arena replaces per-dispatch
            # np.concatenate + pad allocation: entries copy in once
            # (inherent — they arrive scattered), only the pad tail
            # is zeroed, and the arena recycles after the dispatch
            if bufpool.zerocopy_enabled():
                arena_lease = bufpool.get_pool().acquire(bucket * d * n)
                all_blocks = arena_lease.array[: bucket * d * n].reshape(
                    bucket, d, n
                )
            else:
                all_blocks = np.empty((bucket, d, n), dtype=np.uint8)
            try:
                off = 0
                for it in batch:
                    kk = it[0].shape[0]
                    all_blocks[off : off + kk] = it[0]
                    off += kk
            except BaseException:
                # a ragged entry: the lease never reaches the caller
                if arena_lease is not None:
                    arena_lease.release()
                raise
            bufpool.count_copy("dispatch-concat", len(batch))
            if bucket != k:
                all_blocks[k:] = 0
                bufpool.count_copy("dispatch-pad")
        with self._cv:
            self.stats["pad_blocks"] += bucket - k
            _hist_add(self.stats["bucket_hist"], BUCKET_BLOCK_BUCKETS, bucket)
        return all_blocks, k, arena_lease

    def _encode_on_device(self, all_blocks, k: int, codec, family: str,
                          took: dict):
        """One attempt on the device rungs (fused, else XLA), each step a
        leaf phase -> (parity [k, p, n], digests, was_fused, rows
        dispatched). Every phase ends synced (`block_until_ready`,
        `np.asarray`), so the phases' seconds are what each step took and
        not what it enqueued."""
        import jax
        import jax.numpy as jnp

        # injected faults only: a slow-batch stall is no phase's time
        self._tpu_fault_hook()
        fused = (
            self._fused_cm(all_blocks, took) if family == "reedsolomon" else None
        )
        was_fused = fused is not None
        if fused is None:
            # don't pay mega-kernel padding (16) on the XLA
            # path: trim back to the power-of-two bucket
            nb = self._bucket(k)
            if nb < all_blocks.shape[0]:
                all_blocks = all_blocks[:nb]
            with obs.phase("dispatch", "h2d", into=took):
                data = jax.block_until_ready(jnp.asarray(all_blocks))
            with obs.phase("dispatch", "kernel", into=took):
                if family == "cauchy":
                    from ..ops.cauchy import encode_and_hash_cauchy

                    fused = encode_and_hash_cauchy(codec, data)
                else:
                    fused = self._encode_and_hash(codec, data)
                fused = jax.block_until_ready(fused)
        parity, digests = fused
        with obs.phase("dispatch", "d2h", into=took):
            parity = np.asarray(parity)
            digests = np.asarray(digests)
        with obs.phase("dispatch", "unpack", into=took):
            if was_fused:
                from ..ops import fused_pallas as fp

                parity = fp.unpack_chunk_major(parity)
            # a TPU array can arrive on the host in the device's
            # own (non row-major) layout: waiters frame parity and
            # digest ROWS as writev buffers, which must be
            # C-contiguous. Parity is copied only where its rows are
            # strided (the unpack above already leaves them row-major)
            parity = parity[:k]
            if parity.strides[-1] != parity.itemsize:
                parity = np.ascontiguousarray(parity)
            digests = np.ascontiguousarray(digests[:k])
            del fused
        return parity, digests, was_fused, int(all_blocks.shape[0])


def _monotonic() -> float:
    return time.monotonic()


_dispatchers: dict[tuple[int, int, int], TpuDispatcher] = {}
_dlock = threading.Lock()


def get_dispatcher(codec, n: int) -> TpuDispatcher:
    key = (codec.data_shards, codec.parity_shards, n)
    d = _dispatchers.get(key)
    if d is None:
        with _dlock:
            d = _dispatchers.get(key)
            if d is None:
                d = _dispatchers[key] = TpuDispatcher(codec, n)
    return d


def aggregate_stats() -> dict:
    """Summed stats across every live dispatcher (metrics/admin plane).
    Histogram lists sum element-wise; max-style gauges take the max.
    Reads per-dispatcher snapshots (taken under each dispatcher's lock)
    so a scrape racing a dispatch never mixes halves of one batch."""
    out: dict = {}
    for d in list(_dispatchers.values()):
        for k, v in d.stats_snapshot().items():
            if k == "backend_level":
                # most-degraded rung across shapes: the alarming signal
                out[k] = min(out.get(k, LEVEL_FUSED), v)
            elif k in ("max_batch", "bg_batch_max"):
                out[k] = max(out.get(k, 0), v)
            elif isinstance(v, list):
                cur = out.setdefault(k, [0] * len(v))
                for i, x in enumerate(v):
                    cur[i] += x
            elif isinstance(v, dict):
                cur = out.setdefault(k, {})
                for key, x in v.items():
                    cur[key] = cur.get(key, 0) + x
            else:
                out[k] = out.get(k, 0) + v
    return out

"""The named workload profiles.

Each profile is a declarative spec — client mix, object-size
distribution, key-space shape, background pressure, the metrics series
its gates are computed from, and quick/full scale points — executed by
the shared closed-loop engine. The runner (``run_profile``) scrapes
every declared gate series BEFORE the phase runs: a profile whose gate
counters are missing from the exposition fails loudly up front instead
of passing vacuously.

The four profiles:

- ``small-object-storm``: 10^5+ KB-scale (inline) objects; headline is
  metadata-plane ops/s and listing p99. Gated on the deterministic
  fan-out counters (inline PUT/GET/HEAD do ZERO user-plane shard-file
  I/O) and on listing drive-walks staying O(1) per continuation page
  (second sweep pass: zero walks).
- ``ml-dataloader-shuffle``: random 1..N MiB ranged GETs over large
  objects, two epochs with an identical (seeded) access set — epoch 2
  must ride the segment cache. Gated on epoch-2 hit ratio, byte-exact
  ranges, and a (CPU-shadowed, generous) p99 ceiling.
- ``backup-restore``: multipart-heavy sequential backup streams then
  full-object restore reads, byte-verified part by part. Gated on
  sustained MiB/s and a bounded server-tree RSS watermark.
- ``multi-tenant-burst``: adversarial tenants — A pinned to pool 0,
  B expands the cluster live, floods big PUTs + cross-tenant LISTs with
  a heal flood behind it. Gated on ``fg_deferred_behind_bg`` staying
  flat and bounded cross-tenant p99 skew.
- ``repair-degraded-storm``: seeded drive-failure + straggler/error
  fault schedule under verifying zipf traffic over a hive-partitioned
  keyspace while a heal flood runs. Gated on degraded-GET p99 within a
  declared band of healthy p99, zero wrong bytes anywhere, the
  and the BENCH_r09 cauchy-ingress bound (<= 0.75x rs, controlled
  synthetic).
"""

from __future__ import annotations

import asyncio
import bisect
import dataclasses
import hashlib
import json
import os
import random
import shutil
import sys
import tempfile
import time
from typing import Any, Callable
from urllib.parse import quote

from .engine import (
    BUCKET,
    MIB,
    AsyncS3,
    HealFlood,
    RssSampler,
    Server,
    Stats,
    admin,
    hive_keys,
    median,
    multipart_put,
    require_gate_series,
    run_get_loop,
    s3_session,
    scrape_series,
    selftest_fingerprint,
    tbody,
    zipf_cdf,
)

from minio_tpu.client import S3Client


def tbody_range(key: str, gen: int, off: int, length: int) -> bytes:
    """The [off, off+length) slice of tbody(key, gen, ·) without
    materialising the whole object — range verification at any scale."""
    seed = hashlib.md5(f"{key}#{gen}".encode()).digest()
    start = off % len(seed)
    reps = (start + length) // len(seed) + 2
    return (seed * reps)[start:start + length]


@dataclasses.dataclass(frozen=True)
class Profile:
    """One named workload: everything the runner needs, declaratively."""

    name: str
    summary: str
    drives: int
    workers: int
    scan_interval: float
    env: dict[str, str]
    # (metrics path, series name) pairs the gates are computed from;
    # checked present BEFORE the phase runs (no vacuous passes)
    gate_series: list[tuple[str, str]]
    quick_spec: dict[str, Any]
    full_spec: dict[str, Any]
    phase: Callable  # async (ctx) -> result dict with gates


@dataclasses.dataclass
class Ctx:
    port: int
    base: str
    pid: int
    spec: dict[str, Any]
    quick: bool


# ===================================================== small-object-storm


def _shard_io_user(port: int) -> dict[str, float]:
    rows = scrape_series(port, "/api/cache", "minio_storage_shard_io_total")
    return {k: v for k, v in rows.items() if 'plane="user"' in k}


def _mc_counter(port: int, name: str) -> float:
    rows = scrape_series(port, "/api/cache", name)
    return sum(rows.values())


async def _storm_populate(cli: AsyncS3, n: int, body: bytes) -> float:
    sem = asyncio.Semaphore(64)

    async def put_one(i: int) -> None:
        async with sem:
            st, _ = await cli.request(
                "PUT", f"/{BUCKET}/s/{i:07d}", body=body, read=False
            )
            assert st == 200, f"populate PUT {i}: HTTP {st}"

    t0 = time.monotonic()
    await asyncio.gather(*(put_one(i) for i in range(n)))
    return time.monotonic() - t0


async def _storm_churn(cli: AsyncS3, clients: int, duration: float,
                       n: int, body: bytes) -> Stats:
    """Metadata-plane churn: PUT 10% / GET 55% / HEAD 35%, every object
    inline — the headline ops/s phase."""
    stats = Stats()
    stop_at = time.monotonic() + duration

    async def one(cid: int) -> None:
        rng = random.Random(31 * cid + 7)
        while time.monotonic() < stop_at:
            r = rng.random()
            key = f"s/{rng.randrange(n):07d}"
            t0 = time.perf_counter()
            try:
                if r < 0.10:
                    st, _ = await cli.request(
                        "PUT", f"/{BUCKET}/{key}", body=body, read=False
                    )
                    stats.add("PUT", time.perf_counter() - t0, len(body), st)
                elif r < 0.65:
                    st, data = await cli.request("GET", f"/{BUCKET}/{key}")
                    stats.add("GET", time.perf_counter() - t0, len(data), st)
                else:
                    st, _ = await cli.request("HEAD", f"/{BUCKET}/{key}")
                    stats.add("HEAD", time.perf_counter() - t0, 0, st)
                if st == 503:
                    await asyncio.sleep(1.0)
            except Exception:  # noqa: BLE001 — count, keep looping
                stats.add("ERR", time.perf_counter() - t0, 0, 599)

    t0 = time.monotonic()
    await asyncio.gather(*(one(i) for i in range(clients)))
    stats.wall = time.monotonic() - t0
    return stats


async def _storm_sweep(cli: AsyncS3, clients: int, n: int,
                       page: int) -> tuple[Stats, int]:
    """Continuation-token sweep: each client pages through a disjoint
    key-range slice with V1 markers, verifying every page's key count
    (the keyspace is static during the sweep). Returns (stats, pages)."""
    stats = Stats()
    pages = 0

    async def one(cid: int) -> None:
        nonlocal pages
        lo, hi = cid * n // clients, (cid + 1) * n // clients
        pos = lo
        while pos < hi:
            marker = quote(f"s/{pos:07d}", safe="")
            t0 = time.perf_counter()
            try:
                st, data = await cli.request(
                    "GET", f"/{BUCKET}",
                    query=f"prefix=s%2F&marker={marker}&max-keys={page}",
                )
                stats.add("LIST", time.perf_counter() - t0, len(data), st)
                if st == 200:
                    # marker names key #pos: the page holds what follows
                    want = min(page, n - 1 - pos)
                    got = data.count(b"<Key>")
                    if got != want:
                        stats.errors += 1
                pages += 1
            except Exception:  # noqa: BLE001
                stats.add("ERR", time.perf_counter() - t0, 0, 599)
            pos += page

    t0 = time.monotonic()
    await asyncio.gather(*(one(i) for i in range(clients)))
    stats.wall = time.monotonic() - t0
    return stats, pages


def _synthetic_million(n_keys: int, shard_keys: int, page: int) -> dict:
    """In-process (synthetic, no server) O(1)-per-page witness at a key
    count the container can't host as real objects: build a ShardedKeys
    over `n_keys` and time one page resumed near the FRONT vs DEEP into
    the keyspace. A linear resume scan would make the deep page ~three
    orders of magnitude slower; bisect resume keeps the ratio ~1."""
    from minio_tpu.erasure import listing as L

    keys = [f"s/{i:07d}" for i in range(n_keys)]
    t0 = time.perf_counter()
    sk = L.ShardedKeys.build(keys, shard_keys)
    build_s = time.perf_counter() - t0

    def page_cost(pos: int) -> float:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            it = sk.iter_from(f"s/{pos:07d}")
            for _k, _ in zip(it, range(page)):
                pass
            best = min(best, time.perf_counter() - t0)
        return best

    front = page_cost(100)
    deep = page_cost(int(n_keys * 0.9))
    return {
        "keys": n_keys,
        "shard_keys": shard_keys,
        "page": page,
        "build_s": round(build_s, 3),
        "front_page_us": round(front * 1e6, 1),
        "deep_page_us": round(deep * 1e6, 1),
        "deep_vs_front_ratio": round(deep / max(front, 1e-9), 2),
    }


async def storm_phase(ctx: Ctx) -> dict:
    spec = ctx.spec
    n = spec["objects"]
    body = os.urandom(spec["object_kb"] * 1024)
    async with s3_session(ctx.port) as cli:
        io0 = await asyncio.to_thread(_shard_io_user, ctx.port)
        populate_s = await _storm_populate(cli, n, body)

        rounds: list[dict] = []
        sweep2_walks_total = 0.0
        pages_total, sweep_walks_total = 0, 0.0
        for rnd in range(spec["rounds"]):
            churn = await _storm_churn(
                cli, spec["clients"], spec["churn_s"], n, body
            )
            await asyncio.sleep(0.3)  # drain invalidation broadcasts
            w0 = await asyncio.to_thread(
                _mc_counter, ctx.port, "minio_cache_metacache_walks_total")
            sweep1, pages1 = await _storm_sweep(
                cli, spec["sweep_clients"], n, spec["page"])
            w1 = await asyncio.to_thread(
                _mc_counter, ctx.port, "minio_cache_metacache_walks_total")
            sweep2, pages2 = await _storm_sweep(
                cli, spec["sweep_clients"], n, spec["page"])
            w2 = await asyncio.to_thread(
                _mc_counter, ctx.port, "minio_cache_metacache_walks_total")
            pages_total += pages1 + pages2
            sweep_walks_total += w2 - w0
            sweep2_walks_total += w2 - w1
            cs = churn.summary(churn.wall)
            s1 = sweep1.summary(sweep1.wall)
            s2 = sweep2.summary(sweep2.wall)
            rounds.append({
                "meta_ops_per_s": cs["iops"],
                "churn": cs,
                "sweep_pass1": s1,
                "sweep_pass2": s2,
                "listing_p99_ms": s2["per_class"].get("LIST", {}).get("p99_ms"),
                "sweep_walks": [w1 - w0, w2 - w1],
            })
        io1 = await asyncio.to_thread(_shard_io_user, ctx.port)

    headline_ops = median([r["meta_ops_per_s"] for r in rounds])
    headline_lp99 = median([r["listing_p99_ms"] or 0.0 for r in rounds])
    io_delta = {k: io1.get(k, 0) - io0.get(k, 0) for k in io1}
    errors = sum(
        r["churn"]["errors"] + r["sweep_pass1"]["errors"]
        + r["sweep_pass2"]["errors"] for r in rounds
    )
    pages_per_walk = pages_total / max(sweep_walks_total, 1.0)

    out = {
        "objects": n,
        "object_kb": spec["object_kb"],
        "populate_s": round(populate_s, 1),
        "populate_puts_per_s": round(n / max(populate_s, 1e-9), 1),
        "rounds": rounds,
        "meta_ops_per_s_median": headline_ops,
        "listing_p99_ms_median": headline_lp99,
        "shard_io_user_delta": io_delta,
        "sweep_pages": pages_total,
        "sweep_walks": sweep_walks_total,
        "pages_per_walk": round(pages_per_walk, 1),
        "sweep_pass2_walks": sweep2_walks_total,
    }
    if spec.get("synthetic_keys"):
        out["synthetic_million_keys"] = await asyncio.to_thread(
            _synthetic_million, spec["synthetic_keys"], 8192, spec["page"]
        )

    failures = []
    if any(v != 0 for v in io_delta.values()):
        failures.append(
            f"inline fast path broke: user-plane shard I/O moved {io_delta}")
    if errors:
        failures.append(f"request errors: {errors}")
    if sweep2_walks_total != 0:
        failures.append(
            f"cached sweep still walked drives: {sweep2_walks_total} walks")
    if pages_per_walk < spec["min_pages_per_walk"]:
        failures.append(
            f"pages/walk {pages_per_walk:.1f} < {spec['min_pages_per_walk']}")
    syn = out.get("synthetic_million_keys")
    if syn and syn["deep_vs_front_ratio"] > 50:
        failures.append(
            f"deep page {syn['deep_vs_front_ratio']}x slower than front "
            "(resume is not O(1))")
    out["gates_passed"] = not failures
    out["gate_failures"] = failures
    return out


# =================================================== ml-dataloader-shuffle


def _shuffle_ranges(objs: int, blocks: int, max_mib: int) -> list[tuple]:
    """The epoch's access set: every (object, block) start, with a range
    length derived from the pair — identical across epochs, so epoch 2
    re-requests exactly epoch 1's ranges."""
    out = []
    for o in range(objs):
        for b in range(blocks):
            length = 1 + (o * 131 + b * 17) % max_mib
            length = min(length, blocks - b)
            out.append((o, b, length))
    return out


async def _shuffle_epoch(cli: AsyncS3, ranges: list[tuple], loaders: int,
                         epoch_seed: int) -> Stats:
    stats = Stats()
    order = list(ranges)
    random.Random(epoch_seed).shuffle(order)
    queue = list(order)

    async def loader() -> None:
        while queue:
            o, b, length = queue.pop()
            key = f"ds/{o:02d}"
            off, nbytes = b * MIB, length * MIB
            t0 = time.perf_counter()
            try:
                st, data = await cli.request(
                    "GET", f"/{BUCKET}/{key}",
                    headers={"Range": f"bytes={off}-{off + nbytes - 1}"},
                )
                stats.add("RGET", time.perf_counter() - t0, len(data), st)
                if st == 206 and data != tbody_range(key, 0, off, nbytes):
                    stats.errors += 1
            except Exception:  # noqa: BLE001
                stats.add("ERR", time.perf_counter() - t0, 0, 599)

    t0 = time.monotonic()
    await asyncio.gather(*(loader() for _ in range(loaders)))
    stats.wall = time.monotonic() - t0
    return stats


def _segment_hits_misses(port: int) -> tuple[float, float]:
    rows = scrape_series(
        port, "/api/cache", "minio_cache_segment_range_requests_total")
    hit = sum(v for k, v in rows.items() if 'result="hit"' in k)
    miss = sum(v for k, v in rows.items() if 'result="miss"' in k)
    return hit, miss


async def shuffle_phase(ctx: Ctx) -> dict:
    spec = ctx.spec
    objs, obj_mib = spec["objects"], spec["object_mib"]
    async with s3_session(ctx.port) as cli:
        for o in range(objs):
            key = f"ds/{o:02d}"
            st, _ = await cli.request(
                "PUT", f"/{BUCKET}/{key}",
                body=tbody(key, 0, obj_mib * MIB), read=False,
            )
            assert st == 200, f"dataset PUT {key}: HTTP {st}"

        ranges = _shuffle_ranges(objs, obj_mib, spec["range_mib_max"])
        epochs = []
        h1 = m1 = 0.0
        for ep in range(2):
            e = await _shuffle_epoch(
                cli, ranges, spec["loaders"], epoch_seed=977 + ep)
            epochs.append(e.summary(e.wall))
            if ep == 0:
                h1, m1 = await asyncio.to_thread(
                    _segment_hits_misses, ctx.port)
        h2, m2 = await asyncio.to_thread(_segment_hits_misses, ctx.port)

    ep2_req = (h2 - h1) + (m2 - m1)
    hit_ratio = (h2 - h1) / max(ep2_req, 1.0)
    p99_ep2 = epochs[1]["per_class"].get("RGET", {}).get("p99_ms", 0.0)
    out = {
        "objects": objs,
        "object_mib": obj_mib,
        "range_mib_max": spec["range_mib_max"],
        "loaders": spec["loaders"],
        "ranges_per_epoch": len(ranges),
        "epoch1": epochs[0],
        "epoch2": epochs[1],
        "epoch2_segment_hit_ratio": round(hit_ratio, 3),
        "epoch2_p99_ms": p99_ep2,
    }
    failures = []
    errors = epochs[0]["errors"] + epochs[1]["errors"]
    if errors:
        failures.append(f"range byte/HTTP errors: {errors}")
    if hit_ratio < spec["min_hit_ratio"]:
        failures.append(
            f"epoch-2 segment hit ratio {hit_ratio:.3f} "
            f"< {spec['min_hit_ratio']}")
    if not p99_ep2 or p99_ep2 > spec["p99_max_ms"]:
        failures.append(
            f"epoch-2 RGET p99 {p99_ep2}ms outside (0, {spec['p99_max_ms']}]")
    out["gates_passed"] = not failures
    out["gate_failures"] = failures
    return out


# ========================================================= backup-restore


async def backup_restore_phase(ctx: Ctx) -> dict:
    spec = ctx.spec
    streams, nparts, part_mib = (
        spec["streams"], spec["parts"], spec["part_mib"])
    psize = part_mib * MIB
    failures: list[str] = []

    with RssSampler(ctx.pid) as rss:
        rss_baseline_kb = rss.max_kb
        async with s3_session(ctx.port) as cli:
            async def backup_one(s: int) -> None:
                key = f"bk/{s:02d}"
                parts = [tbody(f"{key}:{p}", 0, psize)
                         for p in range(nparts)]
                etag = await multipart_put(cli, BUCKET, key, parts)
                assert "-" in etag, f"multipart etag shape: {etag!r}"

            t0 = time.perf_counter()
            await asyncio.gather(*(backup_one(s) for s in range(streams)))
            backup_wall = time.perf_counter() - t0

            restored = 0
            t0 = time.perf_counter()
            for s in range(streams):  # sequential: a restore is a drain
                key = f"bk/{s:02d}"
                st, data = await cli.request("GET", f"/{BUCKET}/{key}")
                if st != 200 or len(data) != nparts * psize:
                    failures.append(
                        f"restore {key}: HTTP {st}, {len(data)} bytes")
                    continue
                for p in range(nparts):
                    if data[p * psize:(p + 1) * psize] != tbody(
                            f"{key}:{p}", 0, psize):
                        failures.append(f"restore {key} part {p}: bytes "
                                        "differ from backup")
                        break
                else:
                    restored += 1
            restore_wall = time.perf_counter() - t0
    rss_max_kb = rss.max_kb

    total_mib = streams * nparts * part_mib
    backup_mibs = total_mib / max(backup_wall, 1e-9)
    restore_mibs = restored * nparts * part_mib / max(restore_wall, 1e-9)
    cap_kb = rss_baseline_kb + spec["rss_headroom_mb"] * 1024
    out = {
        "streams": streams,
        "parts": nparts,
        "part_mib": part_mib,
        "total_mib": total_mib,
        "backup_mibs": round(backup_mibs, 1),
        "restore_mibs": round(restore_mibs, 1),
        "objects_restored_verified": restored,
        "rss_baseline_kb": rss_baseline_kb,
        "rss_max_kb": rss_max_kb,
        "rss_cap_kb": cap_kb,
    }
    if restored != streams:
        failures.append(f"only {restored}/{streams} streams verified")
    if backup_mibs <= 0 or restore_mibs <= 0:
        failures.append("throughput not positive")
    if rss_baseline_kb and rss_max_kb > cap_kb:
        failures.append(
            f"server tree RSS {rss_max_kb}kB exceeded cap {cap_kb}kB "
            "(streams must not buffer whole objects)")
    out["gates_passed"] = not failures
    out["gate_failures"] = failures
    return out


# ====================================================== multi-tenant-burst


async def _b_put_flood(cli: AsyncS3, stop: asyncio.Event, stats: Stats,
                       kb: int, wid: int) -> None:
    body = os.urandom(kb * 1024)
    i = 0
    while not stop.is_set():
        t0 = time.perf_counter()
        try:
            st, _ = await cli.request(
                "PUT", f"/{BUCKET}/tenantB/burst-{wid}-{i:05d}",
                body=body, read=False,
            )
            stats.add("BPUT", time.perf_counter() - t0, len(body), st)
            if st == 503:
                await asyncio.sleep(0.5)
        except Exception:  # noqa: BLE001
            stats.add("ERR", time.perf_counter() - t0, 0, 599)
        i += 1


async def _b_list_flood(cli: AsyncS3, stop: asyncio.Event,
                        stats: Stats) -> None:
    """Adversarial listings: B sweeps its own prefix AND tenant A's —
    cross-tenant metadata pressure on the shared listing plane."""
    prefixes = ["tenantB%2F", "tenantA%2F", ""]
    i = 0
    while not stop.is_set():
        t0 = time.perf_counter()
        try:
            st, data = await cli.request(
                "GET", f"/{BUCKET}",
                query=f"prefix={prefixes[i % 3]}&max-keys=1000",
            )
            stats.add("BLIST", time.perf_counter() - t0, len(data), st)
            if st == 503:
                await asyncio.sleep(0.5)
        except Exception:  # noqa: BLE001
            stats.add("ERR", time.perf_counter() - t0, 0, 599)
        i += 1


async def burst_phase(ctx: Ctx) -> dict:
    spec = ctx.spec
    a_keys, size = spec["a_keys"], spec["obj_kb"] * 1024

    # tenant A pinned to pool 0 before any data lands
    r = await asyncio.to_thread(
        admin, ctx.port, "POST", "placement/set", json.dumps(
            {"bucket": BUCKET, "prefix": "tenantA/", "mode": "pin",
             "pools": [0]}).encode())
    assert r.status == 200, f"placement/set A: {r.status} {r.body[:200]}"

    async with s3_session(ctx.port) as cli:
        sem = asyncio.Semaphore(16)

        async def put_one(key: str) -> None:
            async with sem:
                st, _ = await cli.request(
                    "PUT", f"/{BUCKET}/{key}",
                    body=tbody(key, 0, size), read=False)
                assert st == 200, f"preload {key}: HTTP {st}"

        await asyncio.gather(
            *(put_one(f"tenantA/{i:05d}") for i in range(a_keys)))

        fg0 = await asyncio.to_thread(
            require_gate_series, ctx.port,
            [("/api/qos", "minio_tpu_dispatch_fg_deferred_behind_bg_total")])

        # -- solo baseline: tenant A alone --------------------------------
        solo = await run_get_loop(
            cli, spec["a_clients"], spec["solo_s"], a_keys,
            key_fmt="tenantA/{:05d}", cls="AGET")

        # -- live expansion; tenant B pinned to the NEW pool --------------
        r = await asyncio.to_thread(
            admin, ctx.port, "POST", "pool/expand", json.dumps(
                {"spec": os.path.join(
                    ctx.base, "x2-d{1...%d}" % spec["expand_drives"])}
            ).encode())
        assert r.status == 200, f"pool/expand: {r.status} {r.body[:300]}"
        r = await asyncio.to_thread(
            admin, ctx.port, "POST", "placement/set", json.dumps(
                {"bucket": BUCKET, "prefix": "tenantB/", "mode": "pin",
                 "pools": [1]}).encode())
        assert r.status == 200, f"placement/set B: {r.status} {r.body[:200]}"

        # -- burst: B floods PUT/LIST with a heal flood behind it ---------
        stop = asyncio.Event()
        b_stats = Stats()
        b_tasks = [
            asyncio.create_task(
                _b_put_flood(cli, stop, b_stats, spec["burst_put_kb"], w))
            for w in range(spec["b_put_clients"])
        ] + [
            asyncio.create_task(_b_list_flood(cli, stop, b_stats))
            for _ in range(spec["b_list_clients"])
        ]
        with HealFlood(ctx.port) as flood:
            burst = await run_get_loop(
                cli, spec["a_clients"], spec["burst_s"], a_keys,
                key_fmt="tenantA/{:05d}", cls="AGET")
            sweeps = flood.sweeps
        stop.set()
        await asyncio.gather(*b_tasks, return_exceptions=True)

        fg1 = await asyncio.to_thread(
            require_gate_series, ctx.port,
            [("/api/qos", "minio_tpu_dispatch_fg_deferred_behind_bg_total")])

    solo_s = solo.summary(solo.wall)
    burst_s = burst.summary(burst.wall)
    b_s = b_stats.summary(max(burst.wall, 1e-9))
    p99_solo = solo_s["per_class"].get("AGET", {}).get("p99_ms", 0.0)
    p99_burst = burst_s["per_class"].get("AGET", {}).get("p99_ms", 0.0)
    skew = p99_burst / max(p99_solo, 1e-9)
    fg_series = "minio_tpu_dispatch_fg_deferred_behind_bg_total"

    out = {
        "a_keys": a_keys,
        "obj_kb": spec["obj_kb"],
        "solo": solo_s,
        "burst": burst_s,
        "tenant_b": b_s,
        "heal_sweeps": sweeps,
        "a_get_p99_ms_solo": p99_solo,
        "a_get_p99_ms_burst": p99_burst,
        "cross_tenant_p99_skew": round(skew, 2),
        "fg_deferred_behind_bg_before": fg0[fg_series],
        "fg_deferred_behind_bg_after": fg1[fg_series],
    }
    failures = []
    if fg1[fg_series] != fg0[fg_series]:
        failures.append(
            f"fg_deferred_behind_bg moved {fg0[fg_series]} -> "
            f"{fg1[fg_series]}")
    if solo_s["errors"] or burst_s["errors"]:
        failures.append(
            f"tenant-A errors: solo {solo_s['errors']}, "
            f"burst {burst_s['errors']}")
    allowed = max(spec["skew_max"] * p99_solo, spec["p99_floor_ms"])
    if not p99_burst or p99_burst > allowed:
        failures.append(
            f"tenant-A burst p99 {p99_burst}ms outside (0, {allowed:.0f}] "
            f"(solo {p99_solo}ms, skew {skew:.1f}x)")
    if b_s["per_class"].get("BPUT", {}).get("count", 0) == 0:
        failures.append("adversary wrote nothing (vacuous burst)")
    if b_s["per_class"].get("BLIST", {}).get("count", 0) == 0:
        failures.append("adversary listed nothing (vacuous burst)")
    out["gates_passed"] = not failures
    out["gate_failures"] = failures
    return out


# ==================================================== repair-degraded-storm


REPAIR_GATE_SERIES: list[tuple[str, str]] = [
    ("/api/tpu", "minio_tpu_repair_partial_blocks_total"),
    ("/api/tpu", "minio_heal_ingress_bytes_total"),
    ("/api/tpu", "minio_tpu_degraded_ingress_bytes_total"),
    ("/api/tpu", "minio_tpu_decode_matrix_cache_total"),
    ("/api/fault", "minio_fault_repair_hedge_reads_total"),
    ("/api/fault", "minio_fault_repair_fallback_blocks_total"),
]


async def _verified_get_loop(cli: AsyncS3, keys: list[str], clients: int,
                             duration: float, size: int,
                             cls: str) -> tuple[Stats, int]:
    """Closed-loop zipf GETs over `keys`, every response byte-compared
    against tbody — a wrong byte anywhere (healthy or degraded) is a
    counted failure, never a silent one. Returns (stats, wrong_bytes)."""
    stats = Stats()
    wrong = 0
    cdf = zipf_cdf(len(keys))
    stop_at = time.monotonic() + duration

    async def one(cid: int) -> None:
        nonlocal wrong
        rng = random.Random(8191 * cid + 3)
        while time.monotonic() < stop_at:
            key = keys[bisect.bisect_left(cdf, rng.random())]
            t0 = time.perf_counter()
            try:
                st, data = await cli.request("GET", f"/{BUCKET}/{key}")
                stats.add(cls, time.perf_counter() - t0, len(data), st)
                if st == 200 and data != tbody(key, 0, size):
                    wrong += 1
                if st == 503:
                    await asyncio.sleep(0.5)
            except Exception:  # noqa: BLE001 — count, keep looping
                stats.add("ERR", time.perf_counter() - t0, 0, 599)

    t0 = time.monotonic()
    await asyncio.gather(*(one(i) for i in range(clients)))
    stats.wall = time.monotonic() - t0
    return stats, wrong


def _wipe_drive_bucket(base: str, idx: int) -> int:
    """The seeded drive failure: drop every object's shard data under
    one drive's bucket dir (the drive stays mounted — reads return
    FileNotFound, the degraded plane's bread-and-butter). Returns how
    many object dirs were dropped."""
    root = os.path.join(base, f"d{idx}", BUCKET)
    dropped = 0
    for ent in os.listdir(root):
        shutil.rmtree(os.path.join(root, ent), ignore_errors=True)
        dropped += 1
    return dropped


def _synthetic_heal_ingress(spec: dict) -> dict:
    """In-process SYNTHETIC measurement (no server, labelled as such in
    the output): the controlled single-lost-DATA-shard case the BENCH_r09
    ingress bound is defined over — survivor bytes a heal moves, per
    family. A byte count, which a CPU run may report. In-process because
    it needs per-object control the wire API doesn't expose: choosing
    WHICH shard is lost (data shard 0, the apples-to-apples repair-plan
    case — a whole-drive wipe mixes parity losses in, which
    repair_schedule correctly refuses)."""
    from minio_tpu.erasure.set import ErasureSet
    from minio_tpu.storage.xlstorage import XLStorage

    saved = {k: os.environ.get(k) for k in (
        "MINIO_TPU_EC_FAMILY", "MINIO_TPU_NATIVE_PLANE", "MINIO_TPU_HEDGE")}
    base = tempfile.mkdtemp(prefix="heal-ingress-")
    try:
        os.environ["MINIO_TPU_NATIVE_PLANE"] = "0"
        # the plan's bytes: a hedge on a slow host would add the
        # fallback's full frames to the cauchy side
        os.environ["MINIO_TPU_HEDGE"] = "0"
        body = tbody("ab", 0, spec["ingress_mib"] * MIB)
        ingress: dict[str, int] = {}
        for fam in ("reedsolomon", "cauchy"):
            os.environ["MINIO_TPU_EC_FAMILY"] = fam
            es = ErasureSet(
                [XLStorage(os.path.join(base, fam, f"d{i}"))
                 for i in range(16)],
                default_parity=8,
            )
            es.make_bucket("fam")
            es.put_object("fam", "o", body)
            fi, _ = es._cached_fileinfo("fam", "o", "")
            lost = fi.erasure.distribution.index(1)  # data shard 0's drive
            shutil.rmtree(os.path.join(base, fam, f"d{lost}", "fam", "o"))
            es.cache.clear()
            res = es.heal_object("fam", "o")
            assert res["healed"], f"{fam} heal failed: {res}"
            ingress[fam] = res["ingressBytes"]
        return {
            "label": "synthetic-in-process",
            "object_mib": spec["ingress_mib"],
            "heal_ingress_bytes": ingress,
            "cauchy_over_rs_ingress": round(
                ingress["cauchy"] / max(ingress["reedsolomon"], 1), 4),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(base, ignore_errors=True)


async def repair_storm_phase(ctx: Ctx) -> dict:
    spec = ctx.spec
    n, size = spec["objects"], spec["object_kb"] * 1024
    keys = hive_keys(n)
    rrs = {"x-amz-storage-class": "REDUCED_REDUNDANCY"}

    async with s3_session(ctx.port) as cli:
        c0 = await asyncio.to_thread(
            require_gate_series, ctx.port, REPAIR_GATE_SERIES)

        # populate: hive-partitioned keyspace, even keys cauchy
        # (STANDARD), odd keys reedsolomon (RRS pinned to the same EC:8
        # via the profile env) — the per-family comparison is over
        # identical shapes
        sem = asyncio.Semaphore(32)

        async def put_one(i: int, key: str) -> None:
            async with sem:
                st, _ = await cli.request(
                    "PUT", f"/{BUCKET}/{key}", body=tbody(key, 0, size),
                    read=False, headers=(rrs if i % 2 else None))
                assert st == 200, f"populate {key}: HTTP {st}"

        await asyncio.gather(*(put_one(i, k) for i, k in enumerate(keys)))

        healthy, wrong_h = await _verified_get_loop(
            cli, keys, spec["clients"], spec["healthy_s"], size, "HGET")

        # seeded failure schedule: one drive's data gone, one drive a
        # straggler, one drive throwing transient read errors
        dropped = await asyncio.to_thread(
            _wipe_drive_bucket, ctx.base, spec["wipe_drive"])
        for rule in (
            {"boundary": "storage", "mode": "latency", "op": "read_file",
             "target": os.path.join(ctx.base, f"d{spec['straggler_drive']}"),
             "latency_ms": spec["straggler_ms"],
             "prob": spec["straggler_prob"], "seed": 1207},
            {"boundary": "storage", "mode": "error", "op": "read_file",
             "target": os.path.join(ctx.base, f"d{spec['error_drive']}"),
             "prob": spec["error_prob"], "seed": 4311},
        ):
            r = await asyncio.to_thread(
                admin, ctx.port, "POST", "fault/inject",
                json.dumps(rule).encode())
            assert r.status == 200, (
                f"fault/inject: {r.status} {r.body[:200]}")

        with HealFlood(ctx.port) as flood:
            storm, wrong_s = await _verified_get_loop(
                cli, keys, spec["clients"], spec["storm_s"], size, "DGET")
            sweeps = flood.sweeps

        r = await asyncio.to_thread(admin, ctx.port, "POST", "fault/clear")
        assert r.status == 200, f"fault/clear: {r.status}"
        r = await asyncio.to_thread(
            admin, ctx.port, "POST", f"heal/{BUCKET}", b"", None, 300)
        assert r.status == 200, f"final heal: {r.status} {r.body[:200]}"

        # post-heal: every key byte-exact, sequentially (no sampling)
        wrong_f = errs_f = 0
        for key in keys:
            st, data = await cli.request("GET", f"/{BUCKET}/{key}")
            if st != 200:
                errs_f += 1
            elif data != tbody(key, 0, size):
                wrong_f += 1

        c1 = await asyncio.to_thread(
            require_gate_series, ctx.port, REPAIR_GATE_SERIES)
        heal_fam = await asyncio.to_thread(
            scrape_series, ctx.port, "/api/tpu",
            "minio_heal_ingress_bytes_total")

    synth = await asyncio.to_thread(_synthetic_heal_ingress, spec)

    healthy_s = healthy.summary(healthy.wall)
    storm_sum = storm.summary(storm.wall)
    p99_h = healthy_s["per_class"].get("HGET", {}).get("p99_ms", 0.0)
    p99_d = storm_sum["per_class"].get("DGET", {}).get("p99_ms", 0.0)
    deltas = {s: c1[s] - c0[s] for _, s in REPAIR_GATE_SERIES}

    out = {
        "objects": n,
        "object_kb": spec["object_kb"],
        "keyspace": "hive-partitioned",
        "objects_dropped_on_failed_drive": dropped,
        "healthy": healthy_s,
        "storm": storm_sum,
        "post_heal_verified": n - wrong_f - errs_f,
        "heal_sweeps": sweeps,
        "healthy_get_p99_ms": p99_h,
        "degraded_get_p99_ms": p99_d,
        "p99_band_mult": spec["p99_band_mult"],
        "repair_series_delta": deltas,
        "heal_ingress_by_family_server": heal_fam,
        "synthetic": synth,
    }

    failures = []
    if wrong_h or wrong_s or wrong_f:
        failures.append(
            f"wrong bytes served: healthy {wrong_h}, storm {wrong_s}, "
            f"post-heal {wrong_f}")
    if healthy_s["errors"] or storm_sum["errors"] or errs_f:
        failures.append(
            f"GET errors: healthy {healthy_s['errors']}, storm "
            f"{storm_sum['errors']}, post-heal {errs_f} (the degraded "
            "plane must mask 2 bad drives at EC 8+8)")
    allowed = max(spec["p99_band_mult"] * p99_h, spec["p99_floor_ms"])
    if not p99_d or p99_d > allowed:
        failures.append(
            f"degraded GET p99 {p99_d}ms outside (0, {allowed:.0f}] "
            f"(healthy {p99_h}ms, band {spec['p99_band_mult']}x)")
    if deltas["minio_tpu_repair_partial_blocks_total"] <= 0:
        failures.append("sub-chunk partial repair never engaged "
                        "(repair_partial_blocks flat across the storm)")
    if deltas["minio_tpu_decode_matrix_cache_total"] <= 0:
        failures.append("decode-matrix cache never consulted")
    ratio = synth["cauchy_over_rs_ingress"]
    if ratio > spec["ingress_ratio_max"]:
        failures.append(
            f"cauchy heal ingress {ratio:.3f}x rs > "
            f"{spec['ingress_ratio_max']} (BENCH_r09 bound regressed)")
    if sweeps == 0:
        failures.append("heal flood swept nothing (vacuous storm)")
    out["gates_passed"] = not failures
    out["gate_failures"] = failures
    return out


# =============================================================== registry


PROFILES: dict[str, Profile] = {p.name: p for p in [
    Profile(
        name="small-object-storm",
        summary="10^5+ inline KB objects; metadata ops/s + listing p99; "
                "zero user-plane shard I/O; O(1) walks per page",
        drives=4,
        workers=2,
        scan_interval=300.0,
        env={
            # TTL is the CROSS-WORKER staleness backstop (a peer
            # worker's PUT can't bump this worker's invalidation seq),
            # so it must sit above one full two-pass sweep: on a 1-core
            # box ~150s of paging wall, else entries built early in
            # pass 1 age out mid-pass-2 and the zero-walk gate measures
            # TTL churn, not cache behaviour. Churn-driven coherence is
            # still exercised every round via the choke-point
            # invalidations the PUTs trigger on both workers.
            "MINIO_TPU_METACACHE_TTL": "600",
            "MINIO_TPU_METACACHE_SHARD_KEYS": "8192",
        },
        gate_series=[
            ("/api/cache", "minio_storage_shard_io_total"),
            ("/api/cache", "minio_cache_metacache_walks_total"),
            ("/api/cache", "minio_cache_metacache_requests_total"),
        ],
        quick_spec={
            "objects": 400, "object_kb": 1, "clients": 24, "churn_s": 3.0,
            "rounds": 1, "page": 50, "sweep_clients": 4,
            "min_pages_per_walk": 1.2,
        },
        full_spec={
            "objects": 100_000, "object_kb": 1, "clients": 64,
            "churn_s": 8.0, "rounds": 5, "page": 1000, "sweep_clients": 8,
            "min_pages_per_walk": 8.0, "synthetic_keys": 1_000_000,
        },
        phase=storm_phase,
    ),
    Profile(
        name="ml-dataloader-shuffle",
        summary="random 1..N MiB ranged GETs over large objects, 2 "
                "epochs; epoch-2 segment hit ratio + byte-exact ranges",
        drives=4,
        workers=1,
        scan_interval=300.0,
        env={"MINIO_TPU_CACHE_MEM_MB": "128",
             "MINIO_TPU_CACHE_DISK_MB": "0"},
        gate_series=[
            ("/api/cache", "minio_cache_segment_range_requests_total"),
            ("/api/cache", "minio_cache_prefetch_runs_total"),
        ],
        quick_spec={
            "objects": 2, "object_mib": 8, "range_mib_max": 2,
            "loaders": 4, "min_hit_ratio": 0.3, "p99_max_ms": 5000.0,
        },
        full_spec={
            "objects": 4, "object_mib": 256, "range_mib_max": 8,
            "loaders": 16, "min_hit_ratio": 0.3, "p99_max_ms": 8000.0,
        },
        phase=shuffle_phase,
    ),
    Profile(
        name="backup-restore",
        summary="multipart-heavy sequential streams then verified "
                "restore; sustained MiB/s + bounded server RSS",
        drives=8,
        workers=1,
        scan_interval=300.0,
        env={},
        gate_series=[
            ("/api/requests", "minio_api_requests_total"),
        ],
        quick_spec={
            "streams": 2, "parts": 4, "part_mib": 1,
            "rss_headroom_mb": 900,
        },
        full_spec={
            "streams": 2, "parts": 16, "part_mib": 8,
            "rss_headroom_mb": 1400,
        },
        phase=backup_restore_phase,
    ),
    Profile(
        name="multi-tenant-burst",
        summary="tenant A pinned to pool 0; B expands live, floods "
                "PUT/LIST + heal; fg_deferred flat + bounded p99 skew",
        drives=4,
        workers=1,  # online topology changes require a single process
        scan_interval=300.0,
        env={},
        gate_series=[
            ("/api/qos", "minio_tpu_dispatch_fg_deferred_behind_bg_total"),
        ],
        quick_spec={
            "a_keys": 48, "obj_kb": 8, "a_clients": 8, "solo_s": 2.5,
            "burst_s": 4.0, "b_put_clients": 4, "b_list_clients": 2,
            "burst_put_kb": 512, "expand_drives": 4,
            "skew_max": 60.0, "p99_floor_ms": 400.0,
        },
        full_spec={
            "a_keys": 256, "obj_kb": 8, "a_clients": 32, "solo_s": 8.0,
            "burst_s": 15.0, "b_put_clients": 8, "b_list_clients": 4,
            "burst_put_kb": 2048, "expand_drives": 8,
            "skew_max": 25.0, "p99_floor_ms": 400.0,
        },
        phase=burst_phase,
    ),
    Profile(
        name="repair-degraded-storm",
        summary="seeded drive failure + stragglers under verifying "
                "traffic + heal flood; p99 band, zero wrong bytes, "
                "cauchy ingress bound",
        drives=16,  # EC 8+8: every object stripes across all drives
        workers=1,  # fault registry + counters live per-process
        scan_interval=300.0,
        env={
            # both families at the same EC 8+8 geometry: storage class
            # selects the family, not the parity
            "MINIO_TPU_EC_FAMILY_STANDARD": "cauchy",
            "MINIO_TPU_EC_FAMILY_RRS": "reedsolomon",
            "MINIO_STORAGE_CLASS_RRS": "EC:8",
        },
        gate_series=REPAIR_GATE_SERIES,
        quick_spec={
            "objects": 24, "object_kb": 256, "clients": 8,
            "healthy_s": 2.5, "storm_s": 4.0,
            "wipe_drive": 3, "straggler_drive": 5, "error_drive": 7,
            "straggler_ms": 80.0, "straggler_prob": 0.3,
            "error_prob": 0.08,
            "p99_band_mult": 30.0, "p99_floor_ms": 600.0,
            "ingress_ratio_max": 0.75,
            "ingress_mib": 2,
        },
        full_spec={
            "objects": 96, "object_kb": 256, "clients": 24,
            "healthy_s": 6.0, "storm_s": 15.0,
            "wipe_drive": 3, "straggler_drive": 5, "error_drive": 7,
            "straggler_ms": 120.0, "straggler_prob": 0.3,
            "error_prob": 0.08,
            "p99_band_mult": 12.0, "p99_floor_ms": 500.0,
            "ingress_ratio_max": 0.75,
            "ingress_mib": 8,
        },
        phase=repair_storm_phase,
    ),
]}


# ================================================================= runner


def run_profile(name: str, quick: bool, port: int) -> dict:
    """Bring up the profile's server shape, check every gate series is
    scrapeable (loud failure, never vacuous), run the phase, tear down."""
    prof = PROFILES[name]
    spec = prof.quick_spec if quick else prof.full_spec
    base = tempfile.mkdtemp(prefix=f"scn-{prof.name}-")
    srv = Server(base, port, prof.drives, prof.workers,
                 scan_interval=prof.scan_interval, extra_env=prof.env)
    try:
        cli = S3Client(f"127.0.0.1:{port}")
        assert cli.make_bucket(BUCKET).status == 200
        presence = require_gate_series(port, prof.gate_series)
        ctx = Ctx(port=port, base=base, pid=srv.proc.pid, spec=spec,
                  quick=quick)
        t0 = time.monotonic()
        out = asyncio.run(prof.phase(ctx))
        # machine fingerprint (cores, drive MiB/s, grid loopback MiB/s)
        # via the diag plane — raises if any selftest series is missing,
        # so a BENCH json can never ship without one
        fingerprint = selftest_fingerprint(port)
        out.update({
            "profile": prof.name,
            "quick": quick,
            "drives": prof.drives,
            "workers": prof.workers,
            "nproc": os.cpu_count(),
            "wall_s": round(time.monotonic() - t0, 1),
            "gate_series_checked": sorted(presence),
            "fingerprint": fingerprint,
        })
        if out["gate_failures"]:
            print(f"PROFILE {prof.name} GATES FAILED: "
                  f"{out['gate_failures']}", file=sys.stderr, flush=True)
        return out
    finally:
        srv.stop()
        shutil.rmtree(base, ignore_errors=True)

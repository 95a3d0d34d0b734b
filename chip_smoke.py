#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Boots `python -m minio_tpu.server` (the normal entry point, one worker,
default backend -> device) over 16 drive directories and drives it over
HTTP with SigV4 through `minio_tpu.client.S3Client`, once per geometry:

- EC 8+8 (`MINIO_STORAGE_CLASS_STANDARD=EC:8`, 128 KiB shards): the fused
  Pallas mega-kernel rung;
- EC 12+4 (storage class unset — what a 16-drive set gets by default,
  87,382-byte shards): the row-major XLA rung + the Pallas hash chain.

Per geometry: concurrent PUTs of seeded 64 MiB objects (>= 512 MiB),
GET + byte/ETag compare, on-drive parity and bitrot digests of a first
stripe block against the plain reference (ops/rs.py + ops/highwayhash.py),
a degraded GET with two data shards removed, admin heal, and a GET that
has to read the healed shards. Then the server's own counters must show
that the DEVICE did the work: the backend ladder (docs/ROBUSTNESS.md)
would otherwise answer every request correctly from the CPU.

A `kernels` child first compares each kernel at production shape with the
numpy reference, measures H2D/D2H, and checks that block_until_ready
blocks. This parent process never imports jax (a chip belongs to one
process at a time): every phase that needs the chip is one child at a
time, and the device named in the last line comes from those children.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`
only if every phase passed on a TPU. `--rehearse` is the CPU rehearsal
(tiny sizes, JAX_PLATFORMS=cpu, prints "platform": "cpu"): it checks the
script's control flow, never the chip. `--inject-fault MODE` arms a
`tpu`-boundary fault rule in each server before traffic — the run must
then FAIL (tests use it to show the script cannot be fooled by the
ladder).
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.metadata
import json
import os
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20
BLOCK = MIB  # stripe block (erasure/coder.py BLOCK_SIZE)
DRIVES = 16
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included

# (name, MINIO_STORAGE_CLASS_STANDARD or None, data, parity)
GEOMETRIES = (("ec8+8", "EC:8", 8, 8), ("ec12+4", None, 12, 4))


class SmokeFailure(Exception):
    pass


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def object_bytes(seed: int, geometry: int, index: int, size: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, geometry, index]).bytes(size)


# --------------------------------------------------------------------------
# kernels child: the only code in this file that imports jax
# --------------------------------------------------------------------------


def child_kernels(args) -> int:
    """Each kernel at production shape vs the numpy reference, byte for
    byte; transfer rates; whether block_until_ready blocks. Runs in its
    own process, which holds the chip while it runs."""
    import numpy as np

    from minio_tpu.ops import runtime

    cache_dir = runtime.ensure_compile_cache()
    import jax

    dev = runtime.device_info()
    if not args.rehearse and dev["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU — JAX reports platform={dev['platform']} "
            f"kind={dev['kind']!r}; this is not a chip run "
            "(use --rehearse for the CPU rehearsal)",
            file=sys.stderr,
        )
        return 3
    emit(phase="device", **dev, compile_cache=cache_dir)
    on_tpu = dev["platform"] == "tpu"

    from minio_tpu.ops import bitrot_jax, fused_pallas as fp
    from minio_tpu.ops.bitrot_pallas import hash256_blocks_pallas
    from minio_tpu.ops.highwayhash import hash256_batch_numpy
    from minio_tpu.ops.rs import get_codec
    from minio_tpu.ops.rs_jax import get_tpu_codec

    rng = np.random.default_rng([args.seed, 99])

    def reference(d, p, blocks):
        """[B, d, n] -> (parity [B, p, n], digests [B, d+p, 32])."""
        ref = get_codec(d, p)
        b, _, n = blocks.shape
        shards = np.zeros((b, d + p, n), dtype=np.uint8)
        shards[:, :d] = blocks
        for i in range(b):
            shards[i] = ref.encode(shards[i])
        digests = hash256_batch_numpy(
            shards.reshape(b * (d + p), n)
        ).reshape(b, d + p, 32)
        return shards[:, d:], digests

    def timed(fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    # -- fused mega-kernel: encode at the floor bucket and at bench.py's
    # shape (192 = MAX_DEVICE_SHARDS // 16), decode with 2 data shards lost
    if on_tpu:
        d, p, n = 8, 8, BLOCK // 8
        for b in (16, 192):
            blocks = rng.integers(0, 256, size=(b, d, n), dtype=np.uint8)
            dd = jax.device_put(fp.pack_chunk_major(blocks))
            (parity_cm, digests), first_s = timed(
                lambda: fp.fused_encode_hash_cm(dd, d, p)
            )
            want_par, want_dig = reference(d, p, blocks)
            got_par = fp.unpack_chunk_major(np.asarray(parity_cm))
            check((got_par == want_par).all(),
                  f"fused encode 8+8 B={b}: parity != numpy reference")
            check((np.asarray(digests) == want_dig).all(),
                  f"fused encode 8+8 B={b}: digests != numpy HighwayHash")
            emit(phase="kernel", kernel="fused_encode_hash_cm", ec="8+8",
                 batch=b, shard_bytes=n, equal=True, first_call_s=first_s)
            if b == 192:
                sync_check(lambda: fp.fused_encode_hash_cm(dd, d, p))
        missing = (1, 5)
        present = tuple(i for i in range(d + p) if i not in missing)[:d]
        blocks = rng.integers(0, 256, size=(16, d, n), dtype=np.uint8)
        want_par, want_dig = reference(d, p, blocks)
        full = np.concatenate([blocks, want_par], axis=1)
        surv = np.ascontiguousarray(full[:, list(present)])
        (rebuilt_cm, digests), first_s = timed(
            lambda: fp.fused_decode_hash_cm(
                jax.device_put(fp.pack_chunk_major(surv)), d, p, present, missing
            )
        )
        rebuilt = fp.unpack_chunk_major(np.asarray(rebuilt_cm))
        check((rebuilt == full[:, list(missing)]).all(),
              "fused decode 8+8 (2 data shards lost): rebuilt != original")
        digs = np.asarray(digests)
        check((digs[:, :d] == want_dig[:, list(present)]).all()
              and (digs[:, d:] == want_dig[:, list(missing)]).all(),
              "fused decode 8+8: digests != numpy HighwayHash")
        emit(phase="kernel", kernel="fused_decode_hash_cm", ec="8+8",
             batch=16, missing=list(missing), equal=True, first_call_s=first_s)
    else:
        emit(phase="kernel", kernel="fused_pallas", skipped="Mosaic needs a TPU")

    # -- Pallas hash chain at both production shard lengths (87382 % 32 ==
    # 22: the tail packet); off-TPU the wrapper takes its XLA branch
    for b, n in ((256, BLOCK // 8), (256, 87382)) if on_tpu else ((8, 4096), (8, 1014)):
        blocks = rng.integers(0, 256, size=(b, n), dtype=np.uint8)
        got, first_s = timed(lambda: hash256_blocks_pallas(blocks))
        check((np.asarray(got) == hash256_batch_numpy(blocks)).all(),
              f"hash256_blocks_pallas ({b}, {n}) != numpy HighwayHash")
        emit(phase="kernel", kernel="hash256_blocks_pallas", batch=b,
             shard_bytes=n, equal=True, first_call_s=first_s)

    # -- the XLA rung a default 16-drive deployment gets
    d, p = 12, 4
    b, n = (16, -(-BLOCK // d)) if on_tpu else (2, 1014)
    blocks = rng.integers(0, 256, size=(b, d, n), dtype=np.uint8)
    codec = get_tpu_codec(d, p)
    (parity, digests), first_s = timed(
        lambda: bitrot_jax.encode_and_hash(codec, blocks)
    )
    want_par, want_dig = reference(d, p, blocks)
    check((np.asarray(parity) == want_par).all(),
          "encode_and_hash 12+4: parity != numpy reference")
    check((np.asarray(digests) == want_dig).all(),
          "encode_and_hash 12+4: digests != numpy HighwayHash")
    emit(phase="kernel", kernel="encode_and_hash", ec="12+4", batch=b,
         shard_bytes=n, equal=True, first_call_s=first_s)
    if not on_tpu:
        sync_check(lambda: bitrot_jax.encode_and_hash(codec, blocks))

    # -- host <-> device transfer of one buffer, medians of 5, unrounded
    nbytes = (256 if on_tpu else 4) * MIB
    host = rng.integers(0, 256, size=nbytes, dtype=np.uint8)
    h2d, d2h = [], []
    for _ in range(5):
        on_dev, dt = timed(lambda: jax.device_put(host))
        h2d.append(dt)
        fresh = jax.block_until_ready(on_dev + np.uint8(1))  # no cached host copy
        t0 = time.perf_counter()
        back = np.asarray(fresh)
        d2h.append(time.perf_counter() - t0)
        check(back[0] == np.uint8(host[0] + 1), "D2H returned wrong bytes")
    gib = nbytes / 2**30
    emit(phase="transfer", bytes=nbytes, device_kind=dev["kind"],
         h2d_gibps=gib / statistics.median(h2d),
         d2h_gibps=gib / statistics.median(d2h),
         h2d_s=h2d, d2h_s=d2h)
    emit(phase="compile", process="kernels", **runtime.compile_stats())
    return 0


def sync_check(dispatch) -> None:
    """Does block_until_ready block? Chain dispatches, then compare the
    time to block_until_ready with the time to a forced scalar fetch of
    the same result (which cannot return before the device is done)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def checksum(out):
        return sum(jnp.sum(x[..., :1].astype(jnp.int32)) for x in out)

    iters = 10
    int(checksum(dispatch()))  # warm both programs
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dispatch()
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready(out)
    bur_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    int(checksum(out))
    fetch_after_s = time.perf_counter() - t1
    t0 = time.perf_counter()
    for _ in range(iters):
        out = dispatch()
    int(checksum(out))
    fetch_s = time.perf_counter() - t0
    blocks = bur_s >= 0.5 * fetch_s
    emit(phase="sync", chained_dispatches=iters, enqueue_s=enqueue_s,
         block_until_ready_s=bur_s, scalar_fetch_after_it_s=fetch_after_s,
         scalar_fetch_instead_s=fetch_s, block_until_ready_blocks=blocks)
    check(blocks, "block_until_ready returned before the device finished: "
          "bench.py and the TPU-lane tests sync with it")


# --------------------------------------------------------------------------
# parent: servers, traffic, checks. No jax here.
# --------------------------------------------------------------------------


def rebuild_native() -> None:
    """Build products are git-ignored, and a copied working tree carries
    another machine's binary: remove them, rebuild from the committed
    sources, and require both native planes (a failed build would
    silently leave the pure-Python ones)."""
    ndir = os.path.join(HERE, "minio_tpu", "native")
    removed = []
    for name in sorted(os.listdir(ndir)):
        if name.endswith((".so", ".so.tmp")):
            os.remove(os.path.join(ndir, name))
            removed.append(name)
    t0 = time.perf_counter()
    from minio_tpu import native

    ok, dp = native.available(), native.dataplane_available()
    emit(phase="native", removed=removed, available=ok,
         dataplane_available=dp, build_s=time.perf_counter() - t0)
    check(ok and dp, "native library did not build from the committed sources")


def versions() -> None:
    row = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            row[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            row[pkg] = None
    emit(phase="versions", python=sys.version.split()[0], **row)


def child_env(rehearse: bool) -> dict:
    """Inherited environment without any MINIO_* routing (in particular
    no MINIO_TPU_BACKEND: the default must reach the device)."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("MINIO_")}
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        # the CPU rehearsal forces the device plane onto XLA's CPU backend
        env["MINIO_TPU_BACKEND"] = "jax"
    return env


def run_kernels_child(args) -> dict:
    check("jax" not in sys.modules, "parent imported jax before a child")
    cmd = [sys.executable, os.path.abspath(__file__), "--child", "kernels",
           "--seed", str(args.seed)] + (["--rehearse"] if args.rehearse else [])
    proc = subprocess.run(
        cmd, cwd=HERE, env=child_env(args.rehearse), stdout=subprocess.PIPE,
        text=True, timeout=DEADLINE_S,
    )
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    check(proc.returncode == 0, f"kernels child exited {proc.returncode}")
    rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    dev = next(r for r in rows if r.get("phase") == "device")
    return {
        "device": {k: dev[k] for k in ("platform", "kind", "count")},
        "compile": next(r for r in rows if r.get("phase") == "compile"),
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def scrape(port: int, group: str) -> dict:
    """metrics-v3 group -> {series name: [(labels, value)]}."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", f"/minio/metrics/v3{group}")
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    check(resp.status == 200, f"scrape {group} -> {resp.status}")
    out: dict = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for item in rest.rstrip("}").split(","):
            if "=" in item:
                k, _, v = item.partition("=")
                labels[k] = v.strip('"')
        out.setdefault(name, []).append((labels, float(val)))
    return out


def total(series: dict, name: str, **match) -> float:
    check(name in series, f"metric {name} is not exported")
    return sum(v for labels, v in series[name]
               if all(labels.get(k) == w for k, w in match.items()))


class Server:
    """One `python -m minio_tpu.server` subprocess over 16 drive dirs."""

    def __init__(self, root: str, env: dict, storage_class: str | None):
        self.root = root
        self.port = free_port()
        self.drives = [os.path.join(root, f"d{i:02d}") for i in range(DRIVES)]
        env = dict(env)
        env["MINIO_TPU_SCAN_INTERVAL"] = "0"
        env["MINIO_PROMETHEUS_AUTH_TYPE"] = "public"
        if storage_class:
            env["MINIO_STORAGE_CLASS_STANDARD"] = storage_class
        self.log_path = os.path.join(root, "server.log")
        self._log = open(self.log_path, "wb")
        check("jax" not in sys.modules, "parent imported jax before a child")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu.server",
             "--address", f"127.0.0.1:{self.port}", *self.drives],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def alive(self) -> None:
        check(self.proc.poll() is None,
              f"server exited early with code {self.proc.returncode}")

    def wait_ready(self, cli, bucket: str) -> None:
        """Listener up, then the object layer (S3 answers 503 while the
        bootstrap runs): make_bucket must come back 200."""
        deadline = time.monotonic() + 120
        status = None
        while time.monotonic() < deadline:
            self.alive()
            try:
                status = cli.make_bucket(bucket).status
            except OSError:
                status = None
            if status == 200:
                return
            time.sleep(0.25)
        raise SmokeFailure(f"make_bucket({bucket}) never answered 200 (last {status})")

    def log_tail(self, nbytes: int = 16000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:  # whatever is left of its process group
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()


def shard_drives(srv: Server, bucket: str, key: str) -> dict:
    """{erasure index (1-based): (drive dir, data_dir)} from each drive's
    own xl.meta — the layout as written, not as assumed."""
    from minio_tpu.storage.xlstorage import XLStorage

    out = {}
    for drive in srv.drives:
        if not os.path.isdir(os.path.join(drive, bucket, key)):
            continue
        fi = XLStorage(drive).read_version(bucket, key, "")
        out[fi.erasure.index] = (drive, fi.data_dir)
    return out


def check_on_drive(srv, bucket, key, body: bytes, d: int, p: int, only=None) -> int:
    """First stripe block of `key`: parity and per-shard digests recomputed
    by the plain reference vs the digest||shard frames on the drives."""
    import numpy as np

    from minio_tpu.ops.highwayhash import hash256_batch_numpy
    from minio_tpu.ops.rs import get_codec

    ref = get_codec(d, p)
    shards = ref.split(body[:BLOCK])  # [d+p, shard_size], zero-padded tail
    shards = ref.encode(shards)
    digests = hash256_batch_numpy(shards)
    n = shards.shape[1]
    layout = shard_drives(srv, bucket, key)
    checked = 0
    for idx, (drive, data_dir) in sorted(layout.items()):
        if only is not None and idx not in only:
            continue
        with open(os.path.join(drive, bucket, key, data_dir, "part.1"), "rb") as f:
            frame = f.read(32 + n)
        check(frame[:32] == digests[idx - 1].tobytes(),
              f"{key}: on-drive digest of shard {idx} != numpy HighwayHash")
        check(frame[32:] == shards[idx - 1].tobytes(),
              f"{key}: on-drive bytes of shard {idx} != numpy reference "
              f"({'parity' if idx > d else 'data'})")
        checked += 1
    want = len(only) if only is not None else d + p
    check(checked == want, f"{key}: found {checked} of {want} shards on the drives")
    return checked


def remove_shards(srv, bucket, key, indices) -> list[str]:
    """Remove the OBJECT dir (never the bucket volume — heal does not
    recreate volumes) on the drives holding these erasure indices."""
    layout = shard_drives(srv, bucket, key)
    gone = []
    for idx in indices:
        drive, _ = layout[idx]
        shutil.rmtree(os.path.join(drive, bucket, key))
        gone.append(drive)
    return gone


def run_geometry(args, gi: int, sizes: dict, kernel_dev: dict) -> dict:
    from minio_tpu.client import S3Client

    name, storage_class, d, p = GEOMETRIES[gi]
    real = not args.rehearse
    root = tempfile.mkdtemp(prefix=f"chip-smoke-{name}-")
    srv = Server(root, child_env(args.rehearse), storage_class)
    try:
        cli = S3Client(f"127.0.0.1:{srv.port}")
        bucket = f"smoke-{name.replace('+', 'p')}"
        t_boot = time.perf_counter()
        srv.wait_ready(cli, bucket)
        boot_s = time.perf_counter() - t_boot
        if args.inject_fault:
            r = cli.admin("POST", "fault/inject", body={
                "boundary": "tpu", "mode": args.inject_fault, "seed": args.seed})
            check(r.status == 200, f"fault/inject -> {r.status} {r.body[:200]!r}")

        size, n_obj = sizes["object_bytes"], sizes["objects"]
        blocks_per_obj = size // BLOCK
        keys = [f"obj-{i:02d}" for i in range(n_obj)]
        etags, put_s = {}, {}

        def put(i: int) -> None:
            body = object_bytes(args.seed, gi, i, size)
            t0 = time.perf_counter()
            # unsigned payload: the streaming-PUT plane (server/auth.py)
            r = cli.request("PUT", f"/{bucket}/{keys[i]}", body=body,
                            unsigned_payload=True, timeout=900)
            put_s[i] = time.perf_counter() - t0
            check(r.status == 200, f"PUT {keys[i]} -> {r.status} {r.body[:300]!r}")
            etags[i] = r.headers.get("etag", "").strip('"')
            check(etags[i] == hashlib.md5(body).hexdigest(),
                  f"PUT {keys[i]}: ETag {etags[i]} != client md5")

        def fan_out(fn, items) -> None:
            """`clients` threads over `items`; the first failure re-raises."""
            items, errs, mu = list(items), [], threading.Lock()

            def worker():
                while not errs:
                    with mu:
                        if not items:
                            return
                        it = items.pop(0)
                    try:
                        fn(it)
                    except BaseException as e:  # noqa: BLE001 — re-raised below
                        errs.append(e)

            ts = [threading.Thread(target=worker) for _ in range(sizes["clients"])]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if errs:
                raise errs[0]

        # the first PUT alone (cold: backend init + first compiles on the
        # dispatch thread), then the rest from `clients` concurrent clients
        put(0)
        srv.alive()
        t0 = time.perf_counter()
        fan_out(put, range(1, n_obj))
        put_wall_s = time.perf_counter() - t0
        srv.alive()

        def get_and_compare(i: int, what: str) -> float:
            t0 = time.perf_counter()
            r = cli.request("GET", f"/{bucket}/{keys[i]}", timeout=900)
            dt = time.perf_counter() - t0
            check(r.status == 200, f"{what} GET {keys[i]} -> {r.status}")
            check(r.headers.get("etag", "").strip('"') == etags[i],
                  f"{what} GET {keys[i]}: ETag changed")
            check(r.body == object_bytes(args.seed, gi, i, size),
                  f"{what} GET {keys[i]}: bytes differ")
            return dt

        get_s = {}
        fan_out(lambda i: get_s.__setitem__(i, get_and_compare(i, "healthy")),
                range(n_obj))
        body0 = object_bytes(args.seed, gi, 0, size)
        on_drive = check_on_drive(srv, bucket, keys[0], body0, d, p)

        # degraded GET: two DATA shards of one object gone
        victim = 1 % n_obj
        check(cli.admin("POST", "cache/clear").status == 200, "cache/clear failed")
        gone = remove_shards(srv, bucket, keys[victim], (1, 2))
        before = scrape(srv.port, "/api/tpu")
        degraded_s = get_and_compare(victim, "degraded")
        after = scrape(srv.port, "/api/tpu")

        def delta(metric, **m):
            return total(after, metric, **m) - total(before, metric, **m)

        # the rung the code intends: the decode mega-kernel where its
        # shape gate passes (d <= 8, on a TPU), else the XLA rung; a
        # window group below MINIO_TPU_DECODE_MIN_SHARDS goes to the host
        want_rung = "fused" if (real and d <= 8) else "xla"
        other = "xla" if want_rung == "fused" else "fused"
        dec = {
            "rung_expected": want_rung,
            "dispatches": delta("minio_tpu_decode_dispatches_total", rung=want_rung),
            "other_rung_dispatches":
                delta("minio_tpu_decode_dispatches_total", rung=other),
            "device_blocks": delta("minio_tpu_decode_device_blocks_total"),
            "blocks": delta("minio_tpu_decode_blocks_total"),
            "fused_decode_failures":
                total(after, "minio_tpu_fused_decode_failures_total"),
        }
        dec["host_blocks"] = dec["blocks"] - dec["device_blocks"]
        if not args.inject_fault:
            check(dec["dispatches"] > 0 and dec["other_rung_dispatches"] == 0
                  and dec["fused_decode_failures"] == 0
                  and dec["device_blocks"] >= blocks_per_obj / 2,
                  f"degraded GET was not rebuilt on the {want_rung} rung: {dec}")

        # heal, then make the healed shards carry a read: remove two OTHER
        # data shards, so the GET must use shards 1 and 2 as rebuilt
        r = cli.admin("POST", f"heal/{bucket}/{keys[victim]}")
        check(r.status == 200, f"heal -> {r.status} {r.body[:300]!r}")
        healed = json.loads(r.body)
        check(healed.get("failed") == 0 and len(healed.get("healed", [])) == 2,
              f"heal did not rebuild both shards: {healed}")
        victim_body = object_bytes(args.seed, gi, victim, size)
        check_on_drive(srv, bucket, keys[victim], victim_body, d, p, only=(1, 2))
        check(cli.admin("POST", "cache/clear").status == 200, "cache/clear failed")
        remove_shards(srv, bucket, keys[victim], (3, 4))
        post_heal_s = get_and_compare(victim, "post-heal")
        srv.alive()

        # did the DEVICE do the work?
        tpu = scrape(srv.port, "/api/tpu")
        flt = scrape(srv.port, "/api/fault")
        full_blocks = n_obj * blocks_per_obj
        counters = {
            "full_blocks_put": full_blocks,
            "dispatch_blocks": total(tpu, "minio_tpu_dispatch_blocks_total"),
            "dispatches": total(tpu, "minio_tpu_dispatch_total"),
            "batch_max_blocks": total(tpu, "minio_tpu_batch_max_blocks"),
            "fused_dispatches": total(tpu, "minio_tpu_fused_dispatches_total"),
            "fused_failures": total(tpu, "minio_tpu_fused_failures_total"),
            # dispatcher thread's split: device window (H2D + execute +
            # D2H, first-call trace/compile included) vs host assembly
            "device_seconds": total(tpu, "minio_tpu_device_seconds_total"),
            "host_seconds": total(tpu, "minio_tpu_host_seconds_total"),
            "numpy_blocks": total(flt, "minio_tpu_backend_numpy_blocks_total"),
            "device_faults": total(flt, "minio_tpu_backend_device_faults_total"),
            "backend_level": total(flt, "minio_tpu_backend_level"),
        }
        check("minio_tpu_device_info" in tpu and tpu["minio_tpu_device_info"],
              "server exports no minio_tpu_device_info: no device plane ran")
        sdev = tpu["minio_tpu_device_info"][0][0]
        server_dev = {"platform": sdev["platform"], "kind": sdev["kind"],
                      "count": int(sdev["count"])}
        compile_row = {
            "programs": total(tpu, "minio_tpu_compile_programs_total"),
            "compile_s": total(tpu, "minio_tpu_compile_seconds_total"),
            "cache_hits": total(tpu, "minio_tpu_compile_cache_total", result="hit"),
            "cache_misses": total(tpu, "minio_tpu_compile_cache_total", result="miss"),
        }
        rest = sorted(v for i, v in put_s.items() if i != 0)
        emit(phase="geometry", geometry=name, storage_class=storage_class,
             drives=DRIVES, shard_bytes=-(-BLOCK // d), device=server_dev,
             objects=n_obj, object_bytes=size, clients=sizes["clients"],
             bytes_written=n_obj * size, boot_s=boot_s,
             first_put_s=put_s[0], rest_put_median_s=statistics.median(rest),
             rest_put_max_s=rest[-1], concurrent_put_wall_s=put_wall_s,
             get_median_s=statistics.median(get_s.values()),
             bytes_and_etags_equal=True, on_drive_shards_equal_reference=on_drive,
             degraded_get_s=degraded_s, degraded_get_equal=True,
             degraded_decode=dec, shards_removed_from=gone,
             heal=healed, healed_shards_equal_reference=True,
             post_heal_get_s=post_heal_s, post_heal_get_equal=True,
             counters=counters, compile=compile_row,
             heal_rung="host (MINIO_TPU_DEVICE_HEAL defaults off)")

        check(server_dev == kernel_dev,
              f"server ran on {server_dev}, kernels child on {kernel_dev}")
        check(counters["dispatch_blocks"] >= full_blocks,
              f"dispatcher saw {counters['dispatch_blocks']} blocks, "
              f"{full_blocks} full blocks were PUT")
        check(counters["numpy_blocks"] == 0,
              f"{counters['numpy_blocks']} blocks were served by the numpy rung")
        check(counters["device_faults"] == 0,
              f"{counters['device_faults']} device faults")
        check(counters["backend_level"] == 2,
              f"backend_level {counters['backend_level']} != 2")
        if real and d <= 8:
            check(counters["fused_dispatches"] > 0,
                  "the fused mega-kernel never dispatched")
        check(counters["fused_failures"] == 0,
              f"{counters['fused_failures']} fused mega-kernel failures")
        return {"device": server_dev, "compile": compile_row}
    except BaseException:
        print(f"--- server log tail ({name}) ---\n{srv.log_tail()}\n--- end ---",
              file=sys.stderr, flush=True)
        raise
    finally:
        srv.stop()
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated byte (default 0)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny size; never a chip result")
    ap.add_argument("--inject-fault", metavar="MODE", default="",
                    help="arm a tpu-boundary fault rule (kernel-fail, "
                    "device-lost) in each server: the run must then fail")
    ap.add_argument("--child", choices=["kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(HERE, "minio_tpu")):
        print("chip_smoke: minio_tpu/ is not beside this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    if args.child == "kernels":
        return child_kernels(args)

    def on_alarm(_sig, _frm):
        raise SmokeFailure(f"not done after {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    sizes = (
        {"object_bytes": 8 * MIB, "objects": 3, "clients": 2}
        if args.rehearse
        # BASELINE.json config 2 / upstream speedtest: 64 MiB objects, 8
        # concurrent clients; one cold PUT + 8 concurrent = 576 MiB
        else {"object_bytes": 64 * MIB, "objects": 9, "clients": 8}
    )
    emit(phase="start", seed=args.seed, rehearse=args.rehearse,
         inject_fault=args.inject_fault or None, **sizes)
    versions()
    rebuild_native()
    kern = run_kernels_child(args)
    compiles = [kern["compile"]]
    for gi in range(len(GEOMETRIES)):
        compiles.append(run_geometry(args, gi, sizes, kern["device"])["compile"])
    emit(phase="compile-total",
         cache_dir=kern["compile"]["cache_dir"],
         programs=sum(c["programs"] for c in compiles),
         compile_s=sum(c["compile_s"] for c in compiles),
         cache_hits=sum(c["cache_hits"] for c in compiles),
         cache_misses=sum(c["cache_misses"] for c in compiles))
    check("jax" not in sys.modules, "the parent process imported jax")
    if args.inject_fault:
        raise SmokeFailure(
            f"--inject-fault {args.inject_fault} did not fail the run")
    signal.alarm(0)
    print(json.dumps({"ok": True, "device": kern["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)

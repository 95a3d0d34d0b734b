"""Chaos harness: randomized seeded fault schedules against the erasure
layer, the internode planes, and the TPU dispatcher (fault/registry.py),
asserting the hardening they prove out — zero data loss or corruption,
quorum errors only when quorum is truly lost, hedged reads decoding
around stragglers, the breaker tripping on chronic latency, the backend
degradation ladder round-tripping, and breaker/hedge/ladder state
converging after faults clear."""

import json
import os
import time

os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")
os.environ.setdefault("MINIO_PROMETHEUS_AUTH_TYPE", "public")

import random
import subprocess
import sys

import numpy as np
import pytest

from minio_tpu import fault
from minio_tpu.erasure.set import ErasureSet
from minio_tpu.fault.storage import FaultInjectedDisk
from minio_tpu.storage.health import HealthCheckedDisk
from minio_tpu.storage.xlstorage import XLStorage

from tests.test_grid import grid_app  # noqa: F401 — fixture reuse
from tests.test_s3_api import ServerThread, _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    # chaos rules are process-global; every test starts and ends sterile.
    # The native GET fast path preads via local_path and would bypass the
    # injection wrapper — force the Python read path.
    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    fault.clear()
    yield
    fault.clear()


def _rig(tmp_path, n=8, cooldown=0.3):
    disks = [
        HealthCheckedDisk(
            FaultInjectedDisk(XLStorage(str(tmp_path / f"d{i}"))),
            fail_threshold=2, cooldown=cooldown,
        )
        for i in range(n)
    ]
    es = ErasureSet(disks)  # 8 drives -> EC 4+4
    es.make_bucket("cbkt")
    return es, disks


def _counters():
    return fault.status()["counters"]


# ---------------------------------------------------------------------------
# storage-boundary schedules (single node)
# ---------------------------------------------------------------------------

READ_MODES = ("error", "bitrot", "latency")
WRITE_MODES = ("error", "enospc", "torn-write")


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_storage_chaos_schedule(tmp_path, seed):
    """One seeded schedule: random fault rules on <= parity drives, full
    traffic under fault, then convergence after the rules clear."""
    rng = random.Random(seed)
    data_rng = np.random.default_rng(seed)
    es, disks = _rig(tmp_path)

    objects = {}
    for i in range(5):
        size = rng.choice([8_000, 60_000, 200_000, 400_000])
        body = data_rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        es.put_object("cbkt", f"pre-{i}", body)
        objects[f"pre-{i}"] = body

    # schedule: k <= p drives faulted for reads; the first <= 3 of them
    # also fault writes (write quorum d+1=5 tolerates 3 of 8)
    k = rng.randint(1, 4)
    bad = rng.sample(range(8), k)
    for j, di in enumerate(bad):
        ep = disks[di].endpoint
        rmode = rng.choice(READ_MODES)
        fault.inject({
            "boundary": "storage", "mode": rmode, "target": ep,
            "op": "read_file", "seed": seed * 100 + di,
            "latency_ms": 30 if rmode == "latency" else 0,
        })
        if j < 3:
            wmode = rng.choice(WRITE_MODES)
            fault.inject({
                "boundary": "storage", "mode": wmode, "target": ep,
                "op": "create_file", "seed": seed * 100 + di + 50,
            })
            for wop in ("rename_data", "write_metadata"):
                fault.inject({
                    "boundary": "storage", "mode": "error", "target": ep,
                    "op": wop, "seed": seed * 100 + di + 60,
                })

    # under fault: every old object reads back exact, new writes land
    for name, body in objects.items():
        _, it = es.get_object("cbkt", name)
        assert b"".join(it) == body, f"seed {seed}: {name} corrupted under fault"
    for i in range(2):
        size = rng.choice([20_000, 300_000])
        body = data_rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        es.put_object("cbkt", f"during-{i}", body)
        objects[f"during-{i}"] = body
        _, it = es.get_object("cbkt", f"during-{i}")
        assert b"".join(it) == body

    st = fault.status()
    assert st["active"] and sum(r["hits"] for r in st["rules"]) > 0

    # convergence: clear, let breakers cool down, everything is intact
    # and every circuit closes again
    fault.clear()
    time.sleep(0.4)
    for name, body in objects.items():
        _, it = es.get_object("cbkt", name)
        assert b"".join(it) == body, f"seed {seed}: {name} lost after recovery"
    body = data_rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    es.put_object("cbkt", "post", body)
    _, it = es.get_object("cbkt", "post")
    assert b"".join(it) == body
    assert all(d.online for d in disks), "a breaker failed to converge"


def test_rule_not_consumed_by_inapplicable_op(tmp_path):
    """A count-limited bitrot rule must spend its one hit on an op that
    can actually be corrupted (read_file), not on whatever metadata op
    happens to run first — the determinism the seeded schedules need."""
    disk = FaultInjectedDisk(XLStorage(str(tmp_path / "b")))
    disk.make_vol("v")
    disk.create_file("v", "f", b"payload-bytes")
    fault.inject({
        "boundary": "storage", "mode": "bitrot", "target": disk.endpoint,
        "count": 1, "seed": 8,
    })
    disk.stat_vol("v")  # cannot be bitrotted: must not consume the rule
    assert fault.status()["rules"][0]["remaining"] == 1
    corrupted = disk.read_file("v", "f", 0, -1)
    assert corrupted != b"payload-bytes"
    assert fault.status()["rules"][0]["remaining"] == 0


def test_get_spills_around_circuit_opened_mid_read(tmp_path):
    """A drive whose breaker opens BETWEEN the metadata read and the
    shard reads raises DiskNotFound from the window path — that must be
    a spill-to-parity, never a failed GET while quorum drives remain."""
    import time as _t

    data_rng = np.random.default_rng(13)
    es, disks = _rig(tmp_path)
    body = data_rng.integers(0, 256, size=900_000, dtype=np.uint8).tobytes()
    es.put_object("cbkt", "midtrip", body)
    oi, h = es.open_object("cbkt", "midtrip")
    # the circuit opens after the handle resolved its sources
    for di in range(3):
        disks[di]._open_until = _t.monotonic() + 60
    assert b"".join(h.read()) == body
    for di in range(3):
        disks[di]._open_until = 0.0


def test_quorum_error_only_when_quorum_lost(tmp_path):
    """5 > p=4 read-faulted drives must fail closed; clearing the faults
    must bring the data back byte-exact (no loss, no corruption)."""
    data_rng = np.random.default_rng(9)
    es, disks = _rig(tmp_path)
    body = data_rng.integers(0, 256, size=500_000, dtype=np.uint8).tobytes()
    es.put_object("cbkt", "precious", body)

    for di in range(5):
        fault.inject({
            "boundary": "storage", "mode": "error",
            "target": disks[di].endpoint, "op": "read_file", "seed": di,
        })
    with pytest.raises(Exception):
        _, it = es.get_object("cbkt", "precious")
        b"".join(it)

    fault.clear()
    time.sleep(0.4)
    _, it = es.get_object("cbkt", "precious")
    assert b"".join(it) == body


# ---------------------------------------------------------------------------
# hedged reads
# ---------------------------------------------------------------------------


def test_hedged_read_decodes_around_straggler(tmp_path, monkeypatch):
    """With one drive injected at +500 ms, a GET completes within the
    hedge budget (parity decode races the straggler and wins) instead of
    inheriting the straggler's latency."""
    monkeypatch.setenv("MINIO_TPU_HEDGE_MIN_MS", "40")
    data_rng = np.random.default_rng(11)
    es, disks = _rig(tmp_path)
    body = data_rng.integers(0, 256, size=3_000_000, dtype=np.uint8).tobytes()
    es.put_object("cbkt", "straggly", body)

    # the straggler must hold a DATA shard (parity shards aren't read
    # eagerly): pick the drive the object's distribution maps to shard 0
    from minio_tpu.utils.hashing import hash_order

    dist = hash_order("cbkt/straggly", 8)
    straggler = disks[dist.index(1)]
    fault.inject({
        "boundary": "storage", "mode": "latency", "latency_ms": 500,
        "target": straggler.endpoint, "op": "read_file", "seed": 3,
    })
    # the straggler's shard reads, as they start and as they end
    started, ended = [], []
    inner_read = straggler.read_file

    def noting_read(*a, **kw):
        started.append(a)
        try:
            return inner_read(*a, **kw)
        finally:
            ended.append(a)

    monkeypatch.setattr(straggler, "read_file", noting_read)
    before = _counters()
    _, it = es.get_object("cbkt", "straggly")
    got = b"".join(it)
    # the straggler's 500 ms never reached the hedged caller: its read was
    # asked for, and was still asleep when the GET had all its bytes
    assert started and not ended, (started, ended)
    assert got == body
    after = _counters()
    assert after["hedge_reads"] > before["hedge_reads"], \
        "500ms straggler never triggered a hedged read"
    assert after["hedge_wins"] > before["hedge_wins"], \
        "hedge fired and beat a 500ms straggler: must win"

    # hedge off: the same GET inherits the straggler — this is the injected
    # latency as actually delivered
    monkeypatch.setenv("MINIO_TPU_HEDGE", "0")
    t0 = time.monotonic()
    _, it = es.get_object("cbkt", "straggly")
    assert b"".join(it) == body
    inherited_s = time.monotonic() - t0
    assert inherited_s >= 0.45  # a sleep's lower bound holds under any load
    assert len(ended) >= 2  # and this GET did wait its read out


def test_latency_breaker_trips_chronically_slow_drive(tmp_path):
    """A slow-but-alive drive goes offline like an erroring one, and
    recovers through the half-open probe once it speeds up."""
    disk = HealthCheckedDisk(
        FaultInjectedDisk(XLStorage(str(tmp_path / "slow"))),
        fail_threshold=4, cooldown=0.25, latency_trip_s=0.02,
    )
    disk.make_vol("v")
    fault.inject({
        "boundary": "storage", "mode": "latency", "latency_ms": 40,
        "target": disk.endpoint, "op": "stat_vol", "seed": 1,
    })
    tripped = False
    for _ in range(12):
        if not disk.online:
            tripped = True
            break
        disk.stat_vol("v")
    assert tripped or not disk.online, "EWMA latency never tripped the breaker"
    assert disk.latency_trips >= 1
    assert disk.health()["latencyTrips"] >= 1
    # a call that was already in flight when the circuit opened must NOT
    # re-close it on completion (only the half-open probe may)
    disk._ok(0.001)
    assert not disk.online, "in-flight success re-closed a tripped circuit"

    fault.clear()
    time.sleep(0.3)
    disk.stat_vol("v")  # half-open probe, now fast -> circuit closes
    assert disk.online


def test_slow_walk_does_not_trip_latency_breaker():
    """walk_dir's wall time is namespace size, not drive health: a big
    metacache build (tens of seconds per walk at 10^5+ keys) must not
    poison the latency EWMA and take a healthy drive offline. Found by
    the small-object-storm profile at 100k keys: every listing walk
    tripped the breaker, then ~half of all requests failed DiskNotFound
    until cooldown."""

    class _SlowWalkDisk:
        endpoint = "slowwalk"
        disk_id = ""

        def walk_dir(self, volume, base=""):
            time.sleep(0.05)  # >> latency_trip_s below
            yield from (f"k{i:04d}/xl.meta" for i in range(16))

        def stat_vol(self, volume):
            return {"name": volume}

    disk = HealthCheckedDisk(
        _SlowWalkDisk(), fail_threshold=4, cooldown=5.0,
        latency_trip_s=0.02,
    )
    for _ in range(12):  # past _EWMA_MIN_SAMPLES with room to spare
        assert len(list(disk.walk_dir("v"))) == 16
        assert disk.online, "slow walk tripped the latency breaker"
    assert disk.latency_trips == 0
    assert disk.ewma_latency() == 0.0, "walks leaked into the EWMA"
    # walks still show up in per-op accounting (/system/drive/latency)
    calls, secs = disk.op_stats_snapshot()["walk_dir"]
    assert calls == 12 and secs > 0.5
    # and small-op latency still drives the breaker exactly as before
    disk.stat_vol("v")
    assert disk.online


# ---------------------------------------------------------------------------
# TPU boundary: backend degradation ladder
# ---------------------------------------------------------------------------


def test_backend_degradation_round_trip(monkeypatch):
    """Inject TPU device faults -> the dispatcher serves every batch
    degraded (byte-identical to the device path), demotes to the numpy
    rung past the threshold, and re-promotes via a probe batch after the
    faults clear."""
    jax = pytest.importorskip("jax")  # noqa: F841 — device rung needs jax
    from minio_tpu.ops import rs_jax
    from minio_tpu.parallel.dispatcher import LEVEL_NUMPY, TpuDispatcher

    monkeypatch.setenv("MINIO_TPU_BACKEND_DEMOTE_FAULTS", "2")
    monkeypatch.setenv("MINIO_TPU_BACKEND_PROBE_AFTER", "2")
    codec = rs_jax.get_tpu_codec(4, 2)
    disp = TpuDispatcher(codec, 1024, window_s=0.0)
    blocks = np.random.default_rng(7).integers(
        0, 256, size=(2, 4, 1024), dtype=np.uint8
    )
    base_parity, base_digests = disp.encode(blocks)
    assert base_parity.shape == (2, 2, 1024)
    assert disp.stats["backend_level"] > LEVEL_NUMPY

    fault.inject({"boundary": "tpu", "mode": "device-lost", "seed": 5})
    for i in range(3):
        parity, digests = disp.encode(blocks)
        # degraded results stay byte-identical to the device path
        np.testing.assert_array_equal(parity, base_parity)
        np.testing.assert_array_equal(digests, base_digests)
    assert disp.stats["backend_level"] == LEVEL_NUMPY
    assert disp.stats["demotions"] == 1
    assert disp.stats["device_faults"] >= 2
    assert disp.stats["numpy_blocks"] >= 2

    # faults clear -> probe batches re-promote within probe_after
    fault.clear()
    promoted = False
    for _ in range(6):
        parity, digests = disp.encode(blocks)
        np.testing.assert_array_equal(parity, base_parity)
        np.testing.assert_array_equal(digests, base_digests)
        if disp.stats["backend_level"] > LEVEL_NUMPY:
            promoted = True
            break
    assert promoted, "probe batches never re-promoted the device backend"
    assert disp.stats["promotions"] >= 1
    assert disp.stats["probes"] >= 1


def test_tpu_slow_batch_injection(monkeypatch):
    """slow-batch stalls a dispatch without failing it or demoting."""
    pytest.importorskip("jax")
    from minio_tpu.ops import rs_jax
    from minio_tpu.parallel.dispatcher import TpuDispatcher

    codec = rs_jax.get_tpu_codec(4, 2)
    disp = TpuDispatcher(codec, 512, window_s=0.0)
    blocks = np.zeros((1, 4, 512), dtype=np.uint8)
    disp.encode(blocks)  # warm/compile
    fault.inject({
        "boundary": "tpu", "mode": "slow-batch", "latency_ms": 120,
        "count": 1, "seed": 2,
    })
    t0 = time.monotonic()
    disp.encode(blocks)
    assert time.monotonic() - t0 >= 0.1
    assert disp.stats["demotions"] == 0


# ---------------------------------------------------------------------------
# network boundary: grid retry policy + injected faults
# ---------------------------------------------------------------------------


def test_grid_call_retries_timeout_for_idempotent(grid_app):  # noqa: F811
    """Satellite fix: retry=True now re-sends after a TIMEOUT too (the
    old code retried only transport errors), through the shared backoff
    policy."""
    from minio_tpu.cluster.grid import GridClient, GridError

    gs, host, port, token, _ = grid_app
    calls = {"n": 0}

    def flaky(p: bytes) -> bytes:
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.8)  # first response arrives after the deadline
            return b"late"
        return b"fast"

    gs.register_single("flaky", flaky)
    c = GridClient(host, port, token)
    try:
        assert c.call("flaky", b"", timeout=0.3, retry=True) == b"fast"
        assert calls["n"] >= 2, "timeout was never retried"

        # non-idempotent (retry=False) still fails closed on timeout
        def stuck(p: bytes) -> bytes:
            time.sleep(0.6)
            return b"x"

        gs.register_single("stuck", stuck)
        with pytest.raises(GridError):
            c.call("stuck", b"", timeout=0.2, retry=False)
    finally:
        c.close()


def test_grid_injected_drop_retried(grid_app):  # noqa: F811
    from minio_tpu.cluster.grid import GridClient, GridError

    gs, host, port, token, _ = grid_app
    gs.register_single("echo", lambda p: b"ok:" + p)
    c = GridClient(host, port, token)
    try:
        fault.inject({
            "boundary": "network", "mode": "drop",
            "target": f"{host}:{port}", "op": "echo", "count": 1, "seed": 4,
        })
        # idempotent: the dropped first attempt is retried transparently
        assert c.call("echo", b"x", retry=True) == b"ok:x"
        fault.inject({
            "boundary": "network", "mode": "drop",
            "target": f"{host}:{port}", "op": "echo", "count": 1, "seed": 4,
        })
        with pytest.raises(GridError):
            c.call("echo", b"y", retry=False)
    finally:
        c.close()


# ---------------------------------------------------------------------------
# admin + metrics plane (single node server)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chaos_server(tmp_path_factory):
    base = tmp_path_factory.mktemp("chaosdrives")
    st = ServerThread([str(base / f"d{i}") for i in range(4)])
    yield st
    st.stop()


def test_admin_fault_endpoints_and_metrics(chaos_server):
    from minio_tpu.client import S3Client

    cli = S3Client(f"127.0.0.1:{chaos_server.port}")
    cli.make_bucket("fbk")
    body = os.urandom(200_000)
    assert cli.put_object("fbk", "obj", body).status == 200

    # inject: 60ms latency on every drive's read_file
    r = cli.request(
        "POST", "/minio/admin/v3/fault/inject",
        body=json.dumps({
            "boundary": "storage", "mode": "latency", "latency_ms": 60,
            "op": "read_file", "seed": 21,
        }).encode(),
    )
    assert r.status == 200, r.body
    rid = json.loads(r.body)["id"]

    t0 = time.monotonic()
    g = cli.get_object("fbk", "obj")
    assert g.status == 200 and g.body == body
    assert time.monotonic() - t0 >= 0.05  # the injected stall was real

    st = json.loads(cli.request("GET", "/minio/admin/v3/fault/status").body)
    assert st["active"]
    assert any(r0["id"] == rid and r0["hits"] > 0 for r0 in st["rules"])
    assert "backendLevel" in st

    # malformed spec -> 400, not a 500
    r = cli.request(
        "POST", "/minio/admin/v3/fault/inject",
        body=json.dumps({"boundary": "storage", "mode": "nope"}).encode(),
    )
    assert r.status == 400

    # metrics-v3 /api/fault: injection + hedge + ladder series exposed
    text = cli.request("GET", "/minio/metrics/v3/api/fault").body.decode()
    assert "minio_fault_rules_active" in text
    assert 'minio_fault_injected_total{boundary="storage"}' in text
    assert "minio_fault_hedge_wins_total" in text
    assert "minio_tpu_backend_level" in text
    assert "minio_tpu_backend_demotions_total" in text
    import re

    hits = int(re.search(
        r'minio_fault_injected_total\{boundary="storage"\} (\d+)', text
    ).group(1))
    assert hits > 0

    r = cli.request("POST", "/minio/admin/v3/fault/clear")
    assert r.status == 200
    st = json.loads(cli.request("GET", "/minio/admin/v3/fault/status").body)
    assert not st["active"] and not st["rules"]


# ---------------------------------------------------------------------------
# 2-node cluster schedules (network boundary through the admin plane)
# ---------------------------------------------------------------------------


def _spawn(port: int, specs: list[str]) -> subprocess.Popen:
    env = dict(os.environ)
    env.update({
        "MINIO_TPU_BACKEND": "numpy",
        "PYTHONPATH": REPO,
        "MINIO_TPU_NATIVE_PLANE": "0",
        "MINIO_PROMETHEUS_AUTH_TYPE": "public",
        # fast breaker recovery so post-chaos convergence fits a test
        "MINIO_TPU_DRIVE_COOLDOWN_S": "1",
        # deterministic data-cache warm-up for the cross-invalidation test
        "MINIO_TPU_CACHE_ADMIT_TOUCHES": "1",
    })
    env.pop("JAX_PLATFORMS", None)
    return subprocess.Popen(
        [sys.executable, "-m", "minio_tpu.server", "--address",
         f"127.0.0.1:{port}", *specs],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )


@pytest.fixture(scope="module")
def cluster2(tmp_path_factory):
    from minio_tpu.client import S3Client

    base = tmp_path_factory.mktemp("chaos2")
    p1, p2 = _free_port(), _free_port()
    specs = [
        f"http://127.0.0.1:{p1}{base}/n1/d1",
        f"http://127.0.0.1:{p1}{base}/n1/d2",
        f"http://127.0.0.1:{p2}{base}/n2/d1",
        f"http://127.0.0.1:{p2}{base}/n2/d2",
    ]
    procs = [_spawn(p1, specs), _spawn(p2, specs)]
    cli1, cli2 = S3Client(f"127.0.0.1:{p1}"), S3Client(f"127.0.0.1:{p2}")

    def wait_ready(cli, timeout=40.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                if cli.request("GET", "/").status == 200:
                    return
            except Exception:
                pass
            time.sleep(0.3)
        raise TimeoutError("cluster node not ready")

    try:
        wait_ready(cli1)
        wait_ready(cli2)
        cli1.make_bucket("ckt")
    except Exception:
        for p in procs:
            p.kill()
            print(p.stdout.read().decode()[-3000:])
        raise
    yield {"cli1": cli1, "cli2": cli2, "ports": (p1, p2)}
    for p in procs:
        if p.poll() is None:
            p.kill()


def test_cluster_chaos_delay_schedule(cluster2):
    """Seeded internode delay/drop mix, injected CLUSTER-WIDE through the
    admin fan-out: traffic stays correct, both nodes report hits."""
    cli1, cli2 = cluster2["cli1"], cluster2["cli2"]
    r = cli1.request(
        "POST", "/minio/admin/v3/fault/inject",
        body=json.dumps({
            "boundary": "network", "mode": "delay", "latency_ms": 30,
            "prob": 0.5, "seed": 31,
        }).encode(),
    )
    assert r.status == 200, r.body
    assert "peers" in json.loads(r.body)  # the fan-out ran

    bodies = {}
    for i in range(3):
        body = os.urandom(120_000)
        assert cli1.put_object("ckt", f"jit-{i}", body).status == 200
        bodies[f"jit-{i}"] = body
    for name, body in bodies.items():
        g = cli2.get_object("ckt", name)
        assert g.status == 200 and g.body == body

    # both nodes saw injected network hits (rule replayed by fan-out)
    for cli in (cli1, cli2):
        st = json.loads(cli.request("GET", "/minio/admin/v3/fault/status").body)
        assert st["counters"]["network"] > 0, st
    assert cli1.request("POST", "/minio/admin/v3/fault/clear").status == 200
    st = json.loads(cli2.request("GET", "/minio/admin/v3/fault/status").body)
    assert not st["active"]  # clear fanned out too


def test_cluster_chaos_partition_schedule(cluster2):
    """Node 1 partitioned from node 2's drives: reads survive on local
    shards (EC 2+2), writes fail closed exactly while quorum is lost,
    and the cluster converges once the partition clears."""
    cli1, cli2 = cluster2["cli1"], cluster2["cli2"]
    body = os.urandom(150_000)
    assert cli1.put_object("ckt", "survivor", body).status == 200

    p2 = cluster2["ports"][1]
    r = cli1.request(
        "POST", "/minio/admin/v3/fault/inject",
        query={"local": "true"},  # node 1's view only: asymmetric partition
        body=json.dumps({
            "boundary": "network", "mode": "partition",
            "target": f"127.0.0.1:{p2}", "seed": 32,
        }).encode(),
    )
    assert r.status == 200, r.body

    # reads decode from the 2 local shards
    g = cli1.get_object("ckt", "survivor")
    assert g.status == 200 and g.body == body
    # writes need 3 of 4 drives: quorum is TRULY lost -> fail closed
    r = cli1.put_object("ckt", "needs-quorum", b"x" * 1000)
    assert r.status in (500, 503), r.status
    # node 2 is unaffected (the rule was local to node 1)
    assert cli2.put_object("ckt", "via-n2", b"fine").status == 200

    assert cli1.request("POST", "/minio/admin/v3/fault/clear").status == 200
    time.sleep(1.2)  # breaker cooldown (MINIO_TPU_DRIVE_COOLDOWN_S=1)
    assert cli1.put_object("ckt", "healed-write", b"back").status == 200
    g = cli2.get_object("ckt", "healed-write")
    assert g.status == 200 and g.body == b"back"
    g = cli1.get_object("ckt", "survivor")
    assert g.status == 200 and g.body == body


# ---------------------------------------------------------------------------
# cache-coherence schedules (cache/ tentpole: no stale serves, ever)
# ---------------------------------------------------------------------------


def test_cache_coherence_schedule(tmp_path, monkeypatch):
    """Injected bitrot + heal + overwrite under concurrent cached GETs:
    every response's body must hash to its own etag (no torn/mixed
    serves), every served version must be one that was legitimately live
    during the read, and once a mutation RETURNS every subsequent read
    observes it — cached or not."""
    import hashlib
    import threading

    monkeypatch.setenv("MINIO_TPU_CACHE", "1")
    monkeypatch.setenv("MINIO_TPU_CACHE_ADMIT_TOUCHES", "1")
    es, disks = _rig(tmp_path)
    v1 = os.urandom(120_000)
    es.put_object("cbkt", "coh", v1)
    for _ in range(2):  # warm FileInfo + data tiers
        _, it = es.get_object("cbkt", "coh")
        b"".join(it)
    from minio_tpu.cache import core as cache_core

    assert cache_core.data_cache().get(es, "cbkt", "coh", "") is not None

    expected = {hashlib.md5(v1).hexdigest(): v1}
    problems: list[str] = []
    stop = threading.Event()
    mu = threading.Lock()

    def reader():
        while not stop.is_set():
            try:
                oi, it = es.get_object("cbkt", "coh")
                body = b"".join(bytes(c) for c in it)
            except Exception as e:  # noqa: BLE001
                with mu:
                    problems.append(f"read failed: {e!r}")
                return
            h = hashlib.md5(body).hexdigest()
            with mu:
                if h != oi.etag:
                    problems.append(f"etag/bytes mismatch: {oi.etag} vs {h}")
                    return
                if expected.get(h) != body:
                    problems.append(f"unknown version served: {h}")
                    return

    threads = [threading.Thread(target=reader) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        # 1) bitrot one drive's shard reads: cached serves are immune,
        #    uncached reads must decode around the corruption
        fault.inject({
            "boundary": "storage", "mode": "bitrot",
            "target": disks[0].endpoint, "op": "read_file", "seed": 9,
        })
        time.sleep(0.15)
        # 2) lose another drive's copy outright, then heal: the rebuild
        #    must invalidate through the choke point
        import shutil

        shutil.rmtree(tmp_path / "d1" / "cbkt" / "coh")
        res = es.heal_object("cbkt", "coh")
        assert res["healed"], res
        time.sleep(0.1)
        # 3) overwrite: v2 becomes live; in-flight readers may still
        #    finish serving v1 (they began before the write completed)
        v2 = os.urandom(90_000)
        with mu:
            expected[hashlib.md5(v2).hexdigest()] = v2
        es.put_object("cbkt", "coh", v2)
        time.sleep(0.15)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not problems, problems

    # determinism: the overwrite returned above, so only v2 may be
    # served now — first a fresh read, then the re-warmed cached path
    for _ in range(3):
        oi, it = es.get_object("cbkt", "coh")
        body = b"".join(bytes(c) for c in it)
        assert body == v2, "stale bytes served after overwrite returned"
        assert oi.etag == hashlib.md5(v2).hexdigest(), "stale etag served"
    inv = es.cache.snapshot()["fileinfo"]["invalidations"]
    assert inv >= 2  # heal + overwrite both flowed through the choke point


def test_drive_failure_storm_family_ingress(tmp_path, monkeypatch):
    """ISSUE-14 chaos schedule: TWO drives lost mid-traffic at EC 8+8,
    for each code family. Phase 1 loses both drives at once — degraded
    GETs under double failure must stay byte-identical (etag-checked)
    and the 2-stale heal recovers both. Phase 2 loses one drive alone —
    the cauchy family's heal must read measurably fewer survivor bytes
    than reedsolomon (>= 25%, the partial-repair schedule) with zero
    wrong bytes. Readers hammer the object the whole time."""
    import hashlib
    import shutil
    import threading

    from minio_tpu.erasure.coder import family_stats_snapshot

    monkeypatch.setenv("MINIO_TPU_NATIVE_PLANE", "0")
    body = os.urandom(3 << 20)
    etag = hashlib.md5(body).hexdigest()
    heal_ingress = {}
    for fam in ("reedsolomon", "cauchy"):
        monkeypatch.setenv("MINIO_TPU_EC_FAMILY", fam)
        root = tmp_path / fam
        disks = [
            HealthCheckedDisk(FaultInjectedDisk(XLStorage(str(root / f"d{i}"))))
            for i in range(16)
        ]
        es = ErasureSet(disks, default_parity=8)  # EC 8+8
        es.make_bucket("storm")
        es.put_object("storm", "obj", body)
        fi, _ = es._cached_fileinfo("storm", "obj", "")
        assert fi.erasure.algorithm == fam
        dist = fi.erasure.distribution

        problems: list[str] = []
        stop = threading.Event()
        mu = threading.Lock()
        verified = [0]  # GETs that came back byte-exact

        def reader():
            while not stop.is_set():
                try:
                    oi, it = es.get_object("storm", "obj")
                    got = b"".join(bytes(c) for c in it)
                except Exception as e:  # noqa: BLE001 — storm witness
                    with mu:
                        problems.append(f"read failed: {e!r}")
                    return
                if hashlib.md5(got).hexdigest() != oi.etag or oi.etag != etag:
                    with mu:
                        problems.append("wrong bytes served")
                    return
                with mu:
                    verified[0] += 1

        def traffic_flowed(n=4):
            """Block until n more GETs verified (each reader ~once): the
            'mid-traffic' in this schedule is an observed count, not a
            sleep that a loaded host turns into zero reads."""
            target = verified[0] + n
            deadline = time.monotonic() + 60
            while verified[0] < target and not problems:
                assert time.monotonic() < deadline, "readers stalled"
                time.sleep(0.01)

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            # phase 1: two drives lose the object at once (data shard 0
            # + a parity shard) — traffic keeps flowing over 14 shards
            lost_a = dist.index(1)       # data shard 0
            lost_b = dist.index(16)      # parity shard 15
            shutil.rmtree(root / f"d{lost_a}" / "storm" / "obj")
            shutil.rmtree(root / f"d{lost_b}" / "storm" / "obj")
            es.cache.clear()
            traffic_flowed()  # degraded GETs under double failure
            res = es.heal_object("storm", "obj")
            assert sorted(res["healed"]) == sorted(
                [disks[lost_a].endpoint, disks[lost_b].endpoint]
            ), res
            assert not res["partialRepair"]  # 2 stale -> generic rebuild
            traffic_flowed()  # healthy again before the next loss
            # phase 2: a single data drive dies — the repair-bandwidth
            # case the second family exists for
            before = family_stats_snapshot()[fam]["heal_ingress_bytes"]
            lost_c = dist.index(2)       # data shard 1
            shutil.rmtree(root / f"d{lost_c}" / "storm" / "obj")
            es.cache.clear()
            traffic_flowed()  # degraded GETs under the single loss
            res = es.heal_object("storm", "obj")
            assert res["healed"] == [disks[lost_c].endpoint], res
            assert res["partialRepair"] == (fam == "cauchy")
            heal_ingress[fam] = (
                family_stats_snapshot()[fam]["heal_ingress_bytes"] - before
            )
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not problems, (fam, problems)
        # post-storm: byte identity + every healed shard re-verifies
        es.cache.clear()
        oi, it = es.get_object("storm", "obj")
        got = b"".join(bytes(c) for c in it)
        assert got == body and oi.etag == etag
        fi2, metas, _, _ = es._quorum_fileinfo("storm", "obj", "", read_data=True)
        for dk, m in zip(es.disks, metas):
            assert m is not None
            dk.verify_file("storm", "obj", m)
    assert heal_ingress["cauchy"] <= 0.75 * heal_ingress["reedsolomon"], (
        heal_ingress
    )


def test_cluster_cache_cross_invalidation(cluster2):
    """2-node coherence: node 2 serves an object from its cache; node 1
    overwrites it. The write returns only after the grid invalidation
    broadcast, so node 2 must serve the new bytes IMMEDIATELY after the
    PUT response — even with injected delay on the invalidation RPC."""
    import hashlib

    cli1, cli2 = cluster2["cli1"], cluster2["cli2"]
    body1 = os.urandom(100_000)
    assert cli1.put_object("ckt", "xinv", body1).status == 200
    for _ in range(3):  # warm node 2's tiers (admit touches = 1 in _spawn)
        g = cli2.get_object("ckt", "xinv")
        assert g.status == 200 and g.body == body1
    st = json.loads(cli2.request("GET", "/minio/admin/v3/cache/status").body)
    assert st["fileinfo"]["hits"] >= 1, st

    # slow the invalidation RPC: a PUT must wait it out, not serve stale
    r = cli1.request(
        "POST", "/minio/admin/v3/fault/inject", query={"local": "true"},
        body=json.dumps({
            "boundary": "network", "mode": "delay", "latency_ms": 50,
            "op": "cache.invalidate", "seed": 41,
        }).encode(),
    )
    assert r.status == 200, r.body
    body2 = os.urandom(80_000)
    assert cli1.put_object("ckt", "xinv", body2).status == 200
    g = cli2.get_object("ckt", "xinv")
    assert g.status == 200
    assert g.body == body2, "node 2 served stale bytes after cross-node PUT"
    assert g.headers["etag"].strip('"') == hashlib.md5(body2).hexdigest()
    assert cli1.request("POST", "/minio/admin/v3/fault/clear").status == 200
    st = json.loads(cli2.request("GET", "/minio/admin/v3/cache/status").body)
    assert st["coherence"]["received"] >= 1, st

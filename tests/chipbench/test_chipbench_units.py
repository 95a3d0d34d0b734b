"""chipbench's yardstick, piece by piece, on the CPU: no server, no chip,
and no jax while this module is imported. A chip result comes only from
`python3 -m chipbench` on the chip; nothing here is one.

The tests of `BENCHMARK.json` and its data files find the benchmark from
where this file lies (`REPO`), so a copy of the tree polices itself
(`test_chipbench_added_cell.py` adds a cell to one and runs them there).
They hold EVERY configuration, cell and metric to the contract's letter, and
the ones that exist today to their own numbers by name: what a later PR adds
as new files and appended entries must pass the first and is not held to the
second."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from chipbench import metrics, plugins, reference, traffic, work  # noqa: E402
from chipbench.procs import parse_metrics, total  # noqa: E402
from chipbench.trace_reduce import reduce_planes, union_ns  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


# ---- BENCHMARK.json against the contract's letter --------------------------


def test_top_level_keys_and_limits():
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    if m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        # a per-layer metric is a reader of its own, found by its name
        assert callable(metrics.reader(m["name"]).read)
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"


def test_metric_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in ALL_METRICS]
    assert len(set(names)) == len(names)
    assert {"s3_mib_s", "s3_p95_ms", "setup_s"} <= {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
    assert all(NAME.match(k) for k in c["reduced"])
    assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    assert c["file"].startswith("chipbench/configs/")
    with open(os.path.join(REPO, c["file"])) as f:
        body = json.load(f)
    assert body["name"] == c["name"] and body["source"] == c["source"]
    assert sorted(body["reduced"]) == sorted(c["reduced"])
    assert "assumed" in body and "guarantees" in body
    dep = body["deployment"]
    # the shapes are the source's own and are never cut
    assert dep["stripe_block_bytes"] == 1 << 20
    assert dep["data_shards"] + dep["parity_shards"] == dep["drives"]
    assert dep["shard_bytes"] == work.shard_len(dep["data_shards"])
    d, p = dep["data_shards"], dep["parity_shards"]
    assert body["guarantees"]["write_quorum"] == (d + 1 if d == p else d)
    assert body["guarantees"]["readable_with_drives_missing"] == p
    # what the harness and the checks read of every configuration
    assert isinstance(body["server_env"], dict)
    assert body["expects"]["backend_level"] in (0, 1, 2) and body["expects"]["device_rung"]


@pytest.mark.parametrize("name,d,p,shard", [("ec8p8-16d", 8, 8, 131072),
                                            ("ec12p4-16d", 12, 4, 87382)])
def test_the_two_16_drive_sets_keep_their_shapes(name, d, p, shard):
    """The configurations that exist, by name: one set of 16 drives each."""
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"])) as f:
        dep = json.load(f)["deployment"]
    assert dep["drives"] == 16 and dep["erasure_sets"] == 1
    assert (dep["data_shards"], dep["parity_shards"], dep["shard_bytes"]) == (d, p, shard)
    assert sorted(entry["reduced"]) == ["clients", "drives_are_directories"]


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry_and_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert os.path.isfile(os.path.join(REPO, "chipbench", "workloads", w["name"] + ".json"))
    for mix in (traffic.load_mix(w["traffic"], rehearse=False),
                traffic.load_mix(w["traffic"], rehearse=True)):
        # what the harness reads of every traffic file, at both sizes
        assert "rehearse" not in mix and mix["trace_s"] > 0 and mix["drives_room_gib"] > 0
        assert 0 < mix["warm"]["quiet_s"] <= mix["warm"]["max_s"] >= mix["warm"]["min_s"] > 0
        assert NAME.match(mix["warm"].get("progress", "minio_tpu_dispatch_total"))
        # the generator and every step of the comparison are files found by name
        assert callable(plugins.load("generators", mix["generator"]).Generator)
        assert mix["checks"] and all(callable(plugins.load("checks", c).run)
                                     for c in mix["checks"])


def test_speedtest_put_is_8_clients_of_64_mib():
    """The mix that exists, by name: what `mc admin speedtest`'s PUT phase
    sends, cut to 8 clients, and every step of `correct` the PUT cells have."""
    mix = traffic.load_mix("speedtest-put", rehearse=False)
    assert mix["clients"] == 8 and mix["object_mib"] == 64
    assert mix["generator"] == "closed_loop_put" and mix["unsigned_payload"] is True
    assert "progress" not in mix["warm"]  # the default, `minio_tpu_dispatch_total`
    assert mix["checks"] == ["answers", "readback", "ondrive_frames", "degraded_read",
                             "device_served", "device_rung", "blocks_dispatched"]
    assert mix["verify"] == {"readback_keys_per_client": 4, "ondrive_objects": 2,
                             "degraded_objects": 1, "timeout_s": 60}
    small = traffic.load_mix("speedtest-put", rehearse=True)
    assert small["checks"] == mix["checks"] and small["generator"] == mix["generator"]
    assert small["verify"]["ondrive_objects"] == 2 and small["verify"]["degraded_objects"] == 1
    both = {w["name"] for w in BENCH["workloads"] if w["traffic"] == "speedtest-put"}
    assert both >= {"ec12p4-16d.speedtest-put", "ec8p8-16d.speedtest-put"}


def test_every_cell_reports_what_its_per_layer_metrics_move():
    from chipbench.run import metric_names

    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in metric_names(BENCH, "end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        mine = metric_names(BENCH, "per_layer", w["name"])
        # a metric that lists this cell, or lists none, must move something
        # the cell reports
        assert mine and all(m["moves"] in e2e for m in mine), w["name"]


def test_pairs_are_unique_and_every_config_is_used():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["name"] for c in BENCH["configs"]} == {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in BENCH["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for fn in files:
                if not fn.endswith(".pyc"):
                    assert ok.match(os.path.relpath(os.path.join(dirpath, fn), REPO))


# ---- the work and the peaks ------------------------------------------------


@pytest.mark.parametrize("d,p,want", [(8, 8, 2_097_664), (12, 4, 1_398_624)])
def test_bytes_per_block(d, p, want):
    assert work.encode_bytes_per_block(d, p) == want


def test_shard_lengths_and_ops():
    assert work.shard_len(8) == 131072 and work.shard_len(12) == 87382
    assert work.encode_ops_per_block(8, 8) == 2 * 64 * 64 * 131072
    # the operations side against the int8 peak lies within a tenth of the
    # bytes side at 8+8 (PERF.md works it)
    pk = work.peaks("TPU v5 lite")
    t_bytes = work.encode_bytes_per_block(8, 8) / pk["hbm_bytes_per_s"]
    t_ops = work.encode_ops_per_block(8, 8) / pk["int8_ops_per_s"]
    assert abs(t_ops / t_bytes - 1) < 0.1


def test_unknown_device_is_an_error_not_a_default():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


# ---- the plain reference against the program's own codec -------------------


@pytest.mark.parametrize("d,p", [(8, 8), (12, 4), (14, 2), (4, 4)])
def test_reference_parity_equals_the_programs_numpy_codec(d, p):
    from minio_tpu.ops.rs import get_codec

    body = np.random.default_rng([d, p]).bytes(2 << 20)
    data = reference.split(body, d)
    parity = reference.encode(data, p)
    ref = get_codec(d, p)
    for b in range(2):
        want = ref.encode(ref.split(body[b << 20: (b + 1) << 20]))
        assert np.array_equal(want[:d], data[b]) and np.array_equal(want[d:], parity[b])


@pytest.mark.parametrize("n", [1, 3, 16, 31, 32, 33, 63, 64, 1014, 87382 % 4096 + 4096])
def test_reference_highwayhash_equals_the_scalar_implementation(n):
    from minio_tpu.ops.highwayhash import hash256

    msgs = np.random.default_rng(n).integers(0, 256, size=(3, n), dtype=np.uint8)
    got = reference.hash256(msgs)
    for i in range(3):
        assert got[i].tobytes() == hash256(msgs[i].tobytes())


def test_object_frames_layout():
    body = np.random.default_rng(5).bytes(1 << 20)
    frames = reference.object_frames(body, 12, 4)
    assert len(frames) == 16 and all(len(f) == 32 + 87382 for f in frames)
    joined = b"".join(f[32:] for f in frames[:12])
    assert joined[: 1 << 20] == body and set(joined[1 << 20:]) <= {0}
    with pytest.raises(ValueError):
        reference.split(body[:-1], 12)


# ---- trace reduction -------------------------------------------------------


def test_union_merges_overlaps():
    total_ns, merged = union_ns([(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    assert total_ns == 20 + 15 + 1 and merged == [(0, 20), (30, 45), (100, 101)]


def test_reduction_of_the_recorded_chip_trace():
    """A cut of the trace recorded on the chip (fixtures/): known busy and
    idle numbers, to the nanosecond."""
    with open(os.path.join(HERE, "fixtures", "trace_ec12p4.json")) as f:
        red = reduce_planes(json.load(f)["planes"])
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.019273204, abs=1e-12)
    assert red["window_s"] == pytest.approx(1.297042903, abs=1e-12)
    assert red["device_ops"][0][0].startswith("module:jit_hash256_blocks_pallas")
    assert red["idle_gaps"][0] == ["PjitFunction(reshape)", pytest.approx(0.49181899)]
    assert red["idle_gaps"][1][0] == "np.asarray(jax.Array)"
    # dispatches: the runs of the program with most device time (the hash
    # chain, once per dispatch; `jit_reshape` runs twice, under two fingerprints)
    assert red["dispatches"] == 4


def test_dispatches_count_one_program_name_over_its_batch_sizes():
    ev = [("jit_enc(111)", 0, 5), ("jit_x(9)", 10, 1), ("jit_x(7)", 12, 1),
          ("jit_enc(222)", 100, 9), ("jit_x(9)", 110, 1), ("jit_x(7)", 112, 1),
          ("jit_enc(111)", 200, 5)]
    red = reduce_planes([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": ev}, {"name": "XLA Ops", "events": ev}]}])
    assert red["dispatches"] == 3 and red["busy_s"] == pytest.approx(23e-9)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_reduction_without_a_device_plane_reads_nothing():
    red = reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [("x", 0, 1000)]}]}])
    assert red["devices"] == 0 and red["busy_s"] is None and red["device_ops"] == []


# ---- the per-layer readers on known counters -------------------------------

EXPO = """\
minio_tpu_dispatch_total {n}
minio_tpu_dispatch_blocks_total{{class="foreground"}} {blocks}
minio_tpu_dispatch_blocks_total{{class="background"}} 0
minio_tpu_device_seconds_total {dev}
minio_tpu_queue_wait_seconds_total {qw}
minio_tpu_queue_wait_seconds_distribution{{le="0.5"}} {items}
minio_tpu_queue_wait_seconds_distribution{{le="+Inf"}} {items}
minio_tpu_compile_programs_total {progs}
minio_tpu_dispatch_bucket_blocks_distribution{{le="64"}} {b64}
minio_tpu_dispatch_bucket_blocks_distribution{{le="128"}} {b128}
minio_tpu_dispatch_bucket_blocks_distribution{{le="+Inf"}} {b128}
"""


def _window(**kw):
    before = parse_metrics(EXPO.format(n=10, blocks=640, dev=1.0, qw=0.5, items=10,
                                       progs=7, b64=10, b128=10))
    after = parse_metrics(EXPO.format(n=30, blocks=2560, dev=6.0, qw=2.5, items=40,
                                      progs=8, b64=25, b128=30))
    base = dict(seconds=10.0, acked_bytes=2 << 30, server_cpu_s=30.0, before=before,
                after=after, data_shards=8, parity_shards=8, device_kind="TPU v5 lite")
    base.update(kw)
    return metrics.Window(**base)


# the traced interval: 5 dispatches of 100 blocks by the counters, 6 programs in the trace
TRACED = dict(trace={"busy_s": 0.5, "window_s": 4.0, "devices": 1, "dispatches": 6},
              traced_before=parse_metrics(EXPO.format(n=25, blocks=2060, dev=0, qw=0, items=0,
                                                      progs=0, b64=0, b128=0)))


@pytest.mark.parametrize("name,want", [
    ("server_cpu_s_per_gib", 15.0),
    ("dispatch_queue_wait_ms", 1e3 * 2.0 / 30),
    ("dispatch_blocks_per_call", 96.0),
    ("dispatch_thread_device_share", 50.0),
    ("window_compiles", 1 + 1),  # one program, and bucket 128 first seen
    ("codec_roofline", 100 * (6 * 100 * 2_097_664 / 819e9) / 0.5),
    ("device_idle_share", 87.5),
])
def test_reader(name, want):
    assert metrics.reader(name).read(_window(**TRACED)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["codec_roofline", "device_idle_share"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    assert metrics.reader(name).read(_window()) is None
    assert name not in metrics.read_all([name, "window_compiles"], _window())


def test_total_names_a_missing_series():
    from chipbench.procs import BenchFailure

    with pytest.raises(BenchFailure):
        total({}, "minio_tpu_dispatch_total")


# ---- traffic ---------------------------------------------------------------


@pytest.mark.parametrize("values,want", [
    (list(range(1, 101)), 95), (list(range(1, 201)), 190), ([5.0], 5.0), ([3, 1, 2], 3)])
def test_percentile_is_nearest_rank(values, want):
    assert traffic.percentile(values, 0.95) == want


def test_every_seed_gives_the_same_work_in_another_order():
    mix = traffic.load_mix("speedtest-put", rehearse=True)
    mod = plugins.load("generators", mix["generator"])
    gens = [mod.Generator(mix, "x:1", "b", seed) for seed in (1, 2 ** 31 + 12346)]
    walks = [[g.body_for(c, i) for c in range(g.clients) for i in range(8)] for g in gens]
    assert walks[0] != walks[1]
    assert sorted(set(walks[0])) == sorted(set(walks[1])) == list(range(mix["distinct_bodies"]))
    a, _ = mod.make_bodies(2 ** 31 + 12345, 3, 1 << 16)
    b, md5b = mod.make_bodies(2 ** 31 + 12345, 3, 1 << 16)
    assert a == b and len({len(x) for x in a}) == 1 and len(set(md5b)) == len(md5b)


def test_the_harness_gives_the_generator_the_configuration_and_the_drives():
    """Before `prepare()`, as attributes: the one generator that exists takes
    four arguments, is not edited, and walks and makes bodies as it did."""
    from chipbench.run import make_generator

    mix = traffic.load_mix("speedtest-put", rehearse=True)
    mix.update(object_mib=1, distinct_bodies=2)
    config, drives, seed = {"deployment": {"drives": 2}}, ["/x/d00", "/x/d01"], 2 ** 31 + 12345
    given = make_generator(mix, "x:1", "b", seed, config, drives)
    assert given.config is config and given.drives is drives
    assert given.bodies == [] and given.md5s == []  # prepare() has not run
    plain = plugins.load("generators", mix["generator"]).Generator(mix, "x:1", "b", seed)
    assert not hasattr(plain, "config") and not hasattr(plain, "drives")
    walks = [[g.body_for(c, i) for c in range(g.clients) for i in range(3)]
             for g in (plain, given)]
    assert walks[0] == walks[1] == [1, 0, 1, 0, 1, 0]
    given.prepare(), plain.prepare()
    assert given.md5s == plain.md5s == ['934c4b34c0cd938d3ab5ccc1b0bf7fae',
                                        '256ee7a04ff8a7c6e24f7145dbfb9faa']
    assert given.bodies == plain.bodies


def test_a_name_with_no_file_is_an_error():
    for kind in ("generators", "checks", "metrics"):
        with pytest.raises(FileNotFoundError):
            plugins.load(kind, "no_such_thing")


def test_samples_are_drawn_from_what_the_window_wrote_and_skip_damaged_keys():
    from chipbench.verify import Verification

    def put(key, done, status=200):
        return traffic.Request(0, "PUT", key, 0, done - 1, done, status, status == 200, 8)

    recs = [put("warm/a", 5), put("c00/0", 11), put("c00/1", 12), put("c00/2", 13, 500)]
    v = Verification(srv=None, cli=None, bucket="b", records=recs, window=(10, 20), gen=None,
                     config={}, mix={}, seed=7, before={}, after={}, platform="cpu")
    assert v.pool() == ["c00/0", "c00/1"] and v.details["keys"] == 3
    v.spoiled.add("c00/0")
    assert v.pool() == ["c00/1"]
    assert v.rng("readback").random() == v.rng("readback").random() != v.rng("other").random()


def test_importing_the_harness_imports_no_jax():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, chipbench.run, chipbench.verify, chipbench.traffic, chipbench.serve, chipbench.plugins; "
         "sys.exit(1 if 'jax' in sys.modules else 0)"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# ---- where the drives go ---------------------------------------------------


def test_drives_go_under_the_runs_own_directory_or_the_run_fails(tmp_path):
    from chipbench import procs

    assert procs.mount_type("/proc/self") == "proc"
    path, medium = procs.drives_root(str(tmp_path))
    assert path == str(tmp_path / "drives") and os.path.isdir(path)
    assert medium.split(":")[0] == procs.mount_type(str(tmp_path))
    procs.check_room(str(tmp_path), 1)
    with pytest.raises(procs.BenchFailure, match="TMPDIR"):
        procs.check_room(str(tmp_path), 1 << 60)
    # nothing of the benchmark names a path outside the checkout, HOME or TMPDIR
    for dirpath, _, files in os.walk(os.path.join(REPO, "chipbench")):
        for fn in files:
            if fn.endswith((".py", ".json")):
                with open(os.path.join(dirpath, fn)) as f:
                    assert "/dev/shm" not in f.read(), fn

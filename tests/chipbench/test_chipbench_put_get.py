"""The mixed PUT + GET cells of PR 34, `ec12p4-16d-4off.put-get` and its
healthy control `ec12p4-16d.put-get`: one traffic file and one generator
serve both, the deployment's state is the configuration's; `correct` has
teeth on both sides (the rest of a run driven with a guarantee broken one step
down must come out `correct: false` by the check named, at rehearsal size;
PERF.md gives the readings on the chip at the cells' own size); every seed
gives the same work in another order; the decode's work at 12 data shards by
hand; the eight readers on a hand-written exposition, and None — never 0,
never an exception — from a program without the rows (an older commit under
these benchmark files) and on a zero denominator; the two new steps of the
comparison on drives and counters made by hand. The entries in
`BENCHMARK.json` are held by name, by value and by order among themselves; a
later PR appends after them."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, bench  # noqa: E402

sys.path.insert(0, REPO) if REPO not in sys.path else None
from chipbench import metrics, plugins, reference, reference_decode, traffic, work_decode  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402

OFF, HEALTHY = "ec12p4-16d-4off.put-get", "ec12p4-16d.put-get"
CONFIG, MIX = "ec12p4-16d-4off", "put-get"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# reader -> the cells it lists, in the order the entries stand
READERS = {
    "mix_put_mib_s": [OFF, HEALTHY],
    "mix_get_mib_s": [OFF, HEALTHY],
    "mix_native_get_ms": [HEALTHY],
    "xla_decode_ms_per_get": [OFF],
    "xla_decode_pad_ms": [OFF],
    "put_offline_shards_per_put": [OFF],
    "mrf_backlog_per_put": [OFF],
    "xla_decode_roofline": [OFF],
}


def config(name: str) -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


# ---- the cells are what the issue names ------------------------------------


def test_the_cells_the_configuration_and_the_metrics_are_held_by_name_and_order():
    """By name, by value and by order among themselves, never by distance
    from the end: what a later PR appends to any list stands after them."""
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(HEALTHY) == names.index(OFF) + 1
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    assert by_name[OFF] == {"name": OFF, "config": CONFIG, "traffic": MIX, "chips": 1,
                            "why": by_name[OFF]["why"]}
    assert by_name[HEALTHY] == {"name": HEALTHY, "config": "ec12p4-16d", "traffic": MIX,
                                "chips": 1, "why": by_name[HEALTHY]["why"]}
    configs = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert len(configs) == 1 and configs[0]["file"] == f"chipbench/configs/{CONFIG}.json"
    assert sorted(configs[0]["reduced"]) == ["clients", "drives_are_directories", "objects"]
    # the eight stand together, in this order, each listing exactly its cells
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    first = per_layer.index("mix_put_mib_s")
    assert per_layer[first:first + len(READERS)] == list(READERS)
    for m in BENCH["per_layer"][first:first + len(READERS)]:
        assert m["workloads"] == READERS[m["name"]] and m["moves"] == "s3_mib_s"
        assert m["source"] == ("device_trace" if m["name"].endswith("_roofline")
                               else "program_counter")
    from chipbench.run import metric_names

    for cell in (OFF, HEALTHY):
        # the rate and the set-up, no tail: `s3_p95_ms` lists its cells by name
        assert {m["name"] for m in metric_names(BENCH, "end_to_end", cell)} \
            == {"s3_mib_s", "setup_s"}
        mine = {m["name"] for m in metric_names(BENCH, "per_layer", cell)}
        assert mine >= {n for n, cells in READERS.items() if cell in cells} | {
            "server_cpu_s_per_gib", "window_compiles", "device_idle_share"}
        assert not mine & {n for n, cells in READERS.items() if cell not in cells}


def test_put_get_is_4_put_and_4_get_clients_of_64_mib_over_16_objects():
    mix = traffic.load_mix(MIX, rehearse=False)
    assert (mix["clients"], mix["put_clients"], mix["get_clients"]) == (8, 4, 4)
    assert (mix["object_mib"], mix["objects"], mix["distinct_bodies"]) == (64, 16, 16)
    assert mix["generator"] == "closed_loop_put_get" and mix["unsigned_payload"] is True
    assert "progress" not in mix["warm"]  # the default: the PUT side moves the dispatcher
    assert mix["warm"]["first_calls"] == "minio_tpu_decode_first_calls_total"
    assert mix["trace_s"] == 12 and mix["drives_room_gib"] == 16
    assert mix["checks"] == ["answers", "readback", "ondrive_online_frames", "device_served",
                             "device_rung", "blocks_dispatched", "degraded_reference_setup",
                             "served_as_stated"]
    assert mix["verify"] == {"readback_keys_per_client": 4, "ondrive_objects": 2,
                             "reference_objects": 2, "timeout_s": 60}
    put = traffic.load_mix("speedtest-put", rehearse=False)
    assert mix["ladder"] == put["ladder"]  # the sibling's ladder, for the sibling's buckets
    small = traffic.load_mix(MIX, rehearse=True)
    assert small["checks"] == mix["checks"] and small["generator"] == mix["generator"]
    # the siblings' rehearsal sizes: 8 blocks are one read window over the device floor
    assert (small["put_clients"], small["get_clients"], small["object_mib"],
            small["objects"], small["distinct_bodies"]) == (2, 2, 8, 4, 4)
    for cell in (OFF, HEALTHY):
        with open(os.path.join(REPO, "chipbench", "workloads", f"{cell}.json")) as f:
            assert json.load(f)["warm_buckets"] == [64, 128, 256]


def test_the_deployment_is_the_default_set_with_every_fourth_drive_offline():
    cfg, healthy = config(CONFIG), config("ec12p4-16d")
    dep = cfg["deployment"]
    assert dep["offline_drives"] == [3, 7, 11, 15] and dep["offline_data_shards"] == 3
    assert cfg["architecture"] is None and cfg["server_env"] == {} == healthy["server_env"]
    for key in ("drives", "erasure_sets", "data_shards", "parity_shards", "stripe_block_bytes",
                "shard_bytes", "bitrot"):
        assert dep[key] == healthy["deployment"][key]  # the sibling's set and shapes
    assert (dep["data_shards"], dep["parity_shards"], dep["shard_bytes"]) == (12, 4, 87382)
    assert dep["object_bytes"] == 64 << 20 and dep["objects"] == 16
    assert cfg["expects"] == {"device_rung": "xla", "backend_level": 2, "decode_rung": "xla"}
    assert "decode_rung" not in healthy["expects"] and "offline_drives" not in healthy["deployment"]
    g = cfg["guarantees"]
    assert (g["write_quorum"], g["drives_online_here"], g["readable_with_drives_missing"],
            g["drives_missing_here"]) == (12, 12, 4, 4)
    assert g["bitrot_verified_on_read"] is True
    assert "node_holds_every_fourth_drive" in cfg["assumed"]
    knobs = cfg["knobs_as_shipped"]
    assert (knobs["MINIO_TPU_READ_WINDOW"], knobs["MINIO_TPU_DECODE_MIN_SHARDS"],
            knobs["MINIO_TPU_DRIVE_COOLDOWN_S"]) == (8, 64, 15)
    # whatever the key's rotation, the four drives hold 3 data shards and 1 parity
    for key in ("obj/0000", "obj/0007", "c03/000123", "warm/100-00"):
        held = [reference_decode.shard_order("chipbench", key, 16)[i]
                for i in dep["offline_drives"]]
        assert sum(1 for s in held if s < 12) == dep["offline_data_shards"] == 3


# ---- the generator ----------------------------------------------------------


def generators(cfg_name: str, seeds, **over):
    mix = dict(traffic.load_mix(MIX, rehearse=False), **over)
    mod = plugins.load("generators", mix["generator"])
    out = []
    for seed in seeds:
        g = mod.Generator(mix, "x:1", "b", seed)
        g.config, g.drives = config(cfg_name), [f"/x/d{i:02d}" for i in range(16)]
        out.append(g)
    return out


def test_every_seed_gives_the_same_puts_and_gets_in_another_order():
    gens = generators(CONFIG, (1, 2 ** 31 + 12346))
    assert all((g.put.clients, g.get.clients, g.put.object_bytes, g.get.object_bytes,
                g.objects) == (4, 4, 64 << 20, 64 << 20, 16) for g in gens)
    laps = 3
    for side, walk in (("get", lambda g, c, i: g.get.object_for(c, i)),
                       ("put", lambda g, c, i: g.put.body_for(c, i))):
        walks = [[walk(g, c, i) for c in range(4) for i in range(laps * 16)] for g in gens]
        assert walks[0] != walks[1], side
        for w in walks:
            assert {w.count(o) for o in range(16)} == {laps * 4}, side  # whole laps
    assert gens[0].setup_keys == [f"obj/{i:04d}" for i in range(16)] == gens[1].setup_keys


def test_the_state_is_the_configurations_and_the_bodies_are_made_once():
    made = []
    for cfg_name, seed in ((CONFIG, 2 ** 31 + 12345), (CONFIG, 2 ** 31 + 12345), ("ec12p4-16d", 7)):
        (g,) = generators(cfg_name, (seed,), object_mib=1, objects=3, distinct_bodies=3)
        g.prepare()
        made.append(g)
    assert made[0].bodies == made[1].bodies != made[2].bodies
    assert made[0].offline == [3, 7, 11, 15] and made[2].offline == []  # none named: none
    for g in made:
        assert g.put.bodies is g.get.bodies is g.bodies and g.put.md5s is g.md5s
        assert {len(b) for b in g.bodies} == {1 << 20} and g.offline_files == {}
    put = traffic.Request(0, "PUT", "c02/000004", 2, 0, 1, 200, True, 1)
    get = traffic.Request(0, "GET", "obj/0001", 1, 0, 1, 200, True, 1)
    assert made[0].sent(put) == (made[0].bodies[2], made[0].md5s[2])
    assert made[0].sent(get)[1] == made[0].md5s[1]
    with pytest.raises(Exception, match="distinct_bodies"):
        generators(CONFIG, (1,), distinct_bodies=8)


# ---- the work ---------------------------------------------------------------


@pytest.mark.parametrize("m,want", [(1, 1_136_382), (3, 1_311_210), (4, 1_398_624)])
def test_decode_bytes_per_block_at_twelve_data_shards_by_hand(m, want):
    # 12 survivors in, m rebuilt out, 32 bytes of digest for each of the 12 + m
    assert want == 12 * 87382 + m * 87382 + 32 * (12 + m)
    assert work_decode.decode_bytes_per_block(12, m) == want


# ---- the readers ------------------------------------------------------------


def expo(blocks=0, get_bytes=None, phases=None, calls=None, offline_shards=0, mrf=0,
         xla=None, fused=None) -> dict:
    """The rows the readers read, zero unless given. `phases`/`calls`:
    {(layer, phase): seconds / calls}; `xla`/`fused`: {missing: device blocks}."""
    lines = [f'minio_tpu_dispatch_blocks_total{{class="foreground"}} {blocks}',
             'minio_tpu_dispatch_blocks_total{class="background"} 0',
             f'minio_tpu_put_offline_shards_total {offline_shards}',
             f'minio_tpu_heal_mrf_pending {mrf}']
    lines += [f'minio_tpu_get_bytes_total{{path="{p}"}} {(get_bytes or {}).get(p, 0)}'
              for p in ("native", "windowed")]
    rows = [("get", p) for p in ("start", "native", "respond")] + [("put", "commit")] + [
        ("decode", p) for p in ("pad", "pack", "h2d", "kernel", "d2h", "unpack", "host")]
    for layer, p in rows:
        lines.append(f'minio_tpu_phase_seconds_total{{layer="{layer}",phase="{p}"}} '
                     f'{(phases or {}).get((layer, p), 0)}')
        lines.append(f'minio_tpu_phase_calls_total{{layer="{layer}",phase="{p}"}} '
                     f'{(calls or {}).get((layer, p), 0)}')
    for rung, table in (("fused", fused), ("xla", xla)):
        for m in range(1, 9):
            lines.append(f'minio_tpu_decode_device_blocks_total{{rung="{rung}",missing="{m}"}} '
                         f'{(table or {}).get(m, 0)}')
    return parse_metrics("\n".join(lines))


BEFORE = expo(blocks=1000, get_bytes={"native": 10 << 20, "windowed": 50 << 20},
              calls={("get", "start"): 10, ("get", "native"): 4, ("put", "commit"): 6},
              phases={("get", "native"): 1.0, ("decode", "pad"): 0.5}, offline_shards=24, mrf=20,
              xla={3: 640})
TRACED_BEFORE = expo(xla={3: 1440, 2: 8}, fused={1: 7})
AFTER = expo(blocks=1640, get_bytes={"native": 330 << 20, "windowed": 1330 << 20},
             calls={("get", "start"): 30, ("get", "native"): 9, ("put", "commit"): 16},
             phases={("get", "native"): 2.5, ("decode", "pad"): 2.5, ("decode", "h2d"): 1.0,
                     ("decode", "kernel"): 3.0, ("decode", "d2h"): 0.5, ("decode", "unpack"): 1.0,
                     ("decode", "pack"): 9.0, ("decode", "host"): 9.0},
             offline_shards=64, mrf=30, xla={3: 1920, 2: 16}, fused={1: 99})
WANT = {
    "mix_put_mib_s": 64.0,                 # 640 blocks in 10 s
    "mix_get_mib_s": 160.0,                # 320 + 1280 MiB in 10 s
    "mix_native_get_ms": 300.0,            # 1.5 s over 5 healthy GETs
    "xla_decode_ms_per_get": 375.0,        # 2 + 1 + 3 + 0.5 + 1 s over 20 GETs: no pack, no host
    "xla_decode_pad_ms": 100.0,
    "put_offline_shards_per_put": 4.0,     # 40 shards over 10 PUTs
    "mrf_backlog_per_put": 1.0,
    # traced: 480 blocks at m = 3 and 8 at m = 2 on the XLA rung, 0.01 s busy; the
    # fused rung's blocks are not this rung's work
    "xla_decode_roofline": 100 * ((480 * 1_311_210 + 8 * 1_223_796) / 819e9) / 0.01,
}


def window(before=BEFORE, after=AFTER, **kw):
    base = dict(seconds=10.0, acked_bytes=2 << 30, server_cpu_s=30.0, before=before, after=after,
                data_shards=12, parity_shards=4, device_kind="TPU v5 lite",
                trace={"busy_s": 0.01, "window_s": 4.0, "devices": 1, "dispatches": 51},
                traced_before=TRACED_BEFORE)
    base.update(kw)
    return metrics.Window(**base)


def test_every_reader_of_the_cells_has_a_value_by_hand():
    assert sorted(WANT) == sorted(READERS) and len(READERS) == 8
    assert work_decode.decode_bytes_per_block(12, 2) == 1_223_796


@pytest.mark.parametrize("name", list(READERS))
def test_reader_reads_the_value(name):
    assert metrics.reader(name).read(window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [n for n in READERS if n != "mix_put_mib_s"])
def test_a_program_without_the_rows_reads_nothing_and_does_not_raise(name):
    """These files are laid over the parent's checkout too: it has the
    dispatcher's blocks (`mix_put_mib_s` reads there), decode counters by
    rung only here, and none of the new rows."""
    old = parse_metrics(
        'minio_tpu_dispatch_blocks_total{class="foreground"} 5\n'
        'minio_tpu_phase_calls_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_phase_seconds_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_decode_device_blocks_total{rung="xla"} 9\n'
        'minio_tpu_decode_blocks_total{family="reedsolomon"} 0\n')
    assert metrics.reader(name).read(window(before=old, after=old, traced_before=old)) is None
    assert name not in metrics.read_all([name, "window_compiles"],
                                        window(before={}, after={}, traced_before={}))


@pytest.mark.parametrize("name", [n for n in READERS if not n.startswith("mix_p") and
                                  n != "mix_get_mib_s"])
def test_a_zero_denominator_reads_nothing(name):
    """A window without a PUT, a GET or a decode: no mean, no share — None."""
    assert metrics.reader(name).read(window(before=AFTER, after=AFTER, traced_before=AFTER)) is None


def test_the_rates_of_an_idle_window_are_zero_and_of_no_window_nothing():
    quiet = window(before=AFTER, after=AFTER)
    assert metrics.reader("mix_put_mib_s").read(quiet) == 0.0
    assert metrics.reader("mix_get_mib_s").read(quiet) == 0.0
    assert metrics.reader("mix_put_mib_s").read(window(seconds=0.0)) is None
    assert metrics.reader("mix_get_mib_s").read(window(seconds=0.0)) is None


def test_the_roofline_needs_a_device_trace_and_cannot_pass_its_ceiling():
    read = metrics.reader("xla_decode_roofline").read
    assert read(window(trace=None, traced_before=None)) is None
    assert read(window(trace={"busy_s": None, "window_s": 4.0, "devices": 0})) is None
    # all the device's busy time spent on nothing but these blocks at the
    # full 819 GB/s is 100 %: the work is bytes that must move, the time is
    # the busy union, so the share is at most that
    least = (480 * 1_311_210 + 8 * 1_223_796) / 819e9
    assert read(window(trace={"busy_s": least, "window_s": 4.0, "devices": 1})) \
        == pytest.approx(100.0)


# ---- the two new steps, on drives and counters made by hand ----------------


def verification(tmp_path, cfg_name, keys, records=(), details=None):
    """What a step is given, over drive directories of this test."""
    drives = [str(tmp_path / f"d{i:02d}") for i in range(16)]
    body = bytes(range(256)) * 4096  # one stripe block
    gen = types.SimpleNamespace(sent=lambda r: (body, "md5"))
    recs = list(records) + [traffic.Request(0, "PUT", k, 0, 10, 11, 200, True, len(body))
                            for k in keys]
    from chipbench.verify import Verification

    v = Verification(srv=types.SimpleNamespace(drives=drives, port=0), cli=None, bucket="b",
                     records=recs, window=(5, 20), gen=gen, config=config(cfg_name),
                     mix={"verify": {"ondrive_objects": 2}, "object_mib": 1}, seed=7,
                     before={}, after={}, platform="cpu")
    v.details.update(details or {})
    return v, drives, body


def write_shards(drives, key, body, skip=()):
    frames = reference.object_frames(body, 12, 4)
    order = reference_decode.shard_order("b", key, 16)
    for pos, drive in enumerate(drives):
        if pos not in skip:
            path = os.path.join(drive, "b", key, "uuid")
            os.makedirs(path)
            with open(os.path.join(path, "part.1"), "wb") as f:
                f.write(frames[order[pos]])


@pytest.mark.parametrize("cfg_name,offline", [(CONFIG, (3, 7, 11, 15)), ("ec12p4-16d", ())])
def test_ondrive_online_frames_wants_a_shard_on_every_online_drive_and_none_elsewhere(
        tmp_path, cfg_name, offline):
    step = plugins.load("checks", "ondrive_online_frames")
    v, drives, body = verification(tmp_path, cfg_name, ["c00/000000", "c01/000000"])
    for key in v.last:
        write_shards(drives, key, body, skip=offline)
    assert step.run(v) == {"online_shards_wrong": (0, 0), "offline_shards_written": (0, 0)}
    assert v.details["online_shards_compared"] == 2 * (16 - len(offline))
    # a shard lost on an online drive, another one altered
    os.remove(reference_decode.shard_path(drives[0], "b", "c00/000000"))
    path = reference_decode.shard_path(drives[1], "b", "c01/000000")
    with open(path, "r+b") as f:
        f.seek(40)
        f.write(b"\x00\x01")
    got = step.run(v)
    assert got["online_shards_wrong"] == (2, 0) and got["offline_shards_written"] == (0, 0)
    if offline:  # and one written where the deployment states no drive
        write_shards([drives[7]], "c00/000000", body)
        assert step.run(v)["offline_shards_written"] == (1, 0)


def tpu_rows(rebuilt=0, xla=None, fused=None, failures=0) -> dict:
    lines = [f'minio_tpu_decode_blocks_total{{family="reedsolomon"}} {rebuilt}',
             f'minio_tpu_fused_decode_failures_total {failures}']
    for rung, table in (("fused", fused), ("xla", xla)):
        lines += [f'minio_tpu_decode_dispatches_total{{rung="{rung}",missing="{m}"}} '
                  f'{(table or {}).get(m, 0)}' for m in range(1, 9)]
    return parse_metrics("\n".join(lines))


def gets(n, done=10.0):
    return [traffic.Request(0, "GET", "obj/0000", 0, done - 1, done, 200, True, 1 << 20)
            for _ in range(n)]


@pytest.mark.parametrize("cfg_name,rows,want", [
    # 4-off: 40 generator GETs + 3 readback + 2 reference, 1 MiB each, all rebuilt at m = 3
    (CONFIG, dict(rebuilt=45, xla={3: 45}), (0, 0, 0, 0)),
    (CONFIG, dict(rebuilt=40, xla={3: 40}), (5, 0, 0, 0)),            # five served healthy
    (CONFIG, dict(rebuilt=45), (0, 1, 0, 0)),                          # rebuilt on the host
    (CONFIG, dict(rebuilt=45, xla={3: 40}, fused={3: 5}), (0, 5, 0, 0)),
    (CONFIG, dict(rebuilt=45, xla={3: 44, 4: 1}, failures=2), (0, 0, 1, 2)),
    # healthy: nothing rebuilt, no dispatch on any rung
    ("ec12p4-16d", dict(), (0, 0, 0, 0)),
    ("ec12p4-16d", dict(rebuilt=8, xla={1: 1}), (8, 1, 1, 0)),          # a GET served degraded
])
def test_served_as_stated_counts_every_get_since_boot(tmp_path, cfg_name, rows, want):
    step = plugins.load("checks", "served_as_stated")
    v, _, _ = verification(tmp_path, cfg_name, ["c00/000000"], records=gets(40),
                           details={"readback_keys": 3, "reference_gets": 2})
    v.before, v.after = tpu_rows(), tpu_rows(**rows)
    step.scrape = lambda port, group: tpu_rows(**rows)
    got = step.run(v)
    assert (got["get_blocks_not_as_stated"][0], got["decode_off_rung"][0],
            got["decode_missing_not_as_stated"][0], got["fused_decode_failures"][0]) == want
    assert all(limit == 0 for _, limit in got.values())
    assert v.details["get_blocks_since_boot"] == 45


def test_degraded_reference_setup_gives_the_step_the_set_up_objects_alone(tmp_path, monkeypatch):
    step = plugins.load("checks", "degraded_reference_setup")
    v, _, _ = verification(tmp_path, CONFIG, ["obj/0000", "obj/0001", "c00/000000", "warm/100-00"])
    v.gen.setup_keys = ["obj/0000", "obj/0001"]
    seen = {}

    def fake(kind, name):
        assert (kind, name) == ("checks", "degraded_reference")
        return types.SimpleNamespace(run=lambda view: seen.update(
            keys=sorted(view.last), details=view.details, spoiled=view.spoiled) or {"x": (0, 0)})

    monkeypatch.setattr(step.plugins, "load", fake)
    assert step.run(v) == {"x": (0, 0)}
    assert seen["keys"] == ["obj/0000", "obj/0001"] and len(v.last) == 4  # the run's own untouched
    assert seen["details"] is v.details and seen["spoiled"] is v.spoiled


# ---- both cells, traced, at rehearsal size ----------------------------------


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("cell", [OFF, HEALTHY])
def test_traced_rehearsal_reads_every_counter_fed_reader_of_the_cell(cell, cache):
    r, last = bench(cache, "--workload", cell, "--seed", str(2 ** 31 + 79), "--seconds", "2",
                    "--trace", "1", "--rehearse")
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    m = {k: v["value"] for k, v in last["metrics"].items()}
    want = {n for n, cells in READERS.items() if cell in cells and not n.endswith("_roofline")}
    assert want <= set(m) and not set(READERS) - want & set(m)  # no device metric on the CPU
    assert m["mix_put_mib_s"] > 0 and m["mix_get_mib_s"] > 0
    d = last["details"]
    offline = 4 if cell == OFF else 0
    assert d["online_shards_compared"] == 2 * (16 - offline) and d["readback_keys"] >= 2
    assert d["reference_gets"] == d["reference_objects"] == 1
    assert d["dispatcher_blocks_since_boot"] >= d["put_blocks_since_boot"] > 0
    assert d["get_blocks_since_boot"] > 0
    if cell == OFF:
        assert m["put_offline_shards_per_put"] == 4.0 and m["mrf_backlog_per_put"] > 0
        assert m["xla_decode_ms_per_get"] > m["xla_decode_pad_ms"] > 0
        assert d["blocks_rebuilt_since_boot"] == d["get_blocks_since_boot"]
        assert d["decode_dispatches_since_boot"]["xla"] > 0 and d["window_decode_dispatches"] > 0
    else:
        assert m["mix_native_get_ms"] > 0
        assert d["blocks_rebuilt_since_boot"] == 0
        assert d["decode_dispatches_since_boot"] == {"fused": 0, "xla": 0}


# ---- the controls -----------------------------------------------------------

CASES = [
    # (cell, launcher, fault, the check that has to read above its limit)
    (OFF, "broken_get_serve", "host-decode", "decode_off_rung"),
    (OFF, "broken_get_serve", "drives-online", "get_blocks_not_as_stated"),
    (OFF, "broken_put_get_serve", "fewer-shards", "online_shards_wrong"),
    (OFF, "broken_put_get_serve", "offline-written", "offline_shards_written"),
    (HEALTHY, "broken_put_get_serve", "one-drive-off", "get_blocks_not_as_stated"),
]


@pytest.mark.parametrize("cell,launcher,fault,caught_by", CASES, ids=[c[2] for c in CASES])
def test_a_broken_guarantee_is_not_correct(cell, launcher, fault, caught_by, cache):
    r, last = bench(cache, "--workload", cell, "--seed", "21", "--seconds", "1", "--trace", "0",
                    "--rehearse", "--launcher", f"tests.chipbench.{launcher}",
                    CHIPBENCH_FAULT=fault)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    checks = {k: c["value"] for k, c in last["checks"].items()}
    assert checks[caught_by] > 0, checks
    assert f"chipbench check {caught_by}:" in r.stderr and "NOT CORRECT" in r.stderr
    if fault == "host-decode":
        # right bytes, every block rebuilt, none of them on a device rung
        assert checks["answers_wrong"] == 0 and checks["get_blocks_not_as_stated"] == 0
        assert last["details"]["window_decode_dispatches"] == 0
    if fault == "drives-online":
        # nothing rebuilt, and the PUTs wrote where the deployment states no drive
        assert last["details"]["blocks_rebuilt_since_boot"] == 0
        assert checks["offline_shards_written"] > 0
    if fault == "fewer-shards":
        assert checks["readback_wrong"] > 0 and checks["offline_shards_written"] == 0
    if fault == "offline-written":
        # the only thing wrong is where the shards are: every other check reads 0
        assert {k for k, v in checks.items() if v} == {"offline_shards_written"}
    if fault == "one-drive-off":
        assert checks["decode_off_rung"] > 0 and checks["answers_wrong"] == 0

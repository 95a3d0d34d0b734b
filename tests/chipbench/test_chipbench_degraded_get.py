"""The degraded-GET cell, `ec8p8-16d-2off.degraded-get`: its traffic is what
ISSUE 29 names; `correct` has teeth on the read side (`broken_get_serve.py`:
the rest of a run driven with the read path broken one step down must come
out `correct: false` by the check named, at rehearsal size; PERF.md gives the
readings on the chip at the cell's own size); every seed gives the same
reads in another order; the decode's work by hand; the read-side readers —
PR 29's thirteen and the five of PR 33 that read what PRs 30 and 32 changed —
on a hand-written exposition, and None — never 0, never an exception — from
a program without the rows (an older commit under these benchmark files).
The cell's entries in `BENCHMARK.json` are held by name, by value and by
order among themselves; a later PR appends after them."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, bench  # noqa: E402

sys.path.insert(0, REPO) if REPO not in sys.path else None
from chipbench import metrics, plugins, traffic, work, work_decode  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402

CELL, CONFIG, MIX = "ec8p8-16d-2off.degraded-get", "ec8p8-16d-2off", "degraded-get"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
READERS = ["decode_roofline", "decode_blocks_per_call", "decode_device_block_share",
           "decode_window_first_calls", "get_read_wait_ms", "get_decode_wait_ms", "get_join_ms",
           "decode_host_copy_ms", "decode_link_ms", "decode_kernel_ms",
           "get_threads_cpu_s_per_gib", "read_pool_cpu_s_per_gib", "get_hedge_reads_per_get"]
# PR 33: what PRs 30 and 32 changed (run reads, the one-pass stack) and the hand-over
LATER = ["get_stack_ms", "get_respond_ms", "get_shard_reads_per_get", "get_frames_per_read",
         "get_stack_copies_per_get"]


# ---- the cell is what the issue names --------------------------------------


def test_the_cell_its_configuration_and_its_metrics_are_held_by_name_and_order():
    """By name, by value and by order among themselves, never by distance
    from the end: what a later PR appends to any list stands after them."""
    cells = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cells == [{"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
                      "why": cells[0]["why"]}]
    configs = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert len(configs) == 1
    assert sorted(configs[0]["reduced"]) == ["clients", "drives_are_directories", "objects"]
    # the thirteen follow the first seven and PR 25's fourteen, in their order
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[21:34] == READERS and len(set(names)) == len(names)
    for m in BENCH["per_layer"][21:34]:
        assert CELL in m["workloads"] and m["moves"] == "s3_mib_s"
    from chipbench.run import metric_names

    assert {m["name"] for m in metric_names(BENCH, "end_to_end", CELL)} >= {"s3_mib_s", "setup_s"}
    assert {m["name"] for m in metric_names(BENCH, "per_layer", CELL)} >= set(READERS) | {
        "server_cpu_s_per_gib", "window_compiles", "device_idle_share"}


def test_the_tail_and_the_five_later_readers_are_this_cells_too():
    """PR 33's additions, by name: the cell reports the tail under the one
    bound the metric has, and five readers of what PRs 30 and 32 changed
    follow the thirteen in their order."""
    p95 = next(m for m in BENCH["end_to_end"] if m["name"] == "s3_p95_ms")
    assert p95["workloads"][:2] == ["ec8p8-16d.speedtest-put", CELL] and p95["bound"] == 0.25
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[34:39] == LATER
    for m in BENCH["per_layer"][34:39]:
        assert CELL in m["workloads"] and m["moves"] == "s3_mib_s"
        assert m["source"] == "program_counter"
        assert m["layer"] == {"get_respond_ms": "front end"}.get(m["name"], "erasure read path")
    from chipbench.run import metric_names

    assert {m["name"] for m in metric_names(BENCH, "end_to_end", CELL)} >= {"s3_p95_ms"}
    assert {m["name"] for m in metric_names(BENCH, "per_layer", CELL)} >= set(LATER)


def test_degraded_get_is_8_clients_reading_16_objects_of_64_mib_with_d03_and_d11_offline():
    mix = traffic.load_mix(MIX, rehearse=False)
    assert (mix["clients"], mix["object_mib"], mix["objects"]) == (8, 64, 16)
    assert mix["generator"] == "closed_loop_get" and mix["trace_s"] == 12
    assert mix["warm"]["progress"] == "minio_tpu_decode_dispatches_total"
    assert mix["checks"] == ["answers", "device_served", "degraded_reference", "decode_rung",
                             "all_degraded"]
    small = traffic.load_mix(MIX, rehearse=True)
    assert small["checks"] == mix["checks"] and small["generator"] == mix["generator"]
    # 8 blocks are one read window of 128 shards, over the 64 of the device floor
    assert (small["clients"], small["object_mib"], small["objects"]) == (2, 8, 4)
    with open(os.path.join(REPO, "chipbench", "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]
    assert dep["offline_drives"] == [3, 11] and dep["drives"] == 16 and cfg["architecture"] is None
    assert (dep["data_shards"], dep["parity_shards"], dep["shard_bytes"]) == (8, 8, 131072)
    assert dep["object_bytes"] == mix["object_mib"] << 20 and dep["objects"] == mix["objects"]
    assert cfg["server_env"] == {"MINIO_STORAGE_CLASS_STANDARD": "EC:8"}
    assert cfg["expects"] == {"device_rung": "fused", "backend_level": 2, "decode_rung": "fused"}
    assert cfg["guarantees"]["bitrot_verified_on_read"] is True
    knobs = cfg["knobs_as_shipped"]
    assert (knobs["MINIO_TPU_READ_WINDOW"], knobs["MINIO_TPU_DECODE_MIN_SHARDS"],
            knobs["MINIO_TPU_CACHE_MEM_MB"]) == (8, 64, 256)


def test_every_seed_gives_the_same_reads_in_another_order():
    """Beside `test_every_seed_gives_the_same_work_in_another_order`: 16
    bodies of 64 MiB, the same offline pair, the same count of GETs per
    object to within one lap; the seed turns bytes and order only."""
    mix = traffic.load_mix(MIX, rehearse=False)
    mod = plugins.load("generators", mix["generator"])
    with open(os.path.join(REPO, "chipbench", "configs", f"{CONFIG}.json")) as f:
        cfg = json.load(f)
    gens = []
    for seed in (1, 2 ** 31 + 12346):
        g = mod.Generator(mix, "x:1", "b", seed)
        g.config, g.drives = cfg, [f"/x/d{i:02d}" for i in range(16)]
        gens.append(g)
    assert all((g.clients, g.objects, g.object_bytes) == (8, 16, 64 << 20) for g in gens)
    laps = 5  # every client 5 laps of the 16 objects
    walks = [[g.object_for(c, i) for c in range(g.clients) for i in range(laps * g.objects)]
             for g in gens]
    assert walks[0] != walks[1]
    for walk in walks:
        counts = [walk.count(o) for o in range(16)]
        assert set(counts) == {laps * 8}  # whole laps: every object as often
    # cut anywhere, the counts differ by at most one lap of the clients
    cut = [g.object_for(c, i) for g in gens[:1] for c in range(8) for i in range(37)]
    assert max(cut.count(o) for o in range(16)) - min(cut.count(o) for o in range(16)) <= 8
    # the bytes from the seed, the offline pair from the configuration
    small = dict(mix, object_mib=1, objects=3)
    made = []
    for seed in (2 ** 31 + 12345, 2 ** 31 + 12345, 7):
        g = mod.Generator(small, "x:1", "b", seed)
        g.config, g.drives = cfg, gens[0].drives
        g.prepare()
        made.append(g)
    assert made[0].bodies == made[1].bodies != made[2].bodies
    assert all(g.offline == [3, 11] for g in made)
    assert {len(b) for g in made for b in g.bodies} == {1 << 20}
    assert made[0].sent(traffic.Request(0, "PUT", "obj/0002", 2, 0, 1, 200, True, 1))[1] \
        == made[0].md5s[2]


# ---- the work ---------------------------------------------------------------


@pytest.mark.parametrize("m,want", [(1, 1_179_936), (2, 1_311_040), (8, 2_097_664)])
def test_decode_bytes_per_block_by_hand(m, want):
    # 8 survivors in, m rebuilt out, 32 bytes of digest for each of the 8 + m
    assert want == 8 * 131072 + m * 131072 + 32 * (8 + m)
    assert work_decode.decode_bytes_per_block(8, m) == want
    assert work_decode.decode_ops_per_block(8, m) == 2 * 8 * m * 64 * 131072
    # rebuilding all eight is an encode's work
    assert work_decode.decode_bytes_per_block(8, 8) == work.encode_bytes_per_block(8, 8)


# ---- the readers ------------------------------------------------------------

GET = ("start", "read_wait", "stack", "decode_wait", "join", "cache_fill", "respond", "shard_io")
DECODE = ("pad", "pack", "h2d", "kernel", "d2h", "unpack", "host")


def expo(seconds=None, cpu=None, calls=None, by_m=None, rebuilt=0, first=0, hedges=0,
         frames=None, copies=None) -> dict:
    """Every row the program exports for the read side, zero unless given;
    `by_m`: {shards rebuilt: (fused dispatches, fused blocks)}; `frames`:
    {unit: frames verified}; `copies`: {(unit, layout): stack copies}."""
    lines = []
    for series, table in (("seconds", seconds), ("cpu_seconds", cpu), ("calls", calls)):
        for layer, names in (("get", GET), ("decode", DECODE)):
            lines += [f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{p}"}} '
                      f'{(table or {}).get((layer, p), 0)}' for p in names]
    for rung in ("fused", "xla"):
        for m in range(1, 9):
            n, b = (by_m or {}).get(m, (0, 0)) if rung == "fused" else (0, 0)
            lines.append(f'minio_tpu_decode_dispatches_total{{rung="{rung}",missing="{m}"}} {n}')
            lines.append(f'minio_tpu_decode_device_blocks_total{{rung="{rung}",missing="{m}"}} {b}')
    lines.append(f'minio_tpu_decode_blocks_total{{family="reedsolomon"}} {rebuilt}')
    lines.append('minio_tpu_decode_blocks_total{family="cauchy"} 0')
    lines.append(f'minio_tpu_decode_first_calls_total{{rung="fused",missing="1",batch="16"}} 1')
    lines.append(f'minio_tpu_decode_first_calls_total{{rung="fused",missing="2",batch="16"}} {first}')
    for e, v in (("reads", hedges), ("wins", 0), ("losses", 0)):
        lines.append(f'minio_tpu_get_hedges_total{{event="{e}"}} {v}')
    for u in ("block", "run"):
        lines.append(f'minio_tpu_get_shard_frames_total{{unit="{u}"}} {(frames or {}).get(u, 0)}')
        lines += [f'minio_tpu_get_stack_copies_total{{unit="{u}",layout="{lay}"}} '
                  f'{(copies or {}).get((u, lay), 0)}' for lay in ("packed", "rows")]
    return parse_metrics("\n".join(lines))


BEFORE = expo(calls={("get", "start"): 10, ("get", "shard_io"): 640},
              seconds={("get", "read_wait"): 1.0, ("get", "stack"): 0.5},
              by_m={1: (80, 640)}, rebuilt=640, hedges=4,
              frames={"run": 5120}, copies={("run", "packed"): 640})
TRACED_BEFORE = expo(by_m={1: (130, 1040), 2: (3, 9)}, rebuilt=1060)
AFTER = expo(
    calls={("get", "start"): 30, ("get", "shard_io"): 1928},
    seconds={("get", "read_wait"): 5.0, ("get", "decode_wait"): 9.0, ("get", "join"): 1.0,
             ("get", "stack"): 2.5, ("get", "respond"): 14.0,
             ("decode", "pad"): 1.0, ("decode", "pack"): 2.0, ("decode", "unpack"): 1.0,
             ("decode", "h2d"): 0.5, ("decode", "d2h"): 1.5, ("decode", "kernel"): 2.0},
    cpu={("get", "decode_wait"): 3.0, ("get", "join"): 1.0, ("get", "shard_io"): 6.0},
    by_m={1: (230, 1840), 2: (5, 15)}, rebuilt=1920, first=1, hedges=14,
    frames={"run": 15360, "block": 8}, copies={("run", "packed"): 1900, ("block", "rows"): 40})
WANT = {
    "decode_blocks_per_call": (1200 + 15) / (150 + 5),
    "decode_device_block_share": 100.0 * 1215 / 1280,
    "decode_window_first_calls": 1.0,
    "get_read_wait_ms": 200.0,        # 4 s over 20 GETs
    "get_decode_wait_ms": 450.0,
    "get_join_ms": 50.0,
    "decode_host_copy_ms": 200.0,     # pad + pack + unpack
    "decode_link_ms": 100.0,          # h2d + d2h
    "decode_kernel_ms": 100.0,
    "get_threads_cpu_s_per_gib": 2.0,  # (3 + 1) CPU s over 2 GiB, shard_io left out
    "read_pool_cpu_s_per_gib": 3.0,
    "get_hedge_reads_per_get": 0.5,
    # traced: 100 m=1 dispatches of 8 blocks and 2 m=2 dispatches of 3 by the
    # counters, 51 programs in the trace, 0.01 s busy
    "decode_roofline": 100 * (51 * (800 * 1_179_936 + 6 * 1_311_040) / 102 / 819e9) / 0.01,
    # the five of PR 33, over the same 20 GETs
    "get_stack_ms": 100.0,            # 2 s
    "get_respond_ms": 700.0,
    "get_shard_reads_per_get": 64.4,  # 1288 reads: 64 a GET and 8 hedged
    "get_frames_per_read": (1280 * 8 + 8 * 1) / 1288,  # 1280 runs of 8 frames, 8 reads of one
    "get_stack_copies_per_get": 65.0,  # 1260 of a run + 40 of a block, both layouts
}


def window(before=BEFORE, after=AFTER, **kw):
    base = dict(seconds=10.0, acked_bytes=2 << 30, server_cpu_s=30.0, before=before, after=after,
                data_shards=8, parity_shards=8, device_kind="TPU v5 lite",
                trace={"busy_s": 0.01, "window_s": 4.0, "devices": 1, "dispatches": 51},
                traced_before=TRACED_BEFORE)
    base.update(kw)
    return metrics.Window(**base)


def test_every_reader_of_the_cell_has_a_value_by_hand():
    assert sorted(WANT) == sorted(READERS + LATER) and len(READERS) == 13 and len(LATER) == 5


@pytest.mark.parametrize("name", READERS + LATER)
def test_reader_reads_the_value(name):
    assert metrics.reader(name).read(window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS + LATER)
def test_a_program_without_the_rows_reads_nothing_and_does_not_raise(name):
    """These files are laid over the parent's checkout too: it exports the
    decode counters by rung only, and no `get` or `decode` phase."""
    old = parse_metrics(
        'minio_tpu_phase_calls_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_phase_seconds_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_phase_cpu_seconds_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_decode_dispatches_total{rung="fused"} 0\n'
        'minio_tpu_decode_dispatches_total{rung="xla"} 0\n'
        'minio_tpu_decode_device_blocks_total{rung="fused"} 0\n'
        'minio_tpu_decode_blocks_total{family="reedsolomon"} 0\n')
    assert metrics.reader(name).read(window(before=old, after=old, traced_before=old)) is None
    assert name not in metrics.read_all([name, "window_compiles"],
                                        window(before={}, after={}, traced_before={}))


@pytest.mark.parametrize("name", ["decode_roofline"])
def test_the_roofline_is_never_zero_and_needs_a_device_trace(name):
    assert metrics.reader(name).read(window(trace=None, traced_before=None)) is None
    quiet = window(before=AFTER, after=AFTER, traced_before=AFTER)
    assert metrics.reader(name).read(quiet) is None  # no decode in the trace: nothing, not 0
    assert metrics.reader("get_read_wait_ms").read(quiet) is None  # no GET: no mean


@pytest.mark.parametrize("name", LATER)
def test_a_window_without_a_get_reads_nothing(name):
    assert metrics.reader(name).read(window(before=AFTER, after=AFTER, traced_before=AFTER)) is None


@pytest.mark.parametrize("name,want", [("get_frames_per_read", None),
                                       ("get_stack_copies_per_get", None),
                                       ("get_shard_reads_per_get", 64.4)])
def test_a_program_with_the_read_clock_and_neither_counter_reads_what_it_has(name, want):
    """PR 29's tree: the `get` phases, no frame and no stack-copy counter."""
    def strip(series):
        return {k: v for k, v in series.items() if not k.startswith(
            ("minio_tpu_get_shard_frames", "minio_tpu_get_stack_copies"))}
    got = metrics.reader(name).read(window(before=strip(BEFORE), after=strip(AFTER)))
    assert got is None if want is None else got == pytest.approx(want)


# ---- the controls -----------------------------------------------------------

CASES = [
    # (fault, a check that has to read above its limit)
    ("rebuilt-flip", "answers_wrong"),
    ("host-decode", "decode_rung"),
    ("drives-online", "all_degraded"),
]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


@pytest.mark.parametrize("fault,caught_by", CASES, ids=[c[0] for c in CASES])
def test_a_broken_read_path_is_not_correct(fault, caught_by, cache):
    r, last = bench(cache, "--workload", CELL, "--seed", "21", "--seconds", "1", "--trace", "0",
                    "--rehearse", "--launcher", "tests.chipbench.broken_get_serve",
                    CHIPBENCH_FAULT=fault)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is False and last["failed"] == 0
    c = last["checks"][caught_by]
    assert c["value"] > c["limit"], last["checks"]
    assert f"chipbench check {caught_by}:" in r.stderr and "NOT CORRECT" in r.stderr
    if fault == "rebuilt-flip":
        # the sampled objects against the reference's reconstruction too
        assert last["checks"]["degraded_reference_wrong"]["value"] >= 1
        assert last["checks"]["all_degraded"]["value"] == 0  # wrong bytes, all of them rebuilt
    if fault == "host-decode":
        assert last["checks"]["answers_wrong"]["value"] == 0  # right bytes, from the host
        assert last["details"]["window_decode_dispatches"] == 0

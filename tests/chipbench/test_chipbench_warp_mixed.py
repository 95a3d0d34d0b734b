"""The `warp mixed` cell of PR 36, `ec12p4-16d-warp.warp-mixed`: the data
files are what the issue names (entries appended, held by name and by order
among themselves, never by distance from the end); the generator's deck —
every 20 operations of a client are 9 GET / 6 HEAD / 3 PUT / 2 DELETE on
every seed, a DELETE never picks a key in flight, the pool never drains; the
plain key-space reference by hand; the ten readers on a hand-written
exposition, and None — never 0, never an exception — from a program without
the rows (an older commit under these benchmark files) and on a zero
denominator; the new steps of the comparison on drives, records and counters
made by hand; the traced rehearsal; and the four controls, each of which must
come out `correct: false` by the check named, at rehearsal size (PERF.md
gives the readings on the chip at the cell's own size)."""

import json
import os
import random
import sys
import threading
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE) if HERE not in sys.path else None
from harness import REPO, bench  # noqa: E402

sys.path.insert(0, REPO) if REPO not in sys.path else None
from chipbench import metrics, plugins, reference, reference_keyspace, traffic  # noqa: E402
from chipbench.procs import parse_metrics  # noqa: E402

CELL, CONFIG, MIX = "ec12p4-16d-warp.warp-mixed", "ec12p4-16d-warp", "warp-mixed"
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# reader -> its layer, in the order the entries stand
READERS = {
    "mixed_ops_per_s": "front end",
    "op_get_ms": "front end",
    "op_stat_ms": "front end",
    "op_put_ms": "front end",
    "op_delete_ms": "front end",
    "stat_meta_read_ms": "erasure read path",
    "delete_drive_ms": "server process",
    "dispatch_pad_block_share": "batching dispatcher",
    "trash_pending_per_delete": "server process",
    "trash_worker_cpu_s_per_gib": "server process",
}
DECK = {"GET": 9, "HEAD": 6, "PUT": 3, "DELETE": 2}


def config(name: str = CONFIG) -> dict:
    with open(os.path.join(REPO, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


# ---- the cell is what the issue names --------------------------------------


def test_the_cell_the_configuration_and_the_readers_are_held_by_name_and_order():
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    assert by_name[CELL] == {"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
                             "why": by_name[CELL]["why"]}
    # the object bytes moved: the cell's `why` says what the rate counts
    assert "s3_mib_s is GET + PUT bytes" in by_name[CELL]["why"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json" and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == ["clients", "drives_are_directories", "objects"]
    assert not any(w["chips"] == 4 for w in BENCH["workloads"]) and len(BENCH["workloads"]) <= 24
    per_layer = [m["name"] for m in BENCH["per_layer"]]
    first = per_layer.index("mixed_ops_per_s")
    assert per_layer[first:first + len(READERS)] == list(READERS)
    for m in BENCH["per_layer"][first:first + len(READERS)]:
        # each lists only the new cell
        assert m["workloads"] == [CELL] and m["moves"] == "s3_mib_s"
        assert m["source"] == "program_counter" and m["layer"] == READERS[m["name"]]
    from chipbench.run import metric_names

    # the rate and the set-up, no tail: `s3_p95_ms` lists its cells by name
    assert {m["name"] for m in metric_names(BENCH, "end_to_end", CELL)} == {"s3_mib_s", "setup_s"}
    mine = {m["name"] for m in metric_names(BENCH, "per_layer", CELL)}
    assert mine >= set(READERS) | {"server_cpu_s_per_gib", "window_compiles", "device_idle_share"}
    # no roofline reader: the encode is the PUT cells' kernels, no new one is added
    assert not {n for n in mine if n.endswith("_roofline")}
    # and no cell that was there reports a reader of this one
    for w in BENCH["workloads"]:
        if w["name"] != CELL:
            assert not set(READERS) & {m["name"] for m in
                                       metric_names(BENCH, "per_layer", w["name"])}


def test_warp_mixed_is_8_closed_loop_clients_on_256_objects_of_10_mib():
    mix = traffic.load_mix(MIX, rehearse=False)
    assert mix["generator"] == "closed_loop_mixed" and mix["unsigned_payload"] is True
    assert (mix["clients"], mix["object_mib"], mix["prepared_objects"],
            mix["distinct_bodies"]) == (8, 10, 256, 16)
    assert mix["deck"] == DECK and sum(DECK.values()) == 20
    assert [100 * n // 20 for n in DECK.values()] == [45, 30, 15, 10]  # warp's shares
    assert "rate" not in mix and "think_ms" not in mix  # a closed loop: no rate, no think time
    assert "progress" not in mix["warm"]  # the default: the loop's PUTs move the dispatcher
    assert mix["trace_s"] == 12 and mix["drives_room_gib"] == 16
    assert mix["checks"] == ["answers", "keyspace_live", "keyspace_deleted",
                             "ondrive_live_frames", "trash_reclaimed", "device_served",
                             "device_rung", "blocks_dispatched", "mix_as_stated"]
    assert mix["verify"] == {"live_keys": 32, "deleted_keys": 32, "ondrive_objects": 2,
                             "trash_drain_s": 10, "timeout_s": 60}
    put = traffic.load_mix("speedtest-put", rehearse=False)
    # the sibling's rungs as they stand: 1, 1 + 2, 1 + 4 PUTs, of 10 blocks here
    assert mix["ladder"] == put["ladder"]
    assert [1 << (g[-1] * mix["object_mib"] - 1).bit_length() for g in mix["ladder"]["rungs"]] \
        == [16, 32, 64]
    # and the crowd's: twelve PUTs behind four, of which any seven in one batch are bucket 128
    assert mix["crowd_rung"] == {"groups": [4, 12], "tries": 6}
    small = traffic.load_mix(MIX, rehearse=True)
    assert small["checks"] == mix["checks"] and small["deck"] == DECK
    assert small["crowd_rung"] is None
    assert (small["clients"], small["object_mib"], small["prepared_objects"]) == (2, 1, 16)
    assert small["verify"]["trash_drain_s"] <= 10
    with open(os.path.join(REPO, "chipbench", "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    # 1, 2 and 3 to 6 PUTs of 10 blocks at once, as powers of two
    assert cell["warm_buckets"] == [16, 32, 64] and "warp mixed" in cell["who"]


def test_the_deployment_is_the_default_set_holding_a_churned_bucket():
    cfg, base = config(), config("ec12p4-16d")
    dep = cfg["deployment"]
    for key, value in base["deployment"].items():
        assert dep[key] == value  # the sibling's set and shapes, every one
    assert cfg["server_env"] == {} == base["server_env"] and cfg["expects"] == base["expects"]
    assert cfg["architecture"] is None
    assert (dep["versioning"], dep["prepared_objects"], dep["object_bytes"]) \
        == ("off", 256, 10_485_760)
    assert dep["operations"] == {"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10}
    assert "uniform" in dep["key_choice"] and "closed loop" in dep["arrival"]
    g = cfg["guarantees"]
    for key, value in base["guarantees"].items():
        assert g[key] == value  # the sibling's five
    assert {"head_answers", "deleted_key", "trash_reclaimed", "delete_acknowledged"} <= set(g)
    assert "write quorum" in g["delete_acknowledged"] and "404" in g["deleted_key"]
    assert sorted(cfg["reduced"]) == ["clients", "drives_are_directories", "objects"]
    assert "2500" in cfg["reduced"]["objects"] and "256" in cfg["reduced"]["objects"]
    assert "20" in cfg["reduced"]["clients"] and "8" in cfg["reduced"]["clients"]
    assert {"warp_defaults", "upstream_trash", "fsync"} <= set(cfg["assumed"])
    # the traffic prepares what the configuration states
    mix = traffic.load_mix(MIX, rehearse=False)
    assert mix["prepared_objects"] == dep["prepared_objects"]
    assert mix["object_mib"] << 20 == dep["object_bytes"]
    assert {op: 5 * n for op, n in mix["deck"].items()} == {
        "HEAD" if op == "STAT" else op: n for op, n in dep["operations"].items()}


# ---- the generator ----------------------------------------------------------


def generator(seed: int, **over):
    mix = dict(traffic.load_mix(MIX, rehearse=False), **over)
    g = plugins.load("generators", mix["generator"]).Generator(mix, "x:1", "b", seed)
    g.config, g.drives = config(), [f"/x/d{i:02d}" for i in range(16)]
    return g


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_every_twenty_operations_of_a_client_are_9_6_3_2_on_every_seed(seed):
    g = generator(seed)
    hands = [g.hand(c, lap) for c in range(8) for lap in range(6)]
    for hand in hands:
        assert len(hand) == 20 and {op: hand.count(op) for op in DECK} == DECK
    assert len({tuple(h) for h in hands}) > 40  # shuffled: a client's laps and the clients differ
    assert g.hand(3, 2) == generator(seed).hand(3, 2)  # and made from the seed alone


def test_another_seed_deals_the_same_deck_in_another_order():
    a, b = generator(1), generator(2 ** 31 + 99)
    assert [a.hand(c, 0) for c in range(8)] != [b.hand(c, 0) for c in range(8)]
    assert (a.clients, a.object_bytes, a.prepared, a.deck) == (8, 10 << 20, 256, DECK) \
        == (b.clients, b.object_bytes, b.prepared, b.deck)
    assert a.setup_keys == [f"obj/{i:04d}" for i in range(256)] == b.setup_keys


def test_the_crowds_rung_is_climbed_until_its_bucket_shows_and_no_longer(monkeypatch):
    """Set-up, against stand-ins for the sibling's ladder and the server: the
    rung for bucket 128 is tried with the ladder's staggers in turn until the
    dispatcher has met the bucket, `tries` times at most, and not at all where
    the ladder already met it or the traffic file names none."""
    import minio_tpu.client as s3

    monkeypatch.setattr(s3, "S3Client", lambda endpoint: types.SimpleNamespace(
        admin=lambda *a, **kw: types.SimpleNamespace(status=200, body=b"")))

    def set_up(shows_after, **over):
        g = generator(3, object_mib=10, prepared_objects=8, **over)
        g.bodies, g.md5s = [b"a", b"b"], ["ma", "mb"]
        rungs, met = [], {16, 32, 64}
        g.ladder.warm_up = lambda seen, want: ([], [[16, 40], [32, 40], [64, 40]])

        def rung(groups, stagger_s, base):
            rungs.append((groups, round(stagger_s * 1e3), base))
            if len(rungs) >= shows_after:
                met.add(128)
            return [traffic.Request(i, "PUT", f"warm/{base:03d}-{i:02d}", i % 2, 0, 1, 200, True,
                                    10 << 20) for i in range(sum(groups))]

        g.ladder.rung = rung
        g.request = lambda c, op, key, body: traffic.Request(c, op, key, body, 1, 2, 200, True, 1)
        records, tries = g.warm_up(lambda: set(met), {16, 32, 64})
        return rungs, tries, records, g

    rungs, tries, records, g = set_up(shows_after=2)
    assert rungs == [([4, 12], 40, 200), ([4, 12], 15, 201)]  # the second try met it: no third
    assert tries == [[16, 40], [32, 40], [64, 40], [128, 40], [128, 15]]
    assert len(records) == 2 * 16 + 8 and len(g.pool) == 8  # the rungs' keys stay outside the pool
    assert {r.etag for r in records[:32]} == {"ma", "mb"}  # the ladder's records, as this one's
    rungs, tries, _, _ = set_up(shows_after=99)
    assert len(rungs) == 6 and [ms for _, ms, _ in rungs] == [40, 15, 100, 40, 15, 100]
    assert set_up(shows_after=0, crowd_rung=None)[0] == []  # none named: none climbed
    g = generator(3, prepared_objects=8)
    g.bodies, g.md5s = [b"a"], ["ma"]
    g.ladder.warm_up = lambda seen, want: ([], [])
    g.ladder.rung = lambda *a: pytest.fail("the bucket was there already")
    g.request = lambda c, op, key, body: traffic.Request(c, op, key, body, 1, 2, 200, True, 1)
    g.warm_up(lambda: {16, 32, 64, 128}, {16, 32, 64})


def test_a_deck_that_could_drain_the_pool_is_refused():
    with pytest.raises(Exception, match="more PUTs than DELETEs"):
        generator(1, deck={"GET": 9, "HEAD": 6, "PUT": 2, "DELETE": 3})
    with pytest.raises(Exception, match="the four operations"):
        generator(1, deck={"GET": 9, "PUT": 3, "DELETE": 2})


def test_a_delete_never_picks_a_key_in_flight_and_the_pool_never_drains():
    """The generator's own loop against a stand-in for the server: eight
    clients, thousands of operations, every request checked on arrival."""
    g = generator(11, object_mib=1, prepared_objects=32)
    g.bodies, g.md5s = [b"a", b"b"], ["ma", "mb"]
    mu = threading.Lock()
    live: dict[str, int] = {}     # what a server would hold
    in_flight: dict[str, int] = {}
    broke: list[str] = []
    smallest = [10 ** 9]

    def stand_in(client, op, key, body):
        with mu:
            if op == "PUT":
                if key in live:
                    broke.append(f"PUT to {key}, which exists")
            elif key not in live:
                broke.append(f"{op} of {key}, which does not exist")
            if op == "DELETE" and in_flight.get(key):
                broke.append(f"DELETE of {key} with {in_flight[key]} in flight")
            in_flight[key] = in_flight.get(key, 0) + 1
        if random.random() < 0.05:
            threading.Event().wait(0.001)  # let the others in
        with mu:
            in_flight[key] -= 1
            if op == "PUT":
                live[key] = body
            elif op == "DELETE":
                del live[key]
            smallest[0] = min(smallest[0], len(g.pool))
        return types.SimpleNamespace(client=client, op=op, key=key, body=body, status=200)

    g.request = stand_in
    for i, key in enumerate(g.setup_keys):
        live[key] = i % 2
        g.pool.add(key, i % 2)
    g.start()
    while sum(len(per) for per in g._records) < 4000:
        threading.Event().wait(0.01)
    g.stop()
    done = g.records()
    assert not broke, broke[:3]
    # the pool is what the server holds, and it grew: one object in twenty operations
    assert sorted(g.pool.keys) == sorted(live) and len(done) >= 4000
    assert smallest[0] >= 32 - 8 and len(live) >= 32 + len(done) // 20 - 8 * 3
    for c in range(8):  # every client did every kind, in whole decks
        ops = [r.op for r in g._records[c]]
        for lap in range(len(ops) // 20):
            assert {op: ops[20 * lap:20 * lap + 20].count(op) for op in DECK} == DECK
    # fresh PUT keys, one per client and count
    puts = [r.key for r in done if r.op == "PUT"]
    assert len(set(puts)) == len(puts) and all(k.startswith("c0") for k in puts)


def test_a_record_carries_200_where_the_answer_is_what_s3_states(monkeypatch):
    g = generator(5, object_mib=1)
    g.bodies, g.md5s = [b"x" * (1 << 20), b"y" * (1 << 20)], ["mx", "my"]
    answers = {}

    class Client:
        def __init__(self, endpoint):
            pass

        def request(self, op, path, body=b"", unsigned_payload=False, timeout=0):
            assert unsigned_payload is (op == "PUT")
            return answers[op]

    import minio_tpu.client as s3

    monkeypatch.setattr(s3, "S3Client", Client)
    resp = lambda status, etag="", body=b"", **h: types.SimpleNamespace(  # noqa: E731
        status=status, body=body, headers=dict(h, etag=f'"{etag}"'))
    answers.update(GET=resp(200, "mx", g.bodies[0]), PUT=resp(200, "mx"), DELETE=resp(204),
                   HEAD=resp(200, "mx", **{"content-length": str(1 << 20)}))
    got = {op: g.request(0, op, "k", 0) for op in DECK}
    assert all(r.status == 200 and r.ok for r in got.values())
    assert got["DELETE"].raw_status == 204 and got["GET"].raw_status == 200
    # the object bytes moved: GET and PUT carry them, HEAD and DELETE none
    assert {op: r.nbytes for op, r in got.items()} == {
        "GET": 1 << 20, "PUT": 1 << 20, "HEAD": 0, "DELETE": 0}
    assert (got["HEAD"].etag, got["HEAD"].length) == ("mx", 1 << 20) \
        == (got["GET"].etag, got["GET"].length) == (got["PUT"].etag, got["PUT"].length)
    # a HEAD that answers for another object: acknowledged, and wrong
    answers["HEAD"] = resp(200, "my", **{"content-length": str(1 << 20)})
    stale = g.request(0, "HEAD", "k", 0)
    assert stale.status == 200 and not stale.ok
    answers["HEAD"] = resp(200, "mx", **{"content-length": "17"})
    assert not g.request(0, "HEAD", "k", 0).ok
    # a GET answered 404: failed, under its own code; a DELETE answered 200 is not what S3
    # states, and 200 is the harness's word for acknowledged: recorded as 0, the code kept
    answers.update(DELETE=resp(200), GET=resp(404, body=b"<Error>NoSuchKey</Error>"))
    d, n = g.request(0, "DELETE", "k", 0), g.request(0, "GET", "k", 0)
    assert (d.status, d.raw_status, d.ok) == (0, 200, False) and "DELETE -> 200" in d.error
    assert (n.status, n.raw_status, n.nbytes, n.ok) == (404, 404, 0, False)
    assert "NoSuchKey" in n.error


# ---- the plain reference ----------------------------------------------------


def rec(op, key, done, status=200, etag="", length=-1, body=0, client=0):
    r = traffic.Request(client, op, key, body, done - 0.5, done, status, True, 0)
    r.etag, r.length = etag, length
    return r


def test_the_key_space_is_a_dictionary_replayed_in_order_of_acknowledgement():
    records = [
        rec("PUT", "a", 1, etag="m1", length=10), rec("PUT", "b", 2, etag="m2", length=20),
        rec("HEAD", "a", 3, etag="m1", length=10), rec("GET", "b", 4, etag="m2", length=20),
        rec("DELETE", "a", 5), rec("PUT", "c", 6, etag="m3", length=30),
        rec("PUT", "d", 7, status=503),             # never acknowledged: never there
        rec("DELETE", "b", 8, status=500),          # nor this: b stays
        rec("GET", "zz", 9, status=404),            # a failed read says nothing
    ]
    random.Random(3).shuffle(records)  # the order is the records' own, not the list's
    model, wrong = reference_keyspace.replay(records)
    assert model.objects == {"b": ("m2", 20), "c": ("m3", 30)} and model.deleted == {"a"}
    assert wrong == [] and model.head("a") is None and model.head("b") == ("m2", 20)


def test_an_answer_that_is_not_the_models_entry_at_that_moment_is_wrong():
    records = [
        rec("PUT", "a", 1, etag="m1", length=10),
        rec("HEAD", "a", 2, etag="m9", length=10),   # another object's ETag
        rec("HEAD", "a", 3, etag="m1", length=11),   # another size
        rec("DELETE", "a", 4),
        rec("GET", "a", 5, etag="m1", length=10),    # answered after its DELETE was acknowledged
        rec("HEAD", "never", 6, etag="m1", length=10),
        rec("PUT", "a", 7, etag="m2", length=12),    # and a key PUT again is there again
        rec("GET", "a", 8, etag="m2", length=12),
    ]
    model, wrong = reference_keyspace.replay(records)
    assert [r.done for r in wrong] == [2, 3, 5, 6]
    assert model.objects == {"a": ("m2", 12)} and model.deleted == set()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "chipbench", "reference_keyspace.py")) as f:
        text = f.read()
    assert "minio_tpu" not in text.replace("nothing of `minio_tpu`", "")
    assert "import" in text and "chipbench" not in text.split('"""')[2]


# ---- the readers ------------------------------------------------------------


def expo(calls=None, seconds=None, cpu=None, pad=0, blocks=0, pending=0, reclaimed_bytes=0):
    """The rows the readers read, zero unless given: {(layer, phase): value}."""
    rows = [("op", p) for p in ("get_object", "head_object", "put_object", "delete_object")] + [
        ("stat", "info"), ("stat", "meta_read"), ("delete", "drive_delete"), ("trash", "reclaim")]
    lines = [f'minio_tpu_dispatch_blocks_total{{class="foreground"}} {blocks}',
             'minio_tpu_dispatch_blocks_total{class="background"} 0',
             f"minio_tpu_dispatch_pad_blocks_total {pad}",
             f"minio_tpu_trash_pending {pending}",
             f"minio_tpu_trash_reclaimed_bytes_total {reclaimed_bytes}"]
    for layer, p in rows:
        for series, table in (("seconds", seconds), ("cpu_seconds", cpu), ("calls", calls)):
            lines.append(f'minio_tpu_phase_{series}_total{{layer="{layer}",phase="{p}"}} '
                         f'{(table or {}).get((layer, p), 0)}')
    return parse_metrics("\n".join(lines))


BEFORE = expo(calls={("op", "get_object"): 100, ("op", "delete_object"): 10,
                     ("stat", "meta_read"): 50},
              seconds={("op", "get_object"): 5.0, ("stat", "meta_read"): 1.0},
              cpu={("trash", "reclaim"): 0.5}, pad=60, blocks=100, pending=3,
              reclaimed_bytes=1 << 30)
AFTER = expo(calls={("op", "get_object"): 550, ("op", "head_object"): 300,
                    ("op", "put_object"): 150, ("op", "delete_object"): 110,
                    ("stat", "meta_read"): 250, ("delete", "drive_delete"): 100,
                    ("trash", "reclaim"): 1600},
             seconds={("op", "get_object"): 50.0, ("op", "head_object"): 1.5,
                      ("op", "put_object"): 30.0, ("op", "delete_object"): 2.0,
                      ("stat", "meta_read"): 2.0, ("delete", "drive_delete"): 1.5,
                      ("trash", "reclaim"): 4.0},
             cpu={("trash", "reclaim"): 1.5}, pad=960, blocks=1600, pending=19,
             reclaimed_bytes=3 << 30)
WANT = {
    "mixed_ops_per_s": 100.0,              # 450 + 300 + 150 + 100 operations in 10 s
    "op_get_ms": 100.0,                    # 45 s over 450 GETs
    "op_stat_ms": 5.0,
    "op_put_ms": 200.0,
    "op_delete_ms": 20.0,
    "stat_meta_read_ms": 5.0,              # 1 s over 200 quorum reads
    "delete_drive_ms": 15.0,
    "dispatch_pad_block_share": 37.5,      # 900 pad blocks beside 1500: every PUT alone in 16
    "trash_pending_per_delete": 0.16,      # 16 more entries pending after 100 DELETEs
    "trash_worker_cpu_s_per_gib": 0.5,     # 1 CPU second for 2 GiB
}


def window(before=BEFORE, after=AFTER, **kw):
    base = dict(seconds=10.0, acked_bytes=6 << 30, server_cpu_s=30.0, before=before, after=after,
                data_shards=12, parity_shards=4, device_kind="TPU v5 lite")
    base.update(kw)
    return metrics.Window(**base)


def test_every_reader_of_the_cell_has_a_value_by_hand():
    assert sorted(WANT) == sorted(READERS) and len(READERS) == 10


@pytest.mark.parametrize("name", list(READERS))
def test_reader_reads_the_value(name):
    assert metrics.reader(name).read(window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [n for n in READERS if n != "dispatch_pad_block_share"])
def test_a_program_without_the_rows_reads_nothing_and_does_not_raise(name):
    """These files are laid over the parent's checkout too: it has the
    dispatcher's blocks and pad blocks (`dispatch_pad_block_share` reads
    there), the phase clock of PUT and GET, and none of the new rows."""
    old = parse_metrics(
        'minio_tpu_dispatch_blocks_total{class="foreground"} 5\n'
        "minio_tpu_dispatch_pad_blocks_total 3\n"
        'minio_tpu_phase_calls_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_phase_seconds_total{layer="put",phase="commit"} 3\n'
        'minio_tpu_phase_cpu_seconds_total{layer="put",phase="commit"} 3\n')
    newer = parse_metrics(
        'minio_tpu_dispatch_blocks_total{class="foreground"} 50\n'
        "minio_tpu_dispatch_pad_blocks_total 33\n"
        'minio_tpu_phase_calls_total{layer="put",phase="commit"} 13\n'
        'minio_tpu_phase_seconds_total{layer="put",phase="commit"} 13\n'
        'minio_tpu_phase_cpu_seconds_total{layer="put",phase="commit"} 13\n')
    assert metrics.reader(name).read(window(before=old, after=newer)) is None
    assert name not in metrics.read_all([name, "window_compiles"], window(before={}, after={}))


@pytest.mark.parametrize("name", [n for n in READERS if n != "mixed_ops_per_s"])
def test_a_zero_denominator_reads_nothing(name):
    """A window without a request, a dispatch or a reclaim: no mean, no share."""
    assert metrics.reader(name).read(window(before=AFTER, after=AFTER)) is None


def test_the_rate_of_an_idle_window_is_zero_and_of_no_window_nothing():
    assert metrics.reader("mixed_ops_per_s").read(window(before=AFTER, after=AFTER)) == 0.0
    assert metrics.reader("mixed_ops_per_s").read(window(seconds=0.0)) is None


def test_a_window_in_which_the_reclaimers_fell_behind_or_caught_up():
    read = metrics.reader("trash_pending_per_delete").read
    behind = expo(calls={("op", "delete_object"): 110}, pending=3 + 1600)
    assert read(window(after=behind)) == 16.0  # nothing reclaimed: one entry a drive
    caught_up = expo(calls={("op", "delete_object"): 110}, pending=0)
    assert read(window(after=caught_up)) == pytest.approx(-0.03)  # it may fall, too


# ---- the new steps, on drives, records and counters made by hand ------------


def verification(tmp_path, records, **mix):
    """What a step is given, over drive directories of this test."""
    drives = [str(tmp_path / f"d{i:02d}") for i in range(16)]
    for d in drives:
        os.makedirs(os.path.join(d, ".minio.sys", "trash"))
    body = bytes(range(256)) * 4096  # one stripe block
    gen = types.SimpleNamespace(sent=lambda r: (body, "md5"), deck=DECK, clients=2)
    from chipbench.verify import Verification

    v = Verification(srv=types.SimpleNamespace(drives=drives, port=0), cli=None, bucket="b",
                     records=list(records), window=(5, 20), gen=gen, config=config(),
                     mix={"verify": dict({"live_keys": 4, "deleted_keys": 4,
                                          "ondrive_objects": 2, "trash_drain_s": 0.3,
                                          "timeout_s": 5}, **mix), "object_mib": 1},
                     seed=7, before={}, after={}, platform="cpu")
    return v, drives, body


def put(key, done, n=1 << 20):
    r = rec("PUT", key, done, etag="md5", length=n)
    r.nbytes = n
    return r


def write_shards(drives, key, body):
    frames = reference.object_frames(body, 12, 4)
    for pos, drive in enumerate(drives):
        path = os.path.join(drive, "b", key, "uuid")
        os.makedirs(path)
        with open(os.path.join(path, "part.1"), "wb") as f:
            f.write(frames[pos])
        with open(os.path.join(drive, "b", key, "xl.meta"), "wb") as f:
            f.write(b"meta")


class Answers:
    """A stand-in for `S3Client`: {(op, key): (status, etag, length, body)}."""

    def __init__(self, table, default=(404, "", 0, b"")):
        self.table, self.default, self.asked = table, default, []

    def request(self, op, path, timeout=0):
        key = path.split("/", 2)[2]
        self.asked.append((op, key))
        status, etag, length, body = self.table.get((op, key), self.default)
        return types.SimpleNamespace(status=status, body=body if op == "GET" else b"", headers={
            "etag": f'"{etag}"', "content-length": str(length)})


def test_keyspace_live_asks_a_sample_of_the_keys_the_model_holds(tmp_path):
    step = plugins.load("checks", "keyspace_live")
    records = [put(f"obj/{i:04d}", 1 + i * 0.1) for i in range(6)] + [
        put("c00/000000", 10), rec("DELETE", "obj/0000", 11), rec("DELETE", "obj/0001", 12)]
    v, _, body = verification(tmp_path, records)
    good = {(op, k): (200, "md5", 1 << 20, body)
            for op in ("GET", "HEAD") for k in ("obj/0002", "obj/0003", "obj/0004", "obj/0005",
                                                "c00/000000")}
    v.cli = Answers(good)
    assert step.run(v) == {"keyspace_live_wrong": (0, 0), "keyspace_answers_wrong": (0, 0)}
    assert v.details["live_keys_at_rest"] == 5 and v.details["live_keys_asked"] == 4
    asked = {k for _, k in v.cli.asked}
    assert len(asked) == 4 and not asked & {"obj/0000", "obj/0001"}  # never a deleted key
    assert {op for op, _ in v.cli.asked} == {"GET", "HEAD"}
    # a HEAD with another size, a GET with other bytes, a key that is gone
    v.cli = Answers({**good, ("HEAD", "obj/0002"): (200, "md5", 5, b""),
                     ("GET", "obj/0003"): (200, "md5", 1 << 20, body[::-1]),
                     ("GET", "obj/0004"): (404, "", 0, b""),
                     ("HEAD", "obj/0005"): (200, "other", 1 << 20, b""),
                     ("GET", "c00/000000"): (404, "", 0, b"")})
    assert step.run(v)["keyspace_live_wrong"] == (4, 0)  # each of the 4 asked is wrong
    # and the replay's own count: a HEAD the run acknowledged with another ETag
    v.records.append(rec("HEAD", "obj/0002", 13, etag="stale", length=1 << 20))
    v.cli = Answers(good)
    assert step.run(v)["keyspace_answers_wrong"] == (1, 0)


def test_keyspace_deleted_wants_404_and_no_file_left_on_any_drive(tmp_path):
    step = plugins.load("checks", "keyspace_deleted")
    records = [put(f"obj/{i:04d}", 1 + i * 0.1) for i in range(6)] + [
        rec("DELETE", f"obj/{i:04d}", 10 + i) for i in range(3)]
    v, drives, body = verification(tmp_path, records)
    write_shards(drives, "obj/0005", body)  # a live key's files are nobody's business
    v.cli = Answers({})
    assert step.run(v) == {"keyspace_deleted_answering": (0, 0),
                           "keyspace_deleted_files_left": (0, 0)}
    assert v.details["deleted_keys_at_rest"] == 3 == v.details["deleted_keys_asked"]
    assert sorted(set(v.cli.asked)) == sorted((op, f"obj/{i:04d}") for op in ("GET", "HEAD")
                                              for i in range(3))
    # a DELETE that removed nothing: the key answers, its 16 shard files and 16 xl.meta lie there
    write_shards(drives, "obj/0001", body)
    v.cli = Answers({("GET", "obj/0001"): (200, "md5", 1 << 20, body),
                     ("HEAD", "obj/0002"): (200, "md5", 1 << 20, b"")})
    got = step.run(v)
    assert got["keyspace_deleted_answering"] == (2, 0)
    assert got["keyspace_deleted_files_left"] == (32, 0)


def test_ondrive_live_frames_gives_the_step_the_keys_that_are_still_there(tmp_path):
    step = plugins.load("checks", "ondrive_live_frames")
    records = [put("obj/0000", 1), put("c00/000000", 10), put("c00/000001", 11),
               put("c01/000000", 12), rec("DELETE", "c00/000000", 13), rec("DELETE", "obj/0000", 14)]
    v, drives, body = verification(tmp_path, records)
    for key in ("c00/000001", "c01/000000"):
        write_shards(drives, key, body)
    # the deleted keys have no file: the step as it stands would count their 16 shards missing
    assert step.run(v) == {"ondrive_shards_wrong": (0, 0)}
    assert v.details["ondrive_shards_compared"] == 32 and len(v.last) == 4  # the run's own untouched
    with open(os.path.join(drives[5], "b", "c01/000000", "uuid", "part.1"), "r+b") as f:
        f.seek(40)
        f.write(b"\x00\x01")
    assert step.run(v) == {"ondrive_shards_wrong": (1, 0)}


@pytest.mark.parametrize("moved,reclaimed,entries,want", [
    (32, 32, 0, (0, 0, 0)),          # two DELETEs, 16 drives each, all gone
    (32, 20, 12, (12, 12, 0)),       # the reclaimers fell behind, or never ran
    (16, 16, 0, (0, 0, 16)),         # a DELETE that moved nothing aside
    (None, None, 32, (32, 0, 32)),   # a program without the counters: all unaccounted for
])
def test_trash_reclaimed_reads_the_drives_and_the_counters(tmp_path, moved, reclaimed,
                                                           entries, want):
    step = plugins.load("checks", "trash_reclaimed")
    records = [put("obj/0000", 1), put("obj/0001", 2), rec("DELETE", "obj/0000", 10),
               rec("DELETE", "obj/0001", 11), rec("DELETE", "obj/0002", 12, status=503)]
    v, drives, _ = verification(tmp_path, records)
    for i in range(entries):
        os.makedirs(os.path.join(drives[i % 16], ".minio.sys", "trash", f"entry-{i}"))
    rows = "" if moved is None else (f"minio_tpu_trash_moved_total {moved}\n"
                                     f"minio_tpu_trash_reclaimed_total {reclaimed}\n")
    step.scrape = lambda port, group: parse_metrics(rows)
    got = step.run(v)
    assert (got["trash_entries_left"][0], got["trash_moved_not_reclaimed"][0],
            got["trash_moved_not_as_deleted"][0]) == want
    assert all(limit == 0 for _, limit in got.values())
    assert v.details["deletes_since_boot"] == 2
    # it waits for the reclaimers only as long as there is something to wait for
    assert (v.details["trash_drained_after_s"] < 0.2) == (want[:2] == (0, 0))


def ops(counts, done=10.0):
    return [rec(op, f"k{i}", done, etag="md5", length=1) for op, n in counts.items()
            for i in range(n)]


@pytest.mark.parametrize("counts,want", [
    ({"GET": 450, "HEAD": 300, "PUT": 150, "DELETE": 100}, 0),   # the shares, exactly
    ({"GET": 459, "HEAD": 294, "PUT": 153, "DELETE": 102}, 0),   # cut decks at the edges
    # a server that starved DELETE: of 900, 405 / 270 / 135 / 90 are expected; off by 45, 30,
    # 15, 90, less the room of 2 clients (19.8, 16.8, 10.2, 7.2), rounded up: 26 + 14 + 5 + 83
    ({"GET": 450, "HEAD": 300, "PUT": 150, "DELETE": 0}, 128),
    # another mix altogether: of 1000, off by 150, 0, 100, 50: 131 + 0 + 90 + 43
    ({"GET": 600, "HEAD": 300, "PUT": 50, "DELETE": 50}, 264),
])
def test_mix_as_stated_allows_the_decks_the_window_cut_and_no_more(tmp_path, counts, want):
    step = plugins.load("checks", "mix_as_stated")
    v, _, _ = verification(tmp_path, ops(counts) + ops({"GET": 50}, done=30.0))  # after the window
    got = step.run(v)
    assert got == {"mix_not_as_stated": (want, 0)}
    assert v.details["window_operations"] == counts
    assert v.details["window_first_calls"] is None  # a program without the counter
    first = 'minio_tpu_dispatch_first_calls_total{rung="xla",bucket="16"} '
    v.before, v.after = parse_metrics(first + "1"), parse_metrics(first + "3")
    step.run(v)
    assert v.details["window_first_calls"] == 2


def test_mix_as_stated_room_is_what_two_cut_decks_a_client_can_hold():
    """A kind with n of 20 cards is off its share by at most n (1 - n / 20) in
    one cut deck: all n of them in its first n cards, or none in 20 - n."""
    step = plugins.load("checks", "mix_as_stated")

    def off(deletes):
        v = types.SimpleNamespace(window=(5, 20), records=ops({"DELETE": deletes}), details={},
                                  before={}, after={},
                                  gen=types.SimpleNamespace(deck=DECK, clients=2))
        return step.run(v)["mix_not_as_stated"][0]

    # 2 clients, 4 cut decks, each nothing but its 2 DELETEs: 8 of 8 operations where 0.8
    # are expected is off by 7.2 = 4 x 2 x (1 - 2 / 20), the room exactly; a ninth is not
    assert 2 * 2 * 2 * (1 - 2 / 20) == pytest.approx(7.2)
    assert off(8) == 0 and off(9) == 1


# ---- the cell, traced, at rehearsal size ------------------------------------


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench-jax-cache")


def test_traced_rehearsal_reads_every_reader_of_the_cell(cache):
    r, last = bench(cache, "--workload", CELL, "--seed", str(2 ** 31 + 79), "--seconds", "2",
                    "--trace", "1", "--rehearse")
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    m = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(READERS) <= set(m) and m["window_compiles"] == 0
    assert m["mixed_ops_per_s"] > 0 and m["stat_meta_read_ms"] > 0 and m["delete_drive_ms"] > 0
    assert m["op_put_ms"] > m["op_stat_ms"] > 0 and m["op_get_ms"] > 0 and m["op_delete_ms"] > 0
    assert 0 <= m["dispatch_pad_block_share"] < 100 and m["trash_worker_cpu_s_per_gib"] > 0
    assert abs(m["trash_pending_per_delete"]) <= 16
    d = last["details"]
    assert d["live_keys_asked"] == 8 == d["deleted_keys_asked"]
    assert d["ondrive_shards_compared"] == 32
    assert d["trash_moved_since_boot"] == d["trash_reclaimed_since_boot"] \
        == 16 * d["deletes_since_boot"] > 0
    assert d["dispatcher_blocks_since_boot"] >= d["put_blocks_since_boot"] > 0
    window_ops = d["window_operations"]
    assert set(window_ops) == set(DECK) and sum(window_ops.values()) == last["attempted"]
    assert all(c["limit"] == 0 for c in last["checks"].values())  # every step is exact


# ---- the controls -----------------------------------------------------------

CASES = [
    # (fault, the checks that have to read above their limit, whether every request succeeds)
    ("delete-noop", ("keyspace_deleted_answering", "keyspace_deleted_files_left",
                     "trash_moved_not_as_deleted"), True),
    ("stale-head", ("answers_wrong", "keyspace_answers_wrong"), True),
    ("trash-kept", ("trash_entries_left", "trash_moved_not_reclaimed"), True),
    ("put-lost", ("keyspace_live_wrong",), False),
]


@pytest.mark.parametrize("fault,caught_by,all_acknowledged", CASES, ids=[c[0] for c in CASES])
def test_a_broken_guarantee_is_not_correct(fault, caught_by, all_acknowledged, cache):
    r, last = bench(cache, "--workload", CELL, "--seed", "21", "--seconds", "1", "--trace", "0",
                    "--rehearse", "--launcher", "tests.chipbench.broken_mixed_serve",
                    CHIPBENCH_FAULT=fault)
    assert r.returncode == 0 and last is not None, r.stderr[-3000:]
    assert last["correct"] is False
    checks = {k: c["value"] for k, c in last["checks"].items()}
    for name in caught_by:
        assert checks[name] > 0, checks
        assert f"chipbench check {name}:" in r.stderr
    assert "NOT CORRECT" in r.stderr
    assert (last["failed"] == 0) == all_acknowledged
    if fault == "trash-kept":
        # the temptation: every answer is right, only the drives fill
        assert {k for k, v in checks.items() if v} == set(caught_by)
        d = last["details"]
        assert d["trash_reclaimed_since_boot"] == 0 < d["trash_moved_since_boot"]
    if fault == "delete-noop":
        assert last["details"]["trash_moved_since_boot"] == 0 and checks["answers_wrong"] == 0
    if fault == "stale-head":
        # nothing on the drives is wrong, and nothing about the trash
        assert checks["ondrive_shards_wrong"] == checks["trash_entries_left"] == 0

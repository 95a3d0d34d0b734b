"""One run of one cell:

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in `setup_s`): native build check, server child, the
generator's bodies from the seed, `make_bucket` 200, the device named by the
child, the generator's warm-up (staged rungs that put every batch bucket the
cell lists through the device, see `generators/`), then the cell's own client
loop until `quiet_s` seconds have passed with no new batch bucket, no new
compiled program, and the loop still making progress. The window is
`--seconds` of that same loop by the clock: nothing starts or stops at its
edges, so there is no ramp inside it. Then the clients finish what they
have in flight, the comparison that decides `correct` runs, the server
stops, and (with `--trace 1`) a child reduces the trace.

The last line of stdout is the result; without a TPU (and without
`--rehearse`) there is none and the exit code is not 0. `--rehearse` is the
CPU rehearsal at tiny size: it prints `"platform": "cpu"` and never a
device metric. `BENCH_RUN` in the environment is not read.

What the generator a traffic file names receives: `Generator(mix, endpoint,
bucket, seed)`, then, before `prepare()`, two attributes — `gen.config`, the
content of the cell's `configs/<config>.json`, and `gen.drives`, the server's
drive directories in the order of its command line — which is what a check
is given too. A deployment's state (drives offline, say) is therefore stated
once, in the configuration's file, and enacted by the generator in its
set-up. `warm.progress` in the traffic file names the counter of `/api/tpu`
(or of the metrics-v3 group `warm.progress_group`) whose advance says the own
loop is getting work done; unset, it is `minio_tpu_dispatch_total`, which a
loop of PUTs moves and a loop of GETs from healthy drives never does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

T_PROCESS = time.monotonic()

from . import metrics as metrics_mod  # noqa: E402
from . import plugins  # noqa: E402
from . import traffic as traffic_mod  # noqa: E402
from . import verify as verify_mod  # noqa: E402
from .procs import (ROOT, BenchFailure, Server, check, check_room, child_env,  # noqa: E402
                    drives_root, ensure_native, note, scrape, total)

HERE = os.path.dirname(os.path.abspath(__file__))
BUCKETS = "minio_tpu_dispatch_bucket_blocks_distribution"
PROGRESS = "minio_tpu_dispatch_total"


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell file, configuration file, traffic file), each found
    by the name `BENCHMARK.json` gives."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    check(entry is not None, f"workload {name!r} is not in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell_path = os.path.join(HERE, "workloads", f"{name}.json")
    cell = load_json(cell_path) if os.path.exists(cell_path) else {}
    return (bench, {**cell, **entry}, load_json(ROOT, cfg["file"]),
            traffic_mod.load_mix(entry["traffic"], rehearse))


def buckets_seen(series: dict) -> set[int]:
    prev, seen = 0.0, set()
    for labels, v in series.get(BUCKETS, []):
        if v > prev and labels.get("le") != "+Inf":
            seen.add(int(float(labels["le"])))
        prev = v
    return seen


def metric_names(bench: dict, group: str, cell: str) -> list[dict]:
    """The metrics of a group that this cell reports: those that list it, and
    those that list no cells."""
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def make_generator(mix: dict, endpoint: str, bucket: str, seed: int, config: dict,
                   drives: list[str]):
    """The generator the traffic file names, built with the four arguments
    every generator takes and then given what a check is given: the
    configuration file's content and the drive directories. `prepare()` has
    not run yet, so a deployment's state that the configuration states (drives
    offline, say) can be enacted by the generator in its set-up."""
    gen = plugins.load("generators", mix["generator"]).Generator(mix, endpoint, bucket, seed)
    gen.config, gen.drives = config, drives
    return gen


def run(args) -> int:
    check(os.path.isdir(os.path.join(ROOT, "minio_tpu")),
          "minio_tpu/ is not beside chipbench/: run from a checkout of the repository")
    bench, cell, config, mix = load_cell(args.workload, args.rehearse)
    dep = config["deployment"]
    native_s = ensure_native()
    from minio_tpu.client import S3Client

    # everything the run writes goes under this one directory in TMPDIR
    root = tempfile.mkdtemp(prefix="chipbench-")

    def cleanup():
        shutil.rmtree(root, ignore_errors=True)

    try:
        droot, medium = drives_root(root)
        srv = Server(root, droot, child_env(args.rehearse), dep["drives"], config["server_env"],
                     args.launcher.split())
    except BaseException:
        cleanup()
        raise
    try:
        t_boot = time.monotonic()
        endpoint, bucket = f"127.0.0.1:{srv.port}", "chipbench"
        gen = make_generator(mix, endpoint, bucket, args.seed, config, srv.drives)
        gen.prepare()
        bodies_s = time.monotonic() - t_boot
        cli = S3Client(endpoint)
        srv.wait_ready(cli, bucket)
        boot_s = time.monotonic() - t_boot
        dev = srv.ask("device")
        device = {"platform": dev["platform"], "kind": dev["kind"], "count": int(dev["count"])}
        if not args.rehearse:
            if device["platform"] != "tpu" or device["count"] < cell["chips"]:
                print(f"chipbench: no TPU — the server child holds platform="
                      f"{device['platform']} kind={device['kind']!r} count={device['count']}, "
                      f"the cell needs {cell['chips']} TPU chip(s); this is not a chip run "
                      "(use --rehearse for the CPU rehearsal)", file=sys.stderr)
                srv.stop()
                cleanup()
                return 3
        check_room(root, int(mix["drives_room_gib"] * (1 << 30)))

        # -- warm-up: the generator's ladder, then the cell's own loop until quiet
        t_ladder = time.monotonic()
        want = set(cell.get("warm_buckets", [])) if not args.rehearse else set()
        early, tries = gen.warm_up(lambda: buckets_seen(scrape(srv.port, "/api/tpu")), want)
        srv.alive()
        bad = [r for r in early if r.status != 200]
        check(not bad, f"warm-up request failed: {bad[0].op} {bad[0].key} -> "
              f"{bad[0].status} {bad[0].error}" if bad else "")
        ladder_s = time.monotonic() - t_ladder
        gen.start()
        t_loop = time.monotonic()
        # quiet: no new bucket and no new compiled program for quiet_s, and two
        # dispatches finished since the last one: the histogram counts a bucket
        # when its dispatch STARTS, and its first dispatch may trace and lower
        # for many seconds, so a bucket seen is not yet a bucket warmed. Which
        # counter says "finished" is the mix's to name (`warm.progress`)
        progress = mix["warm"].get("progress", PROGRESS)
        group = mix["warm"].get("progress_group", "/api/tpu")
        last_change, state, done_then = t_loop, None, 0.0
        while True:
            time.sleep(0.5)
            srv.alive()
            s = scrape(srv.port, "/api/tpu")
            now = time.monotonic()
            new = (buckets_seen(s), total(s, "minio_tpu_compile_programs_total"))
            done = total(s if group == "/api/tpu" else scrape(srv.port, group), progress)
            if new != state:
                state, last_change, done_then = new, now, done
            quiet = now - last_change >= mix["warm"]["quiet_s"] and done >= done_then + 2
            if now - t_loop >= mix["warm"]["min_s"] and quiet and want <= state[0]:
                break
            if now - t_loop >= mix["warm"]["max_s"]:
                break
        warm_loop_s = time.monotonic() - t_loop
        note(phase="warm-up", ladder=tries, ladder_s=ladder_s, own_loop_s=warm_loop_s,
             buckets=sorted(state[0]), wanted=sorted(want))

        # -- the window
        before = scrape(srv.port, "/api/tpu")
        cpu0 = srv.cpu_seconds()
        t0 = time.monotonic()
        setup_s = t0 - T_PROCESS
        trace_dir = os.path.join(root, "trace")
        traced_before = None
        if args.trace:
            # the traced seconds END the window: stopping the profiler takes
            # seconds of the server's time, which must fall outside it
            time.sleep(max(0.0, args.seconds - mix["trace_s"]))
            srv.ask("trace-start", trace_dir)
            traced_before = scrape(srv.port, "/api/tpu")
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        t1 = time.monotonic()
        cpu1 = srv.cpu_seconds()
        after = scrape(srv.port, "/api/tpu")
        if args.trace:
            srv.ask("trace-stop", timeout=240)
        window_s = t1 - t0
        gen.stop()
        srv.alive()
        mem = srv.ask("memstats")

        records = early + gen.records()
        timed = [r for r in records if t0 <= r.done <= t1]
        acked = [r for r in timed if r.status == 200]
        failed = [r for r in timed if r.status != 200]
        check(acked, f"no request was acknowledged in the window "
              f"({len(failed)} failed: {failed[0].error if failed else ''})")
        acked_bytes = sum(r.nbytes for r in acked)
        lat_ms = [1e3 * (r.done - r.sent) for r in acked]
        note(phase="window", seconds=window_s, requests=len(timed), acknowledged=len(acked),
             failed=len(failed), first_error=failed[0].error if failed else None,
             p50_ms=traffic_mod.percentile(lat_ms, 0.50),
             p95_ms=traffic_mod.percentile(lat_ms, 0.95), max_ms=max(lat_ms),
             buckets_after=sorted(buckets_seen(after)))

        # -- correct? (outside the window and outside setup_s)
        t_verify = time.monotonic()
        checks, details = verify_mod.run_checks(verify_mod.Verification(
            srv=srv, cli=cli, bucket=bucket, records=records, window=(t0, t1), gen=gen,
            config=config, mix=mix, seed=args.seed, before=before, after=after,
            platform=device["platform"]))
        verify_s = time.monotonic() - t_verify
        srv.alive()
    except BaseException:
        print(f"--- server log tail ---\n{srv.log_tail()}\n--- end ---",
              file=sys.stderr, flush=True)
        srv.stop()
        cleanup()
        raise
    srv.stop()
    shutil.rmtree(droot, ignore_errors=True)
    del gen

    result: dict = {}
    try:
        reduction = None
        if args.trace:
            out = os.path.join(root, "trace.json")
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            r = subprocess.run([sys.executable, "-m", "chipbench.trace_reduce", trace_dir, out],
                               cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
            check(r.returncode == 0, f"trace reduction failed: {r.stderr[-2000:]}")
            reduction = load_json(out)
    finally:
        cleanup()

    e2e = {"s3_mib_s": acked_bytes / (1 << 20) / window_s,
           "s3_p95_ms": traffic_mod.percentile(lat_ms, 0.95),
           "setup_s": setup_s}
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer") for m in bench[g]}
    if args.trace:
        w = metrics_mod.Window(
            seconds=window_s, acked_bytes=acked_bytes, server_cpu_s=cpu1 - cpu0,
            before=before, after=after, data_shards=dep["data_shards"],
            parity_shards=dep["parity_shards"], device_kind=device["kind"],
            trace=reduction if device["platform"] == "tpu" else None,
            traced_before=traced_before)
        names = [m["name"] for m in metric_names(bench, "per_layer", args.workload)]
        values = metrics_mod.read_all(names, w)
    else:
        values = {m["name"]: e2e[m["name"]]
                  for m in metric_names(bench, "end_to_end", args.workload)}
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["attempted"] = len(timed)
    result["failed"] = len(failed)
    result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    result["device"] = dict(device, memory_peak_bytes=mem.get("memory_peak_bytes") or 0)
    if args.trace:
        traced = reduction["devices"] > 0 and device["platform"] == "tpu"
        result["device"]["busy_s"] = reduction["busy_s"] if traced else None
        result["device"]["window_s"] = reduction["window_s"]
        if traced:
            result["breakdown"] = {"device_ops": reduction["device_ops"],
                                   "idle_gaps": reduction["idle_gaps"]}
        result["end_to_end_traced"] = e2e
    result["phases_s"] = {"native": native_s, "bodies": bodies_s, "boot": boot_s,
                          "ladder": ladder_s, "warm_loop": warm_loop_s,
                          "verify": verify_s}
    result["drives_on"] = medium
    # what the run left on the drives until it ended: every PUT since boot
    details["drives_gib_written"] = (sum(r.nbytes for r in records if r.op == "PUT")
                                     * dep["drives"] / dep["data_shards"] / (1 << 30))
    result["details"] = details
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    print(json.dumps(result), flush=True)
    for k, (v, lim) in checks.items():
        print(f"chipbench check {k}: {v} (limit {lim}){'' if v <= lim else '  <-- NOT CORRECT'}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench", description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny size; never a chip result")
    ap.add_argument("--launcher", default="chipbench.serve", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    # ended from outside: still stop the child and free the drives' RAM
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        return run(args)
    except BenchFailure as e:
        print(f"chipbench FAILED: {e}", file=sys.stderr, flush=True)
        return 1

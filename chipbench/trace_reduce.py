"""From a profiler trace (`.xplane.pb`) to device busy time.

Run as a child of its own, after the server has stopped, with
JAX_PLATFORMS=cpu: reading a trace needs jax's `ProfileData` but no device,
and the harness parent never imports jax.

    python -m chipbench.trace_reduce <trace dir> <out.json>

Busy time is the union of the intervals of every event on each device
plane's operation line ("XLA Ops"): the device runs nothing but the codec,
so no kernel name is matched, and the number reads the same work whatever
implements it. `busy_s` is the mean over the device planes; the traced
interval is first event start to last event end over ALL planes (host
included), which is what the profiler was on for. `dispatches` counts the
device dispatches whose programs ran inside the trace: the runs, on the
"XLA Modules" line, of the jitted program that took most of the device's
time (its name without the fingerprint, so that a program compiled for
several batch sizes is one name) — a dispatch runs its main program once,
while a small one may run twice (12+4's `jit_reshape` does). A trace with
no device plane (a CPU rehearsal) gives `devices: 0` and no busy time.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
MIN_GAP_NS = 1_000_000  # between two operations of one program the device is not "idle"


def short(name: str) -> str:
    """An op's event name is its whole HLO text: keep the result's name."""
    return name.split(" = ", 1)[0][:80]


def union_ns(intervals: list[tuple[int, int]]) -> tuple[int, list[tuple[int, int]]]:
    """Total covered length and the merged intervals of [(start, end)]."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def reduce_planes(planes: list[dict]) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] -> the reduction. Pure, so a recorded fixture tests it."""
    lo, hi = None, None
    for pl in planes:
        for ln in pl["lines"]:
            for _, s, d in ln["events"]:
                lo = s if lo is None or s < lo else lo
                hi = s + d if hi is None or s + d > hi else hi
    out = {"devices": 0, "window_s": ((hi - lo) / 1e9) if lo is not None else 0.0,
           "busy_s": None, "dispatches": None, "device_ops": [], "idle_gaps": [],
           "planes": [{"name": pl["name"],
                       "lines": {ln["name"]: len(ln["events"]) for ln in pl["lines"]}}
                      for pl in planes]}
    dev = [pl for pl in planes if pl["name"].startswith(DEVICE_PREFIX)]
    if not dev or lo is None:
        return out
    busy, dispatches, per_op, per_module, gaps = [], [], {}, {}, []
    host = [(n, s, s + d) for pl in planes if not pl["name"].startswith(DEVICE_PREFIX)
            for ln in pl["lines"] for n, s, d in ln["events"]]
    for pl in dev:
        ops = [ln for ln in pl["lines"] if ln["name"] == OPS_LINE]
        ev = [e for ln in ops for e in ln["events"]]
        total, merged = union_ns([(s, s + d) for _, s, d in ev])
        busy.append(total / 1e9)
        for n, _, d in ev:
            per_op["op:" + short(n)] = per_op.get("op:" + short(n), 0) + d
        programs: dict = {}  # name without fingerprint -> [device ns, runs]
        for ln in pl["lines"]:
            if ln["name"] == MODULES_LINE:
                for n, _, d in ln["events"]:
                    per_module["module:" + short(n)] = per_module.get("module:" + short(n), 0) + d
                    row = programs.setdefault(n.split("(", 1)[0], [0, 0])
                    row[0] += d
                    row[1] += 1
        dispatches.append(max(programs.values(), default=[0, 0])[1])
        edges = [(lo, lo)] + merged + [(hi, hi)]
        gaps += [(edges[i][1], edges[i + 1][0]) for i in range(len(edges) - 1)
                 if edges[i + 1][0] - edges[i][1] >= MIN_GAP_NS]
    out["devices"] = len(dev)
    out["busy_s"] = sum(busy) / len(busy)
    out["dispatches"] = sum(dispatches) / len(dispatches)
    # the jitted programs that took most device time, then single operations
    def rank(table, k):
        return sorted(table.items(), key=lambda kv: -kv[1])[:k]

    mods = rank(per_module, TOP // 2)
    out["device_ops"] = [[n, d / 1e9] for n, d in mods + rank(per_op, TOP - len(mods))]
    # the longest idle gaps, each named by the host event that covers most
    # of it (the program writes no TraceAnnotation yet: the names are the
    # runtime's own)
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, cover = "no host event", 0
        for n, s, e in host:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = n, c
        out["idle_gaps"].append([short(best), (g1 - g0) / 1e9])
    return out


def load_planes(trace_dir: str) -> list[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for pl in ProfileData.from_file(paths[-1]).planes:
        lines = [{"name": ln.name,
                  "events": [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                             for ev in ln.events]}
                 for ln in pl.lines]
        planes.append({"name": pl.name, "lines": lines})
    return planes


def main(argv: list[str]) -> int:
    trace_dir, out = argv
    red = reduce_planes(load_planes(trace_dir))
    with open(out, "w") as f:
        json.dump(red, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""The launcher of the server child. It calls `minio_tpu.server.app.main`,
the function `python -m minio_tpu.server` runs, in its main thread, for
both values of `--trace`. A side thread answers the harness's commands,
one line on stdin each, `<verb> <answer-file> [words]`:

- `device`: the device JAX reports in this process (before any traffic,
  so a run without a TPU stops early);
- `trace-start <dir>` / `trace-stop`: `jax.profiler` around a few seconds
  of the window — only the process that holds the chip can trace it;
- `memstats`: peak bytes in use on the fullest device.

When stdin closes (the harness is gone) the server is told to stop.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading


def _answer(path: str, **row) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(row, f)
    os.replace(tmp, path)


def _do(verb: str, words: list[str]) -> dict:
    import jax

    if verb == "device":
        devs = jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}
    if verb == "trace-start":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the Python tracer makes traces huge
        opts.host_tracer_level = 2
        jax.profiler.start_trace(words[0], profiler_options=opts)
        return {}
    if verb == "trace-stop":
        jax.profiler.stop_trace()
        return {}
    if verb == "memstats":
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        return {"memory_peak_bytes": max(peaks) if peaks else None}
    raise ValueError(f"unknown verb {verb!r}")


def _commands() -> None:
    for line in sys.stdin:
        verb, out, *words = line.split()
        try:
            _answer(out, ok=True, **_do(verb, words))
        except Exception as e:  # noqa: BLE001 — reported to the harness, which fails the run
            _answer(out, ok=False, error=f"{type(e).__name__}: {e}")
    os.kill(os.getpid(), signal.SIGTERM)


def main(argv: list[str]) -> None:
    threading.Thread(target=_commands, name="chipbench-ctl", daemon=True).start()
    from minio_tpu.server.app import main as server_main

    server_main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])

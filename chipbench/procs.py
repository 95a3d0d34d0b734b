"""The server child and the ways the harness reads it, copied from
`chip_smoke.py` (PR 21, proven on the chip) so that the program may change
and the yardstick may not: `Server`, `scrape`, `total`, `child_env`,
`free_port`, and the native-library check. No jax here."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLK_TCK = os.sysconf("SC_CLK_TCK")


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result line."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def note(**row) -> None:
    """An earlier line on stdout: for the reader of a log, not the driver."""
    print(json.dumps(row), flush=True)


def ensure_native() -> float:
    """Build products are git-ignored, so a fresh checkout has no native
    library: importing `minio_tpu.native` builds it from the committed
    sources (g++ -O3 -mavx2), once per checkout. Both native planes must
    be there; a failed build would silently leave the pure-Python ones.
    Returns the seconds it took."""
    t0 = time.perf_counter()
    from minio_tpu import native

    check(native.available() and native.dataplane_available(),
          "native library did not build from the committed sources")
    return time.perf_counter() - t0


def child_env(rehearse: bool) -> dict:
    """The inherited environment without any MINIO_* routing (in particular
    no MINIO_TPU_BACKEND: the default must reach the device). The compile
    cache stays where JAX_COMPILATION_CACHE_DIR says, else the program puts
    it at <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("MINIO_")}
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        # the CPU rehearsal forces the device plane onto XLA's CPU backend
        env["MINIO_TPU_BACKEND"] = "jax"
    return env


def mount_type(path: str) -> str:
    """The file system type of the mount that holds `path`, from /proc/mounts."""
    path = os.path.realpath(path)
    best, kind = "", ""
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, where, fstype, *_ = line.split()
                if (path == where or path.startswith(where.rstrip("/") + "/")) \
                        and len(where) >= len(best):
                    best, kind = where, fstype
    except OSError:
        pass
    return kind


def drives_root(root: str) -> tuple[str, str]:
    """The directory of the drive directories, under the run's own directory
    in TMPDIR and nowhere else, and the medium it is on (`<fstype>:<TMPDIR>`)."""
    path = os.path.join(root, "drives")
    os.makedirs(path)
    return path, f"{mount_type(root) or 'unknown'}:{tempfile.gettempdir()}"


def check_room(root: str, room_bytes: int) -> None:
    """The run keeps every object it PUTs until it ends, so TMPDIR must have
    `room_bytes` free: a run that would fill it fails here, with no result."""
    st = os.statvfs(root)
    free = st.f_bavail * st.f_frsize
    check(free >= room_bytes,
          f"TMPDIR ({tempfile.gettempdir()}) has {free >> 30} GiB free; the run keeps what it "
          f"PUTs on the drives until it ends and needs room for {room_bytes >> 30} GiB")


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
    return parse_metrics(body)


def parse_metrics(body: str) -> dict:
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
    """The served program as one child: `python -m chipbench.serve` (or the
    launcher module a test names), which calls `minio_tpu.server.app.main` — what
    `python -m minio_tpu.server` runs — over `drives` directories."""

    def __init__(self, root: str, drives_root: str, env: dict, drives: int,
                 server_env: dict, launcher: list[str]):
        self.root = root
        self.port = free_port()
        self.drives = [os.path.join(drives_root, f"d{i:02d}") for i in range(drives)]
        env = dict(env)
        env["MINIO_TPU_SCAN_INTERVAL"] = "0"
        env["MINIO_PROMETHEUS_AUTH_TYPE"] = "public"
        env.update(server_env)
        self.ctl = os.path.join(root, "ctl")
        os.makedirs(self.ctl)
        self.log_path = os.path.join(root, "server.log")
        self._log = open(self.log_path, "wb")
        self._seq = 0
        check("jax" not in sys.modules, "the harness imported jax before the child")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", *launcher,
             "--address", f"127.0.0.1:{self.port}", *self.drives],
            cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
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
            time.sleep(0.1)
        raise BenchFailure(f"make_bucket({bucket}) never answered 200 (last {status})")

    def ask(self, verb: str, *words: str, timeout: float = 120.0) -> dict:
        """One command to the launcher's side thread (a line on its stdin);
        the answer is a JSON file it renames into place."""
        self._seq += 1
        out = os.path.join(self.ctl, f"{self._seq:03d}-{verb}.json")
        self.proc.stdin.write((" ".join([verb, out, *words]) + "\n").encode())
        self.proc.stdin.flush()
        deadline = time.monotonic() + timeout
        while not os.path.exists(out):
            self.alive()
            check(time.monotonic() < deadline, f"launcher never answered {verb}")
            time.sleep(0.02)
        with open(out) as f:
            ans = json.load(f)
        check(ans.get("ok"), f"launcher {verb}: {ans.get('error')}")
        return ans

    def cpu_seconds(self) -> float:
        """utime + stime of the server process (all its threads)."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rpartition(")")[2].split()
        return (int(fields[11]) + int(fields[12])) / CLK_TCK

    def log_tail(self, nbytes: int = 6000) -> str:
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
        if self.proc.stdin:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
        self._log.close()

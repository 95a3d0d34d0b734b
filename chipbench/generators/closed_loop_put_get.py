"""The closed-loop PUT + GET generator: ingest and read-back at once, what a
backup that verifies, or a data lake that is written and queried, sends.
`put_clients` threads PUT whole objects to fresh keys (`c<cc>/<iiiiii>`) back
to back and `get_clients` threads GET whole set-up objects back to back, all
of `object_mib`, in one window. It is the two generators that are there,
side by side: the PUT side IS `closed_loop_put` (its keys, its walk over the
bodies, its ladder) and the GET side IS `closed_loop_get` (its set-up PUTs
`obj/0000`..., the configuration's `offline_drives` taken offline by the
storage fault rule and the cache cleared once, GET client c's i-th GET being
object (seed + 7c + i) mod `objects`, every body and ETag compared), each
loaded by name and given its share of the traffic file. Both sides use the
same `objects` seeded bodies (`distinct_bodies` = `objects`), made once.

One traffic file serves a healthy and a degraded deployment: the state is
the configuration's. A configuration that names no `offline_drives` has
none: no fault rule is set, the cache is still cleared once, and every GET
rides the healthy path.

Set-up, all inside `warm_up` and so inside `setup_s`, in this order: the
set-up PUTs (every object gets all its shard files), the drives offline and
the cache cleared, one GET alone and then the GET clients' own loop until
every object has been read and the counter `warm.first_calls` names has stood
still for `warm.quiet_s` (`closed_loop_get.warm_up`, as it stands); then the
PUT ladder for the batch buckets the cell's workload file names
(`closed_loop_put.warm_up`) — WITH the drives offline, so that the drive
breakers open and the PUT path meets its state before the window, not in it.
The harness then runs the whole loop, PUTs and GETs together, until quiet.

Every seed gives the same sizes, the same offline drives, the same keys and
the same count of GETs per object to within one lap; the seed turns the bytes
and the order in which a client walks the bodies, never the work.

What it receives (`chipbench/run.py`): the traffic file, the endpoint, the
bucket and the seed; then, before `prepare()`, `config` and `drives`. What
the checks read of it: `sent(record)`, `bodies`, `md5s`, `offline`,
`offline_files` (as `closed_loop_get` gives them) and `setup_keys`, the keys
of the set-up objects. A PUT's record carries `op == "PUT"`, a GET's `"GET"`;
`client` counts each side from 0. Parameters, all from the traffic file:
`put_clients`, `get_clients`, `object_mib`, `objects`, `distinct_bodies`,
`unsigned_payload`, `ladder`, `warm`. No jax, no numpy beyond body generation.
"""

from __future__ import annotations

from chipbench import plugins
from chipbench.procs import check
from chipbench.traffic import Request


class Generator:
    """`prepare()`, `warm_up()`, `start()` once each; `records()` grows until
    `stop()`."""

    def __init__(self, spec: dict, endpoint: str, bucket: str, seed: int, timeout: float = 300.0):
        check(spec["distinct_bodies"] == spec["objects"],
              "put-get: the PUT side walks the set-up objects' bodies: distinct_bodies = objects")
        self.put_clients, self.get_clients = spec["put_clients"], spec["get_clients"]
        self.objects = spec["objects"]
        self.put = plugins.load("generators", "closed_loop_put").Generator(
            dict(spec, clients=self.put_clients), endpoint, bucket, seed, timeout)
        self.get = plugins.load("generators", "closed_loop_get").Generator(
            dict(spec, clients=self.get_clients), endpoint, bucket, seed, timeout)
        self.config: dict | None = None
        self.drives: list[str] | None = None

    def prepare(self) -> None:
        check(self.config is not None and self.drives is not None,
              "the harness gave the generator no configuration or drives")
        dep = self.config["deployment"]
        # a deployment that names no offline drives has none
        self.get.config = dict(self.config, deployment=dict(
            dep, offline_drives=dep.get("offline_drives", [])))
        self.get.drives = self.drives
        self.get.prepare()
        # one set of bodies: a PUT client walks the set-up objects' bodies
        self.put.bodies, self.put.md5s = self.get.bodies, self.get.md5s

    # -- what the checks read

    @property
    def bodies(self) -> list[bytes]:
        return self.get.bodies

    @property
    def md5s(self) -> list[str]:
        return self.get.md5s

    @property
    def offline(self) -> list[int]:
        return self.get.offline

    @property
    def offline_files(self) -> dict:
        return self.get.offline_files

    @property
    def setup_keys(self) -> list[str]:
        return [self.get.key(obj) for obj in range(self.objects)]

    def sent(self, r: Request) -> tuple[bytes, str]:
        """The body PUT under a record's key and its md5, on either side."""
        return self.get.sent(r)

    # -- set-up and the loop

    def warm_up(self, seen, want: set[int]) -> tuple[list[Request], list]:
        records, _ = self.get.warm_up(seen, want)
        if any(r.status != 200 for r in records):
            return records, []
        ladder, tries = self.put.warm_up(seen, want)
        return records + ladder, tries

    def start(self) -> None:
        self.put.start()
        self.get.start()

    def stop(self) -> None:
        """Every client of both sides finishes the request it has in flight."""
        self.put._stop.set()
        self.get._stop.set()
        self.put.stop()
        self.get.stop()

    def records(self) -> list[Request]:
        return self.put.records() + self.get.records()

"""Per-layer metrics: one small reader per metric, `metrics/<name>.py`,
found by the name in `BENCHMARK.json`. A reader is `read(w) -> float | None`
over a `Window`; one that finds nothing to read returns None and the
harness leaves the metric out of the line. It never returns 0 for a share
of a roofline."""

from __future__ import annotations

from dataclasses import dataclass

from chipbench import plugins


@dataclass
class Window:
    """What the readers may read. `before`/`after` are the `/api/tpu`
    scrapes at the window's ends ({series: [(labels, value)]}); `trace` is
    `trace_reduce`'s reading of the traced interval, which ends the window,
    and `traced_before` the scrape made when it began; both None in a run
    without a device trace."""

    seconds: float
    acked_bytes: int
    server_cpu_s: float
    before: dict
    after: dict
    data_shards: int
    parity_shards: int
    device_kind: str
    trace: dict | None = None
    traced_before: dict | None = None

    @staticmethod
    def _sum(series: dict, name: str, **match) -> float:
        return sum(v for labels, v in series.get(name, [])
                   if all(labels.get(k) == w for k, w in match.items()))

    def delta(self, name: str, **match) -> float:
        return self._sum(self.after, name, **match) - self._sum(self.before, name, **match)

    def traced_delta(self, name: str, **match) -> float | None:
        """The counter's move over the traced interval."""
        if self.traced_before is None:
            return None
        return self._sum(self.after, name, **match) - self._sum(self.traced_before, name, **match)

    def histogram(self, series: dict, name: str) -> dict[str, float]:
        """Cumulative `le` rows -> {edge: count in that bucket}."""
        rows = series.get(name, [])
        out, prev = {}, 0.0
        for labels, v in rows:
            out[labels.get("le", "")] = v - prev
            prev = v
        return out


def reader(name: str):
    return plugins.load("metrics", name)


def read_all(names: list[str], w: Window) -> dict[str, float]:
    out = {}
    for name in names:
        v = reader(name).read(w)
        if v is not None:
            out[name] = float(v)
    return out

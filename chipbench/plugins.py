"""Files found by the name the data gives: `metrics/<name>.py` (one reader
per per-layer metric, named in `BENCHMARK.json`), `generators/<name>.py`
(the load generator a traffic file names) and `checks/<name>.py` (the steps
of the comparison a traffic file lists). A later PR adds a file and an
entry; nothing here changes. What each receives: a reader `read(w)` a
`metrics.Window`; a check `run(v)` a `verify.Verification`; a generator the
traffic file, the endpoint, the bucket and the seed, and then, as the
attributes `config` and `drives`, the configuration file's content and the
drive directories that a check finds under `v.config` and `v.srv.drives`
(`traffic.py` has the whole of what the harness calls)."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}: {name!r} has no file at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

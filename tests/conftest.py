"""Test configuration: the default lane pins JAX to the CPU with 8 virtual
devices — multi-chip sharding is validated there (same XLA partitioner,
no ICI), and it is what the driver's tier-1 command runs
(`JAX_PLATFORMS=cpu`, several xdist workers).

TPU lane: `MINIO_TPU_TEST_TPU=1 python -m pytest tests -m tpu -p no:xdist`
keeps the real backend so the Pallas kernel tests run on hardware —
kernel regressions fail tests, not just benches (VERDICT r2 weak #2). ONE
process: a chip belongs to one process at a time, so the lane never runs
under xdist and its tests start no child that needs the device. The
default (CPU) lane skips those tests via their backend guards.
"""

import os

import pytest

TPU_LANE = os.environ.get("MINIO_TPU_TEST_TPU") == "1"

# The optional `cryptography` dependency gates SSE / admin-wire
# encryption (minio_tpu/crypto/sse.py raises a typed error at use when
# it is absent, as in this container). Test modules import this marker
# for the affected tests so they SKIP visibly instead of failing red —
# one definition, so the reason string cannot drift per file.
import importlib.util  # noqa: E402

HAS_CRYPTO = importlib.util.find_spec("cryptography") is not None
requires_crypto = pytest.mark.skipif(
    not HAS_CRYPTO,
    reason="needs the optional 'cryptography' package (SSE / admin-wire "
    "encryption)",
)

# Runtime sanitizer (analysis/sanitizer.py): on by default under pytest;
# MINIO_TPU_SANITIZE=0 opts out. Installed before any minio_tpu module
# creates locks so instance locks get the lock-order witness.
os.environ.setdefault("MINIO_TPU_SANITIZE", "1")
from minio_tpu.analysis import sanitizer

SANITIZE = sanitizer.enabled()
if SANITIZE:
    sanitizer.install()

if not TPU_LANE:
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    # hermetic CPU lane: compiles neither land in nor load from the
    # persistent cache that ops/runtime.py places inside the checkout
    jax.config.update("jax_enable_compilation_cache", False)
else:
    # the lane's one process compiles for the chip: same cache placement
    # as the server (env var if set, else <checkout>/.jax_cache)
    from minio_tpu.ops import runtime

    runtime.ensure_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: needs the real TPU backend (run via the TPU lane)"
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 lane (`-m 'not slow'`); run "
        "explicitly or via make bench-smoke",
    )


# -- env-mutation sanitizer -------------------------------------------------
#
# pytest imports every test module up front (collection), so a module
# that mutates MINIO_* env at import leaks into every module that runs
# after it — the MINIO_COMPRESSION_ENABLE bug class (PR 6). Policy:
#
# - the pervasive shared-default convention
#   (`os.environ.setdefault("MINIO_TPU_BACKEND", "numpy")`) is an
#   explicit allowlist below; those stay session-wide as before;
# - any OTHER import-time MINIO_* mutation fails every test in the
#   mutating module (and is undone so later modules run clean) — env a
#   module needs belongs in a module-scoped fixture that restores it;
# - mutations made DURING a module's tests without cleanup fail the
#   module at teardown (and are restored so later modules run clean).

_ALLOWED_IMPORT_DEFAULTS = frozenset({
    "MINIO_TPU_BACKEND",        # numpy: fast CPU codec for tests
    "MINIO_TPU_SCAN_INTERVAL",  # 0: no background scanner threads
    "MINIO_PROMETHEUS_AUTH_TYPE",  # public: unauthenticated metrics scrape
})

_import_env_leaks: dict = {}  # module nodeid -> {name: (old, new)}
_collect_snaps: dict = {}


def pytest_collectstart(collector):
    if SANITIZE and isinstance(collector, pytest.Module):
        _collect_snaps[collector.nodeid] = sanitizer.env_snapshot()


def pytest_collectreport(report):
    snap = _collect_snaps.pop(report.nodeid, None)
    if snap is None:
        return
    diff = sanitizer.env_diff(snap)
    leaks = {
        k: (old, new) for k, (old, new) in diff.items()
        if not (
            k in _ALLOWED_IMPORT_DEFAULTS and old == sanitizer._ENV_MISSING
        )
    }
    if leaks:
        _import_env_leaks[report.nodeid] = leaks
        sanitizer.report_env_leak(f"import:{report.nodeid}", leaks)
        # undo only the offending keys; allowlisted defaults stand
        for k, (old, _new) in leaks.items():
            if old == sanitizer._ENV_MISSING:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


def pytest_runtest_setup(item):
    if not SANITIZE:
        return
    for nodeid, leaks in _import_env_leaks.items():
        if item.nodeid.startswith(nodeid + "::"):
            changes = ", ".join(
                f"{k}: {old!r} -> {new!r}"
                for k, (old, new) in sorted(leaks.items())
            )
            pytest.fail(
                f"{nodeid} mutated MINIO_* env at module import "
                f"({changes}), leaking into every module collected "
                "after it; use a module-scoped fixture that restores "
                "the previous value instead",
                pytrace=False,
            )


@pytest.fixture(scope="module", autouse=True)
def _module_env_sanitizer(request):
    if not SANITIZE:
        yield
        return
    snap = sanitizer.env_snapshot()
    yield
    diff = sanitizer.env_diff(snap)
    sanitizer.env_restore(snap)
    if diff:
        nodeid = request.node.nodeid
        sanitizer.report_env_leak(f"module:{nodeid}", diff)
        changes = ", ".join(
            f"{k}: {old!r} -> {new!r}"
            for k, (old, new) in sorted(diff.items())
        )
        pytest.fail(
            f"{nodeid} leaked MINIO_* env mutations past its last test "
            f"({changes}); clean up in a fixture/finally (the sanitizer "
            "has restored them)",
            pytrace=False,
        )


def pytest_collection_modifyitems(config, items):
    if not TPU_LANE:
        return
    if "tpu" in (config.getoption("-m", default="") or ""):
        return  # explicit tpu mark expression: run as selected
    # safety: the TPU lane is meant for `-m tpu`; running the whole
    # suite against one real chip would break the 8-device mesh tests
    skip = pytest.mark.skip(reason="TPU lane runs only -m tpu tests")
    for item in items:
        if "tpu" not in item.keywords:
            item.add_marker(skip)

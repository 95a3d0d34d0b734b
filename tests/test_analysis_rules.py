"""Unit tests for the miniovet rules: one known-bad and one known-good
fixture snippet per rule, plus pragma semantics (a pragma suppresses
exactly one line, and unused pragmas surface under strict mode)."""

import textwrap

import pytest

from minio_tpu.analysis import analyze_source


def run(src, relpath="server/app.py", rules=None):
    return analyze_source(
        textwrap.dedent(src), path=relpath, rules=rules, relpath=relpath
    )


def rules_hit(src, relpath="server/app.py", rules=None):
    return {f.rule for f in run(src, relpath, rules)}


# -- blocking --------------------------------------------------------------

BAD_BLOCKING = """
    import time

    async def handler(request):
        time.sleep(1)
        return 200
"""

GOOD_BLOCKING = """
    import asyncio

    async def handler(request):
        await asyncio.sleep(1)
        return 200
"""


def test_blocking_bad():
    fs = run(BAD_BLOCKING, rules=["blocking"])
    assert len(fs) == 1 and fs[0].rule == "blocking"
    assert "time.sleep" in fs[0].message
    assert fs[0].line == 5


def test_blocking_good():
    assert run(GOOD_BLOCKING, rules=["blocking"]) == []


def test_blocking_catches_requests_subprocess_and_file_io():
    src = """
        import requests, subprocess

        async def handler(p):
            requests.get("http://x")
            subprocess.run(["ls"])
            open("/etc/hosts").read()
    """
    fs = run(src, rules=["blocking"])
    assert len(fs) == 3


def test_blocking_sync_code_only_flags_time_sleep():
    src = """
        import time, requests

        def worker():
            requests.get("http://x")  # fine: blocking thread
            time.sleep(1)             # must be classified
    """
    fs = run(src, rules=["blocking"])
    assert len(fs) == 1 and "time.sleep" in fs[0].message


def test_blocking_nested_sync_def_not_flagged():
    # a nested sync def is typically an executor target; only the async
    # body itself is the event loop's frame
    src = """
        import requests

        async def handler(p):
            def call():
                return requests.get("http://x")
            return await run_in_executor(call)
    """
    assert run(src, rules=["blocking"]) == []


# -- cancellation ----------------------------------------------------------

BAD_CANCELLATION = """
    async def handler(request):
        try:
            await do_work(request)
        except Exception:
            return error_response()
"""

GOOD_CANCELLATION = """
    import asyncio

    async def handler(request):
        try:
            await do_work(request)
        except asyncio.CancelledError:
            raise
        except Exception:
            return error_response()
"""


def test_cancellation_bad():
    fs = run(BAD_CANCELLATION, rules=["cancellation"])
    assert len(fs) == 1 and fs[0].rule == "cancellation"
    assert fs[0].line == 5


def test_cancellation_good():
    assert run(GOOD_CANCELLATION, rules=["cancellation"]) == []


def test_cancellation_reraise_is_ok():
    src = """
        async def handler(request):
            try:
                await do_work(request)
            except Exception:
                log()
                raise
    """
    assert run(src, rules=["cancellation"]) == []


def test_cancellation_sync_try_not_flagged():
    # no await in the try body: cancellation cannot be delivered there
    src = """
        async def handler(request):
            try:
                parse(request)
            except Exception:
                return None
            await send(request)
    """
    assert run(src, rules=["cancellation"]) == []


def test_cancellation_bare_except_flagged():
    src = """
        async def handler(request):
            try:
                await do_work(request)
            except:
                pass
    """
    fs = run(src, rules=["cancellation"])
    assert len(fs) == 1 and "bare" in fs[0].message


# -- hostsync --------------------------------------------------------------

BAD_HOSTSYNC = """
    import numpy as np

    def encode_step(blocks):
        parity = compute(blocks)
        return np.asarray(parity)
"""

GOOD_HOSTSYNC = """
    import jax.numpy as jnp

    def encode_step(blocks):
        data = jnp.asarray(blocks, dtype=jnp.uint8)
        return compute(data)
"""


def test_hostsync_bad_in_hot_path():
    fs = run(BAD_HOSTSYNC, relpath="ops/rs_jax.py", rules=["hostsync"])
    assert len(fs) == 1 and fs[0].rule == "hostsync"
    assert "np.asarray" in fs[0].message


def test_hostsync_good_in_hot_path():
    assert run(GOOD_HOSTSYNC, relpath="ops/rs_jax.py", rules=["hostsync"]) == []


def test_hostsync_ignores_cold_files():
    assert run(BAD_HOSTSYNC, relpath="server/app.py", rules=["hostsync"]) == []


def test_hostsync_boundary_function_whitelisted():
    src = """
        import numpy as np

        def _loop(self):
            return np.asarray(self.batch)
    """
    assert run(src, relpath="parallel/dispatcher.py", rules=["hostsync"]) == []


def test_hostsync_float_on_name_flagged():
    src = """
        def encode_step(x):
            return float(x)
    """
    fs = run(src, relpath="ops/rs_jax.py", rules=["hostsync"])
    assert len(fs) == 1


# -- gf-dtype --------------------------------------------------------------

BAD_GF_DTYPE = """
    import numpy as np

    def make(n):
        stripe = np.zeros((16, n))
        return stripe
"""

GOOD_GF_DTYPE = """
    import numpy as np

    def make(n):
        stripe = np.zeros((16, n), dtype=np.uint8)
        return stripe
"""


def test_gf_dtype_bad():
    fs = run(BAD_GF_DTYPE, relpath="ops/gf.py", rules=["gf-dtype"])
    assert len(fs) == 1 and "dtype" in fs[0].message


def test_gf_dtype_good():
    assert run(GOOD_GF_DTYPE, relpath="ops/gf.py", rules=["gf-dtype"]) == []


def test_gf_dtype_wrong_dtype_flagged():
    src = """
        import numpy as np
        MUL_TABLE = np.zeros((256, 256), dtype=np.float32)
    """
    fs = run(src, relpath="ops/gf.py", rules=["gf-dtype"])
    assert len(fs) == 1 and "uint8" in fs[0].message


def test_gf_dtype_blockspec_tiling():
    bad = """
        import jax.experimental.pallas as pl
        spec = pl.BlockSpec((8, 100), lambda i: (0, 0))
    """
    good = """
        import jax.experimental.pallas as pl
        spec = pl.BlockSpec((8, 128), lambda i: (0, 0))
    """
    assert rules_hit(bad, "ops/rs_pallas.py", ["gf-dtype"]) == {"gf-dtype"}
    assert run(good, "ops/rs_pallas.py", rules=["gf-dtype"]) == []


def test_gf_dtype_covers_cauchy_module():
    """ISSUE-14 satellite: the second code family's kernels sit under
    the same static gate — ops/cauchy.py is in gf-dtype scope, its
    family-specific buffer names (cauchy matrices, sub-chunks,
    piggybacks, heal 'rebuilt' frames) match the naming net, and a
    BlockSpec off the (8, 128) tile is flagged there too."""
    bad_alloc = """
        import numpy as np

        def make(d, p, n):
            cauchy_matrix = np.zeros((p, d))
            sub_chunk = np.zeros(n)
            piggyback = np.empty((p, n))
            rebuilt = np.zeros(n, dtype=np.float64)
            return cauchy_matrix, sub_chunk, piggyback, rebuilt
    """
    fs = run(bad_alloc, relpath="ops/cauchy.py", rules=["gf-dtype"])
    assert len(fs) == 4, [f.message for f in fs]
    good_alloc = """
        import numpy as np

        def make(d, p, n):
            cauchy_matrix = np.zeros((p, d), dtype=np.uint8)
            sub_chunk = np.zeros(n, dtype=np.uint8)
            return cauchy_matrix, sub_chunk
    """
    assert run(good_alloc, relpath="ops/cauchy.py", rules=["gf-dtype"]) == []
    bad_tile = """
        import jax.experimental.pallas as pl
        spec = pl.BlockSpec((7, 128), lambda i: (0, 0))
    """
    assert rules_hit(bad_tile, "ops/cauchy.py", ["gf-dtype"]) == {"gf-dtype"}
    # the REAL module passes its own gate
    import os as _os

    real = open(_os.path.join(
        _os.path.dirname(__file__), "..", "minio_tpu", "ops", "cauchy.py"
    )).read()
    assert analyze_source(
        real, path="minio_tpu/ops/cauchy.py", relpath="ops/cauchy.py",
        rules=["gf-dtype"],
    ) == []


def test_gf_dtype_int_weight_tables_allowed():
    # bit-plane weights are int8 into the MXU by design: name doesn't
    # match the byte-domain patterns
    src = """
        import numpy as np

        def build(r, k):
            w = np.zeros((8 * r, 8 * k), dtype=np.int8)
            return w
    """
    assert run(src, relpath="ops/rs_jax.py", rules=["gf-dtype"]) == []


# -- lock-discipline -------------------------------------------------------

BAD_LOCK = """
    def put(self, bucket, obj):
        mtx = self.ns.new(bucket, obj)
        if not _lock_dyn(mtx, write=True):
            raise TimeoutError
        do_write(bucket, obj)
        mtx.unlock()
"""

GOOD_LOCK = """
    def put(self, bucket, obj):
        mtx = self.ns.new(bucket, obj)
        if not _lock_dyn(mtx, write=True):
            raise TimeoutError
        try:
            do_write(bucket, obj)
        finally:
            mtx.unlock()
"""


def test_lock_bad():
    fs = run(BAD_LOCK, relpath="erasure/set.py", rules=["lock-discipline"])
    assert len(fs) == 1 and "_lock_dyn" in fs[0].message


def test_lock_good():
    assert run(GOOD_LOCK, relpath="erasure/set.py", rules=["lock-discipline"]) == []


def test_lock_ownership_transfer_pattern_ok():
    # open_object hands the held lock to the streaming handle: releases
    # in a broad handler + re-raise, success path returns inside the try
    src = """
        def open_object(self, bucket, obj):
            mtx = self.ns.new(bucket, obj)
            if not _lock_dyn(mtx, write=False):
                raise TimeoutError
            try:
                fi = self._quorum_fileinfo(bucket, obj)
                return Handle(fi, mutex=mtx)
            except BaseException:
                mtx.runlock()
                raise
    """
    assert run(src, relpath="erasure/set.py", rules=["lock-discipline"]) == []


def test_lock_transfer_with_trailing_statement_flagged():
    # the pre-fix open_object shape: statements after the try run with
    # the lock held but unprotected
    src = """
        def open_object(self, bucket, obj):
            mtx = self.ns.new(bucket, obj)
            if not _lock_dyn(mtx, write=False):
                raise TimeoutError
            try:
                fi = self._quorum_fileinfo(bucket, obj)
            except BaseException:
                mtx.runlock()
                raise
            oi = self._to_object_info(bucket, obj, fi)
            return Handle(oi, mutex=mtx)
    """
    fs = run(src, relpath="erasure/set.py", rules=["lock-discipline"])
    assert len(fs) == 1


def test_await_under_sync_lock_flagged():
    src = """
        async def send(self, frame):
            with self._lock:
                await self.ws.send(frame)
    """
    fs = run(src, rules=["lock-discipline"])
    assert len(fs) == 1 and "await" in fs[0].message


def test_async_lock_ok():
    src = """
        async def send(self, frame):
            async with self._lock:
                await self.ws.send(frame)
    """
    assert run(src, rules=["lock-discipline"]) == []


# -- knob ------------------------------------------------------------------

BAD_KNOB = """
    import os
    v = os.environ.get("MINIO_TPU_TOTALLY_NEW_KNOB", "1")
"""

GOOD_KNOB = """
    import os
    v = os.environ.get("MINIO_TPU_BATCH_WINDOW_MS", "2")
"""


def test_knob_undeclared():
    fs = run(BAD_KNOB, rules=["knob"])
    assert len(fs) == 1 and "undeclared" in fs[0].message


def test_knob_declared():
    assert run(GOOD_KNOB, rules=["knob"]) == []


def test_knob_default_mismatch():
    src = """
        import os
        v = os.environ.get("MINIO_TPU_BATCH_WINDOW_MS", "250")
    """
    fs = run(src, rules=["knob"])
    assert len(fs) == 1 and "registry declares" in fs[0].message


def test_knob_prefix_family():
    good = """
        import os
        for k, v in os.environ.items():
            if k.startswith("MINIO_NOTIFY_WEBHOOK_ENABLE_"):
                ep = os.environ.get(f"MINIO_NOTIFY_WEBHOOK_ENDPOINT_{k}", "")
    """
    bad = """
        import os
        for k, v in os.environ.items():
            if k.startswith("MINIO_NOTIFY_CARRIERPIGEON_ENABLE_"):
                pass
    """
    assert run(good, rules=["knob"]) == []
    fs = run(bad, rules=["knob"])
    assert len(fs) == 1 and "prefix knob" in fs[0].message


def test_knob_wrapper_helper_read_needs_declaration():
    src = """
        v = setting("MINIO_TPU_NOT_A_REAL_KNOB", "cfgkey")
    """
    fs = run(src, rules=["knob"])
    assert len(fs) == 1 and "undeclared" in fs[0].message


# -- pragmas ---------------------------------------------------------------

def test_pragma_suppresses_exactly_one_line():
    src = """
        import time

        async def handler(request):
            time.sleep(1)  # miniovet: ignore[blocking] -- test fixture
            time.sleep(2)
            return 200
    """
    fs = run(src, rules=["blocking"])
    assert len(fs) == 1
    assert fs[0].line == 6  # only the unannotated sleep

def test_pragma_on_preceding_comment_line():
    src = """
        import time

        def worker():
            # miniovet: ignore[blocking] -- daemon pacing
            # (reason continues on a second comment line)
            time.sleep(1)
    """
    assert run(src, rules=["blocking"]) == []


def test_pragma_wrong_rule_does_not_suppress():
    src = """
        import time

        async def handler(request):
            time.sleep(1)  # miniovet: ignore[hostsync]
    """
    fs = run(src, rules=["blocking"])
    assert len(fs) == 1


def test_unused_pragma_reported_in_strict():
    src = """
        x = 1  # miniovet: ignore[blocking]
    """
    fs = run(src)  # default rule set includes the pragma pseudo-rule
    assert [f.rule for f in fs] == ["pragma"]


def test_pragma_mention_in_docstring_is_not_a_pragma():
    src = '''
        def f():
            """Annotate sites with `# miniovet: ignore[blocking]`."""
            return 1
    '''
    assert run(src) == []


def test_syntax_error_reported_as_parse_finding():
    fs = analyze_source("def f(:\n", path="x.py")
    assert len(fs) == 1 and fs[0].rule == "parse"


# -- span (obs tracing discipline) -----------------------------------------

BAD_SPAN_NO_WITH = """
    from minio_tpu import obs

    def read_shard(self):
        sp = obs.span(obs.TYPE_STORAGE, "readfile", drive="d0")
        sp.__enter__()
        return 1
"""

GOOD_SPAN_WITH = """
    from minio_tpu import obs

    def read_shard(self):
        with obs.span(obs.TYPE_STORAGE, "readfile", drive="d0") as sp:
            sp.set(bytes=1)
        return 1
"""


def test_span_call_outside_with_flagged():
    fs = run(BAD_SPAN_NO_WITH, rules=["span"])
    assert len(fs) == 1 and fs[0].rule == "span"
    assert "context-manager" in fs[0].message


def test_span_in_with_ok():
    assert run(GOOD_SPAN_WITH, rules=["span"]) == []


def test_span_start_call_flagged_anywhere():
    src = """
        def f(tracer):
            tracer.span_start("x")
    """
    fs = run(src, rules=["span"])
    assert len(fs) == 1 and "span_start" in fs[0].message


def test_imported_span_name_flagged():
    src = """
        from minio_tpu.obs import span

        def f():
            span("s3", "x")
    """
    fs = run(src, rules=["span"])
    assert len(fs) == 1


def test_bare_span_without_obs_import_not_flagged():
    # an unrelated local helper also called `span` must not trip the rule
    src = """
        def span(a, b):
            return a + b

        def f():
            return span(1, 2)
    """
    assert run(src, rules=["span"]) == []


def test_direct_span_construction_flagged():
    src = """
        from minio_tpu import obs

        def f():
            return obs.Span("s3", "x", {})
    """
    fs = run(src, rules=["span"])
    assert len(fs) == 1 and "Span construction" in fs[0].message


def test_span_rule_exempts_obs_package():
    src = """
        def span(t, n, **fields):
            return Span(t, n, fields)
    """
    assert run(src, relpath="obs/trace.py", rules=["span"]) == []


@pytest.mark.parametrize("src,findings", [
    # the phase clock opens the same span and a profiler annotation besides
    ("""
        from minio_tpu import obs

        def f():
            ph = obs.phase("dispatch", "d2h")
            ph.__enter__()
     """, 1),
    ("""
        from minio_tpu import obs

        def f(took):
            with obs.phase("dispatch", "d2h", into=took):
                pass
            with obs.phase("put", "md5"), obs.span(obs.TYPE_STORAGE, "x"):
                pass
     """, 0),
    ("""
        from minio_tpu.obs import phase

        def f():
            phase("put", "md5")
     """, 1),
    ("""
        from minio_tpu import obs

        def f():
            return obs.Phase("put", "md5", None, {})
     """, 1),
    # a stopwatch is no context manager and is not held to the rule
    ("""
        from minio_tpu import obs

        def f():
            clock = obs.PhaseClock("put", "ingest")
            clock.book()
     """, 0),
    # an unrelated local `phase`
    ("""
        def phase(a):
            return a

        def f():
            return phase(1)
     """, 0),
], ids=["orphan", "with", "imported-name", "direct-construction", "stopwatch", "local-name"])
def test_phase_is_context_manager_only_too(src, findings):
    fs = run(src, rules=["span"])
    assert len(fs) == findings and all(f.rule == "span" for f in fs)


# -- retry-discipline ------------------------------------------------------

BAD_RETRY = """
    import time

    def fetch(conn):
        while True:
            try:
                conn.request("GET", "/x")
                return conn.getresponse()
            except OSError:
                pass
            time.sleep(1.0)
"""

GOOD_HEARTBEAT = """
    import time

    def keepalive(ws):
        while True:
            time.sleep(10)
            try:
                ws.send_binary(b"ping")
            except OSError:
                teardown(ws)
                return
"""

GOOD_PACING = """
    import time

    def scan(store):
        for raw in store.walk_objects("b"):
            try:
                inspect(raw)
            except Exception:
                queue_heal(raw)
            time.sleep(0.01)
"""


def test_retry_discipline_flags_adhoc_loop():
    fs = run(BAD_RETRY, rules=["retry-discipline"])
    assert len(fs) == 1 and "fault/retry.py" in fs[0].message


def test_retry_discipline_exempts_teardown_heartbeat():
    # handler exits the loop (return): teardown, not a retry
    assert run(GOOD_HEARTBEAT, rules=["retry-discipline"]) == []


def test_retry_discipline_exempts_pacing_loop():
    # no network/storage-shaped call in the loop body: pacing, not retry
    assert run(GOOD_PACING, rules=["retry-discipline"]) == []


def test_retry_discipline_exempts_retry_module():
    src = """
        import time

        def _sleep_loop(fn):
            while True:
                try:
                    return fn.call()
                except OSError:
                    pass
                time.sleep(0.1)
    """
    assert run(src, relpath="fault/retry.py", rules=["retry-discipline"]) == []


def test_retry_discipline_sleep_inside_handler_flagged():
    src = """
        import time

        def fetch(cli):
            for _ in range(5):
                try:
                    return cli.call("op", b"")
                except OSError:
                    time.sleep(0.5)
    """
    fs = run(src, rules=["retry-discipline"])
    assert len(fs) == 1


# -- cache-discipline ------------------------------------------------------

BAD_CACHE_DICT_WRITE = """
    def warm(es, k, v):
        es.cache._fi[k] = v
"""

BAD_CACHE_INTERNAL_POP = """
    def evict(es, k):
        es.cache._fi.pop(k)
"""

BAD_CACHE_NON_API_CALL = """
    def poke(es, k):
        es.cache.forget(k)
"""

BAD_METACACHE_WRITE = """
    def seed(ck, keys):
        _MC_MEM[ck] = (0, keys, None)
"""

GOOD_CACHE_CHOKEPOINT = """
    def mutate(es, bucket, obj):
        es.cache.invalidate_object(bucket, obj)
        es.cache.invalidate_prefix(bucket, obj + "/")
        es.cache.invalidate_bucket(bucket)
        es.cache.bump_epoch()
        es.cache.clear()
"""

GOOD_CACHE_READ_SIDE = """
    def read(es, bucket, obj, vid, loader, fi, data):
        fi2, metas = es.cache.fileinfo(bucket, obj, vid, loader)
        hit = es.cache.data_get(bucket, obj, vid)
        if es.cache.data_admit(bucket, obj, vid, fi):
            es.cache.data_put(bucket, obj, vid, fi, data)
        return es.cache.snapshot()
"""


def test_cache_discipline_flags_internal_dict_write():
    fs = run(BAD_CACHE_DICT_WRITE, relpath="erasure/set.py",
             rules=["cache-discipline"])
    assert fs and all(f.rule == "cache-discipline" for f in fs)


def test_cache_discipline_flags_internal_pop():
    fs = run(BAD_CACHE_INTERNAL_POP, relpath="erasure/set.py",
             rules=["cache-discipline"])
    assert fs and "cache internal" in fs[0].message


def test_cache_discipline_flags_non_api_method():
    fs = run(BAD_CACHE_NON_API_CALL, relpath="server/object_handlers.py",
             rules=["cache-discipline"])
    assert fs and "non-choke-point" in fs[0].message


def test_cache_discipline_flags_metacache_write():
    fs = run(BAD_METACACHE_WRITE, relpath="server/admin.py",
             rules=["cache-discipline"])
    assert fs and "_MC_MEM" in fs[0].message


def test_cache_discipline_allows_chokepoint_and_reads():
    assert run(GOOD_CACHE_CHOKEPOINT, relpath="erasure/set.py",
               rules=["cache-discipline"]) == []
    assert run(GOOD_CACHE_READ_SIDE, relpath="erasure/set.py",
               rules=["cache-discipline"]) == []


def test_cache_discipline_exempts_cache_package_and_listing():
    assert run(BAD_CACHE_DICT_WRITE, relpath="cache/core.py",
               rules=["cache-discipline"]) == []
    assert run(BAD_METACACHE_WRITE, relpath="erasure/listing.py",
               rules=["cache-discipline"]) == []


GOOD_SEGMENT_READ_SIDE = """
    def serve(es, bucket, obj, vid, fi, hint, data, tok):
        seg = es.cache.segment_open(bucket, obj, vid, hint)
        tok2 = es.cache.segment_admit(bucket, obj, vid, fi)
        es.cache.segment_put(bucket, obj, vid, fi, 1, 0, data, tok)
        es.cache.segment_observe(bucket, obj, vid, 0, 100, fi)
        return seg
"""

BAD_SEGMENT_DIRECT_DROP = """
    from ..cache.segment import segment_cache

    def purge(es):
        segment_cache().drop_where(lambda k: True)
"""

BAD_SEGMENT_INTERNAL_STATE = """
    from ..cache import segment

    def peek(es):
        return segment.segment_cache()._dirs
"""

GOOD_SEGMENT_SNAPSHOT = """
    from ..cache.segment import segment_cache

    def stats():
        return segment_cache().snapshot()
"""


def test_cache_discipline_allows_segment_read_side():
    assert run(GOOD_SEGMENT_READ_SIDE, relpath="erasure/set.py",
               rules=["cache-discipline"]) == []


def test_cache_discipline_flags_direct_segment_drop():
    fs = run(BAD_SEGMENT_DIRECT_DROP, relpath="erasure/set.py",
             rules=["cache-discipline"])
    assert fs and "segment_cache().drop_where" in fs[0].message


def test_cache_discipline_flags_segment_internal_state():
    fs = run(BAD_SEGMENT_INTERNAL_STATE, relpath="server/admin.py",
             rules=["cache-discipline"])
    assert fs and "_dirs" in fs[0].message


def test_cache_discipline_allows_segment_snapshot_and_own_package():
    assert run(GOOD_SEGMENT_SNAPSHOT, relpath="server/metrics.py",
               rules=["cache-discipline"]) == []
    assert run(BAD_SEGMENT_DIRECT_DROP, relpath="cache/core.py",
               rules=["cache-discipline"]) == []


# -- knob-native: getenv() in C++ sources checked against the registry ----

from minio_tpu.analysis.rules_native import scan_native_source  # noqa: E402


def test_knob_native_flags_undeclared_getenv():
    src = 'int n = atoi(getenv("MINIO_TPU_TOTALLY_UNDECLARED"));\n'
    fs = scan_native_source(src, "native/fake.cpp")
    assert len(fs) == 1
    assert fs[0].rule == "knob-native"
    assert "MINIO_TPU_TOTALLY_UNDECLARED" in fs[0].message
    assert fs[0].line == 1


def test_knob_native_allows_declared_and_prefix_knobs():
    src = (
        'const char* a = getenv("MINIO_TPU_NATIVE_THREADS");\n'
        'const char* b = getenv("MINIO_NOTIFY_WEBHOOK_ENABLE_X");\n'
    )
    assert scan_native_source(src, "native/fake.cpp") == []


def test_knob_native_pragma_suppresses():
    src = (
        'getenv("MINIO_TPU_NOPE");  '
        "// miniovet: ignore[knob-native] -- test fixture\n"
    )
    assert scan_native_source(src, "native/fake.cpp") == []


def test_knob_native_ignores_non_minio_env():
    assert scan_native_source('getenv("HOME");\n', "native/fake.cpp") == []


def test_knob_native_runs_via_analyze_paths(tmp_path):
    from minio_tpu.analysis import analyze_paths

    cpp = tmp_path / "x.cpp"
    cpp.write_text('getenv("MINIO_TPU_NOT_A_KNOB");\n')
    fs = analyze_paths([str(tmp_path)])
    assert [f.rule for f in fs] == ["knob-native"]
    # rule selection excludes it like any other rule
    assert analyze_paths([str(tmp_path)], rules=["knob"]) == []

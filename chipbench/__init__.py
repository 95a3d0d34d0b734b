"""chipbench — the benchmark of the served S3 path on the chip.

`python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json`. Everything that is the yardstick lives
here and imports nothing of `minio_tpu` except the system under test:
traffic generation (`traffic/*.json`, read by the generator each names,
`generators/`), the plain reference (`reference.py`), the comparison that
decides `correct` (`verify.py` and its steps, `checks/`), the work and
peaks tables (`work.py`), the trace reduction (`trace_reduce.py`) and one
reader per per-layer metric (`metrics/`). Whatever a name in the data
stands for is a file found by that name (`plugins.py`). The harness parent
never imports jax: the server child holds the chip.
"""

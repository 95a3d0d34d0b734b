"""The device served it, not a lower rung of the ladder: backend level as
the configuration expects, no block from the numpy rung, no device fault,
no fused failure — since boot, read after the run."""

from chipbench.procs import scrape, total


def run(v):
    tpu = scrape(v.srv.port, "/api/tpu")
    fault = scrape(v.srv.port, "/api/fault")
    level = total(fault, "minio_tpu_backend_level")
    return {
        "backend_level_below": (v.config["expects"]["backend_level"] - level, 0),
        "numpy_rung_blocks": (total(fault, "minio_tpu_backend_numpy_blocks_total"), 0),
        "device_faults": (total(fault, "minio_tpu_backend_device_faults_total"), 0),
        "fused_failures": (total(tpu, "minio_tpu_fused_failures_total"), 0),
    }

"""Share of the window, in %, that the ONE dispatch thread spent inside
its device call: `minio_tpu_device_seconds_total` delta over the window's
seconds. That counter is host-clock time ending in `np.asarray`, so it is
H2D + kernel + D2H + any trace-and-lower — not kernel time. At 100 % that
thread is the cap. Not a share of a roofline. Moves s3_mib_s."""


def read(w):
    if w.seconds <= 0:
        return None
    return 100.0 * w.delta("minio_tpu_device_seconds_total") / w.seconds

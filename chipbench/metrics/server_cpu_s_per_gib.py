"""CPU seconds (utime + stime of the server child, /proc/<pid>/stat, all
its threads) per GiB acknowledged in the window. Source: program_counter
(the kernel's accounting of the program). Moves s3_mib_s: on a host-bound
path this is the cost that sets the rate."""


def read(w):
    if w.acked_bytes <= 0:
        return None
    return w.server_cpu_s / (w.acked_bytes / 2**30)

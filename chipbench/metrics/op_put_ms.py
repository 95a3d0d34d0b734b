"""Mean wall ms of one PUT as its handler sees it: Δ seconds ÷ Δ calls of the
front end's phase `op`/`put_object` (the parsed, authorized request to the
finished response; on the event loop, wall only). What the client adds on top
is its connection, SigV4 and the socket. None from a program without the row
and from a window without such a request.
Source: program_counter. Moves s3_mib_s.
`read(w)` receives a `metrics.Window`."""

from chipbench.op_counters import ms_per_call


def read(w):
    return ms_per_call(w, "op", "put_object")

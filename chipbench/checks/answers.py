"""Front end, SigV4: every request since boot, the window's among them. A
200 whose ETag is not the md5 of the body sent is a wrong answer."""


def run(v):
    t0, t1 = v.window
    lied = [r for r in v.records if r.status == 200 and not r.ok]
    v.details["answers_wrong_in_window"] = sum(1 for r in lied if t0 <= r.done <= t1)
    if lied:
        v.note(f"{lied[0].op} {lied[0].key}: 200 but ETag wrong")
    return {"answers_wrong": (len(lied), 0)}

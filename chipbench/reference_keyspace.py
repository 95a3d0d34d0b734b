"""The plain reference of a key space: what an unversioned S3 bucket is, as a
dictionary. PUT sets key -> (md5, size); DELETE removes the key; GET and HEAD
answer the entry or nothing (404). It imports nothing of `minio_tpu` and
knows no server: `replay` is given the records of a run — (key, operation,
when it was acknowledged, what it answered) — and plays every acknowledged
PUT and DELETE in the order of their acknowledgement, key by key. It gives
the state every key must be in at rest (`Bucket.objects`, `Bucket.deleted`)
and, on the way, every acknowledged GET and HEAD whose answer was not the
entry the key had at that moment (`wrong`).

The order is total for one key because of how the traffic is made
(`generators/closed_loop_mixed.py`): a key is PUT once, by one client; it is
read only after that PUT was acknowledged; its DELETE is sent only while no
request of it is in flight, and nothing is sent to it afterwards. So an
answer has exactly one right value, and a record that breaks the order — a
read acknowledged for a key the model does not hold — is a wrong answer, not
a race.

What `replay` receives: an iterable of records with the attributes `key`,
`op` ("PUT", "GET", "HEAD", "DELETE"), `done`, `status` (200 where the server
answered what S3 states for the operation), and, for what the answer said,
`etag` and `length` (a PUT's: what was sent and acknowledged).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Bucket:
    """An unversioned bucket: `objects` is what GET and HEAD must answer,
    `deleted` the keys whose last acknowledged operation was a DELETE."""

    objects: dict[str, tuple[str, int]] = field(default_factory=dict)
    deleted: set[str] = field(default_factory=set)

    def put(self, key: str, md5: str, size: int) -> None:
        self.objects[key] = (md5, size)
        self.deleted.discard(key)

    def delete(self, key: str) -> None:
        if self.objects.pop(key, None) is not None:
            self.deleted.add(key)

    def head(self, key: str) -> tuple[str, int] | None:
        """(md5, size), or None: 404. A GET answers the same and the body."""
        return self.objects.get(key)


def replay(records) -> tuple[Bucket, list]:
    """-> (the bucket at rest, the acknowledged GET and HEAD records whose
    answer was not the model's entry for their key at that moment)."""
    bucket, wrong = Bucket(), []
    for r in sorted(records, key=lambda r: r.done):
        if r.status != 200:
            continue
        if r.op == "PUT":
            bucket.put(r.key, r.etag, r.length)
        elif r.op == "DELETE":
            bucket.delete(r.key)
        elif r.op in ("GET", "HEAD"):
            if bucket.head(r.key) != (r.etag, r.length):
                wrong.append(r)
    return bucket, wrong

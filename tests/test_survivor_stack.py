"""A degraded read lays a decode group's survivors out once, in the form the
rung that will decode them takes (`erasure/coder.py` SurvivorStack, filled by
`erasure/set.py` stack_survivors): the decode mega-kernel's chunk-major input
where that kernel will take the group, `[d, W, per]` everywhere else. Bytes
and counts on the CPU: the packed stack is what the parent's zero-pad +
`pack_chunk_major` built, whatever the payloads' form; the layout follows
what the coder can observe; `reconstruct_data_flat` returns the same rows
from a plain array, a `rows` stack and a packed one."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

pytest.importorskip("jax")

from minio_tpu.erasure import bitrot_io, bufpool  # noqa: E402
from minio_tpu.erasure import set as es_mod  # noqa: E402
from minio_tpu.erasure.coder import ErasureCoder, SurvivorStack  # noqa: E402
from minio_tpu.ops import bitrot_jax  # noqa: E402
from minio_tpu.ops import fused_pallas as fp  # noqa: E402

D, PER, CB = 8, 131072, fp.CHUNK_BYTES


def shapes_only(d, p, batch, n):
    """`fp.supports` less its first gate: the shapes the mega-kernel takes,
    on a host that has no TPU."""
    return d <= 8 and 1 <= p <= 8 and batch >= 16 and batch % 16 == 0 \
        and n % CB == 0 and n > 0


@pytest.fixture
def as_on_the_chip(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BACKEND", "jax")
    monkeypatch.setattr(fp, "supports", shapes_only)
    monkeypatch.setattr(bitrot_jax, "_fused_dec_cooldown", 0)


def runs_of(surv, view=True):
    """Each shard's blocks of `surv` [W, d, per] as one verified run read:
    `digest || block` frames end to end, through `verify_run`."""
    w, d, per = surv.shape
    return {
        k: bitrot_io.verify_run(
            b"".join(bitrot_io.frame_block(surv[b, k].tobytes()) for b in range(w)),
            [per] * w, view=view,
        )
        for k in range(d)
    }


def dirty_the_pool(nbytes):
    lease = bufpool.get_pool().acquire(nbytes)
    lease.array[:] = 0xA5
    lease.release()


def copies_moved(before):
    now = es_mod.stack_copies_snapshot()
    return {k: now[k] - before[k] for k in now if now[k] != before[k]}


@pytest.mark.parametrize("m", [1, 2, 8])
@pytest.mark.parametrize("w", [1, 7, 8, 9, 16])
def test_the_packed_stack_is_the_parents_pad_and_pack(w, m, as_on_the_chip, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_DECODE_MIN_SHARDS", "16")  # a window of one block too
    surv = np.random.default_rng([w, m]).integers(0, 256, (w, D, PER), dtype=np.uint8)
    bpad = -(-w // 16) * 16
    parents = fp.pack_chunk_major(
        np.concatenate([surv, np.zeros((bpad - w, D, PER), np.uint8)])
    )
    got = runs_of(surv)
    dirty_the_pool(bpad * D * PER)
    before = es_mod.stack_copies_snapshot()
    stack = ErasureCoder(8, 8).survivor_stack(w, PER, m)
    try:
        assert stack.packed and stack.shape == (D, w, PER)
        assert stack.array.shape == (PER // CB, bpad, D, CB) == parents.shape
        assert stack.array.flags.c_contiguous
        es_mod.stack_survivors(stack, tuple(range(D)), [(0, 0, w)], [got])
        assert np.array_equal(stack.array, parents)
        assert not stack.array[:, w:].any()  # written, on an arena that held 0xA5
        assert np.array_equal(stack.block_major(), surv)
    finally:
        stack.release()
    # one strided copy per shard where the run is one array; a run of one
    # frame has no such array and goes block by block
    assert copies_moved(before) == {("run" if w > 1 else "block", "packed"): D}


def single_frame_runs(surv, view=True):
    w = surv.shape[0]
    return [runs_of(surv[b : b + 1], view) for b in range(w)], [(b, 0, 1) for b in range(w)]


def unequal(surv):
    """The run read with a shorter last frame after it, as a part's tail is:
    its payloads are no one array, and the group is the equal-length head."""
    w, d, per = surv.shape
    got = {}
    for k in range(d):
        blks = [surv[b, k].tobytes() for b in range(w)] + [b"tail" * 100]
        got[k] = bitrot_io.verify_run(
            b"".join(bitrot_io.frame_block(b) for b in blks),
            [per] * w + [400], view=True,
        )
    return [got], [(0, 0, w)]


FORMS = {
    "bytes": lambda surv: ([runs_of(surv, view=False)], [(0, 0, len(surv))]),
    "single-frame-runs": single_frame_runs,
    "unequal-lengths": unequal,
    "two-stretches": lambda surv: (
        [runs_of(surv[:3]), runs_of(surv[3:])], [(0, 0, 3), (1, 0, len(surv) - 3)]),
    "inside-a-run": lambda surv: (
        [runs_of(np.concatenate([surv[:1], surv, surv[:2]]))], [(0, 1, len(surv))]),
}


@pytest.mark.parametrize("chunk", [CB, 0], ids=["packed", "rows"])
@pytest.mark.parametrize("form", FORMS.values(), ids=FORMS.keys())
def test_payloads_in_any_form_fill_the_same_stack(form, chunk):
    w, per = 7, 4 * CB
    surv = np.random.default_rng(11).integers(0, 256, (w, D, per), dtype=np.uint8)
    present = tuple(range(1, D + 1))  # shard 0 lost, the first parity stands in
    by_runs = SurvivorStack(D, w, per, chunk)
    es_mod.stack_survivors(
        by_runs, present, [(0, 0, w)],
        [dict(zip(present, runs_of(surv).values()))],
    )
    got, stretches = form(surv)
    got = [dict(zip(present, g.values())) for g in got]
    before = es_mod.stack_copies_snapshot()
    stack = SurvivorStack(D, w, per, chunk, pooled=False)
    es_mod.stack_survivors(stack, present, stretches, got)
    assert np.array_equal(stack.array, by_runs.array)
    assert np.array_equal(stack.block_major(), surv)
    layout = "packed" if chunk else "rows"
    one_array = all(getattr(p, "rows", None) is not None for g in got for p in g.values())
    assert copies_moved(before) == (
        {("run", layout): D * len(stretches)} if one_array else {("block", layout): D * w}
    )
    by_runs.release()
    stack.release()  # unpooled: nothing to give back, and no fault


LAYOUTS = {
    # (d, p, family, w, per, m, backend, knobs) -> packed?
    "8+8-window-of-8": ((8, 8, "reedsolomon", 8, PER, 1, "jax", {}), True),
    "8+8-eight-to-rebuild": ((8, 8, "reedsolomon", 8, PER, 8, "jax", {}), True),
    "8+8-full-batch": ((8, 8, "reedsolomon", 16, PER, 1, "jax", {}), True),
    "4+4": ((4, 4, "reedsolomon", 8, 262144, 2, "jax", {}), True),
    "12+4-d-over-8": ((12, 4, "reedsolomon", 8, 87382, 1, "jax", {}), False),
    "8+8-tail-block": ((8, 8, "reedsolomon", 8, 777, 1, "jax", {}), False),
    "under-the-device-floor": ((8, 8, "reedsolomon", 3, PER, 1, "jax", {}), False),
    "cauchy": ((8, 8, "cauchy", 8, PER, 1, "jax", {}), False),
    "cpu-plane-process": ((8, 8, "reedsolomon", 8, PER, 1, "numpy", {}), False),
    "knob-off": ((8, 8, "reedsolomon", 8, PER, 1, "jax", {"MINIO_TPU_FUSED_CM": "0"}), False),
    "floor-beyond-any-window": (
        (8, 8, "reedsolomon", 8, PER, 1, "jax", {"MINIO_TPU_DECODE_MIN_SHARDS": str(1 << 30)}),
        False),
}


@pytest.mark.parametrize("case,packed", LAYOUTS.values(), ids=LAYOUTS.keys())
def test_the_layout_follows_what_the_coder_observes(case, packed, as_on_the_chip, monkeypatch):
    d, p, family, w, per, m, backend, knobs = case
    monkeypatch.setenv("MINIO_TPU_BACKEND", backend)
    for k, v in knobs.items():
        monkeypatch.setenv(k, v)
    stack = ErasureCoder(d, p, family=family).survivor_stack(w, per, m, pooled=False)
    assert stack.packed is packed and stack.shape == (d, w, per)
    if not packed:
        assert stack.array.shape == (d, w, per)


def test_a_cooling_kernel_or_a_host_without_a_tpu_gets_rows(monkeypatch):
    monkeypatch.setenv("MINIO_TPU_BACKEND", "jax")
    coder = ErasureCoder(8, 8)
    # `fp.supports` as shipped: false off the TPU, so every tier-1 run is here
    assert not coder.survivor_stack(8, PER, 1, pooled=False).packed
    monkeypatch.setattr(fp, "supports", shapes_only)
    assert coder.survivor_stack(8, PER, 1, pooled=False).packed
    # after a failure the kernel sits out its cooldown; asking does not spend it
    monkeypatch.setattr(bitrot_jax, "_fused_dec_cooldown", 3)
    assert not coder.survivor_stack(8, PER, 1, pooled=False).packed
    assert bitrot_jax._fused_dec_cooldown == 3


def numpy_kernel(calls):
    """`fp.fused_decode_hash_cm` in numpy: chunk-major in, chunk-major out."""
    from minio_tpu.ops import rs

    def stand_in(surv_cm, d, p, present, missing, key=None):
        cm = np.asarray(surv_cm)
        nc, bpad, d_, cb = cm.shape
        assert d_ == d and cb == CB and bpad % 16 == 0
        calls.append((cm.shape, tuple(present), tuple(missing)))
        blocks = fp.unpack_chunk_major(cm)  # [bpad, d, n]
        mat = rs.get_codec(d, p).reconstruct_rows_for(list(present)[:d], list(missing))
        from minio_tpu.ops import gf

        out = np.zeros((bpad, len(missing), nc * cb), dtype=np.uint8)
        for r, row in enumerate(mat):
            for k in range(d):
                if int(row[k]):
                    out[:, r] ^= gf.MUL_TABLE[int(row[k])][blocks[:, k]]
        digests = np.zeros((bpad, d + len(missing), 32), dtype=np.uint8)
        return fp.pack_chunk_major(out), digests

    return stand_in


@pytest.mark.parametrize("w,missing", [(8, (0,)), (5, (2, 6)), (16, (7,))],
                         ids=["w8-m1", "w5-m2", "w16-m1"])
def test_reconstruct_data_flat_returns_the_same_rows_from_every_form(
        w, missing, as_on_the_chip, monkeypatch):
    per = 2 * CB
    coder = ErasureCoder(8, 8)
    rng = np.random.default_rng([w, len(missing)])
    data = rng.integers(0, 256, (w, D, per), dtype=np.uint8)
    full = np.stack([coder._np.encode(np.concatenate(
        [data[b], np.zeros((8, per), np.uint8)])) for b in range(w)])  # [w, 16, per]
    present = tuple(i for i in range(16) if i not in missing)[:D]
    surv = np.ascontiguousarray(full[:, present])  # [w, d, per]
    calls: list = []
    monkeypatch.setattr(fp, "fused_decode_hash_cm", numpy_kernel(calls))
    stats = bitrot_jax.decode_stats_snapshot()
    packed = coder.survivor_stack(w, per, len(missing))
    assert packed.packed
    es_mod.stack_survivors(packed, present, [(0, 0, w)], [dict(zip(present, runs_of(surv).values()))])
    from_packed = coder.reconstruct_data_flat(packed, present, missing)
    packed.release()
    assert calls == [((per // CB, -(-w // 16) * 16, D, CB), present, missing)]
    now = bitrot_jax.decode_stats_snapshot()
    assert (now["fused"] - stats["fused"], now["blocks"] - stats["blocks"],
            now["pad_blocks"] - stats["pad_blocks"]) == (1, w, -w % 16)
    # a plain [d, W, per] array, as before this stack existed, and the same
    # array inside a `rows` stack: padded and packed by the kernel's entry
    plain = np.ascontiguousarray(surv.transpose(1, 0, 2))
    from_plain = coder.reconstruct_data_flat(plain, present, missing)
    rows = SurvivorStack(D, w, per, pooled=False)
    rows.array[:] = plain
    from_rows = coder.reconstruct_data_flat(rows, present, missing)
    assert len(calls) == 3 and calls[1] == calls[2] == calls[0]
    want = data[:, list(missing)].transpose(1, 0, 2)  # [m, w, per]
    for rec in (from_packed, from_plain, from_rows):
        assert rec.shape == (len(missing), w, per) and np.array_equal(rec, want)
    # under the device floor the host rebuilds, from either form
    monkeypatch.setenv("MINIO_TPU_DECODE_MIN_SHARDS", str(1 << 30))
    assert np.array_equal(coder.reconstruct_data_flat(plain, present, missing), want)
    assert np.array_equal(coder.reconstruct_data_flat(rows, present, missing), want)
    assert len(calls) == 3

"""Ask the TPU compiler, without a TPU: every kernel of the served path is
AOT-compiled for a DESCRIBED v5e chip at production shapes.

Interpret mode and the CPU lane cannot see what Mosaic refuses (a slice
off the tiling, too much VMEM, a program that does not fit HBM); this
file can, at no chip time, on every PR. A compile that passes is not a
chip run — `python chip_smoke.py` on the chip is — but a kernel the
compiler refuses here would be served by the ladder's next rung there,
silently except for one stderr line.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import or
in a skipif/parametrize argument, so every xdist worker collects the
same tests and only the worker given this file loads libtpu; shapes are
`jax.ShapeDtypeStruct(..., sharding=...)`, never arrays; code that asks
`jax.default_backend()` is steered by monkeypatching here, not by an
option of the program. All cases live in this ONE file: a second file
could land on another worker, whose fixture would skip in silence.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from minio_tpu.ops.highwayhash import MINIO_KEY


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The program's own backend probes answer "tpu" while a case traces:
    ops/bitrot_pallas.py and ops/bitrot_jax.py pick the Pallas chain and
    the TPU unroll from jax.default_backend()."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _u8(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)


@pytest.mark.parametrize(
    "d,p,batch,n",
    [
        (8, 8, 16, 131072),   # EC 8+8, the mega-kernel's floor bucket
        (8, 8, 64, 131072),   # one streamed 64 MiB PUT = one 64-block arena
        (4, 4, 16, 262144),   # 8-drive set
        (2, 2, 16, 524288),   # 4-drive set (BASELINE config 1)
        (8, 2, 16, 131072),   # decode form: 8 survivors -> 2 missing
    ],
    ids=["enc8+8xB16", "enc8+8xB64", "enc4+4xB16", "enc2+2xB16", "dec8m2xB16"],
)
def test_fused_mega_kernel_compiles(one_chip, as_tpu, d, p, batch, n):
    from minio_tpu.ops import fused_pallas as fp

    nc = n // fp.CHUNK_BYTES
    run = fp._build(d, p, batch, nc, MINIO_KEY)
    compiled = run.lower(
        _u8((nc, batch, d, fp.CHUNK_BYTES), one_chip),
        jax.ShapeDtypeStruct((128, 128), jnp.int8, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fresh_hash_jit():
    """hash256_blocks_pallas re-jitted from its undecorated function: the
    trace taken here (Pallas branch, under the patched backend probe) must
    not sit in the module-level jit's cache for a CPU test to find."""
    from minio_tpu.ops import bitrot_pallas as bp

    return jax.jit(
        bp.hash256_blocks_pallas.__wrapped__, static_argnames=("key",)
    )


@pytest.mark.parametrize(
    "b,n",
    [
        (256, 131072),   # 16 blocks x 16 shards, EC 8+8 shard length
        (256, 87382),    # EC 12+4 shard length: 87382 % 32 == 22 tail packet
        (3072, 131072),  # MAX_DEVICE_SHARDS (erasure/coder.py)
    ],
    ids=["256x128KiB", "256x87382", "3072x128KiB"],
)
def test_hash_chain_kernel_compiles(one_chip, as_tpu, b, n):
    compiled = _fresh_hash_jit().lower(_u8((b, n), one_chip), MINIO_KEY).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rs_pallas_encode_compiles(one_chip):
    from minio_tpu.ops import rs_pallas

    d, r, b, n = 8, 8, 16, 131072
    compiled = rs_pallas._encode_padded.lower(
        jax.ShapeDtypeStruct((8 * r, 8 * d), jnp.int8, sharding=one_chip),
        _u8((b, d, n), one_chip), d, r,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_rung_ec12p4_compiles_with_pallas_hash(one_chip, as_tpu, monkeypatch):
    """The rung a default 16-drive deployment gets: row-major XLA encode +
    the Pallas hash chain over 87,382-byte shards, jitted as one program."""
    from minio_tpu.ops import bitrot_jax, bitrot_pallas
    from minio_tpu.ops.rs_jax import get_tpu_codec

    monkeypatch.setattr(
        bitrot_pallas, "hash256_blocks_pallas", _fresh_hash_jit()
    )
    d, p, b, n = 12, 4, 16, 87382
    codec = get_tpu_codec(d, p)
    compiled = jax.jit(
        lambda x: bitrot_jax.encode_and_hash(codec, x)
    ).lower(_u8((b, d, n), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the whole program, not just the kernel, has to fit one 16 GB chip
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 8 << 30

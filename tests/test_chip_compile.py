"""The kernels of the served path and the mesh bodies, compiled for the
chip that is described and not attached (v5e:2x2), at the widths the
program really hands over: 16 MB per shard row (TpuRSCodec.preferred_chunk),
65,536-probe batches, the arena's power-of-two row counts, and the [1->2, k,
16 MB] and [1->2, k, 1 MiB] batches `_mesh_encode` builds (a chunk of a
large block; a small-block row, which is every row of a volume under 10 GB). The chip's compiler refuses here what it
would refuse there — a slice off the tiling, a program past HBM — at no
chip time. Nothing runs: a compile that passes is not a chip run.

One file, the topology inside a module fixture (never at import): only the
xdist worker that is handed this file loads the TPU's library."""

import os

import numpy as np
import pytest

HBM_BYTES = int(15.75 * (1 << 30))  # what one v5e chip offers a program
ROW_WORDS = (16 << 20) // 4  # TpuRSCodec.preferred_chunk, packed uint32
PROBES = 65_536


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described device can be written to the persistent
    # cache but never read back without a chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_rows(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("vol", "blk"))
    return mesh, NamedSharding(mesh, P("vol", None, "blk"))


def _compile(lowered, pallas: bool):
    compiled = lowered.compile()  # raises what the chip's compiler would
    m = compiled.memory_analysis()
    held = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert held <= HBM_BYTES, f"{held / 2**30:.2f} GiB wanted on one chip"
    assert ("tpu_custom_call" in compiled.as_text()) == pallas
    return m


def _decode_rows(lost):
    from seaweedfs_tpu.storage.erasure_coding.galois import (
        DECODE_ROWS_CACHE,
        build_matrix,
    )

    matrix = build_matrix(10, 14)
    survivors = [i for i in range(14) if i not in lost][:10]
    return DECODE_ROWS_CACHE.rows_for(matrix, survivors, list(lost))


def _parity(k, m):
    from seaweedfs_tpu.storage.erasure_coding.galois import build_matrix

    return build_matrix(k, k + m)[k:]


# (id, matrix builder, words per row): every GF matrix the served path
# dispatches through _gf_matmul_pallas
_GF_CASES = [
    ("encode-10.4", lambda: _parity(10, 4), ROW_WORDS),
    ("encode-6.3", lambda: _parity(6, 3), ROW_WORDS),
    ("encode-12.4", lambda: _parity(12, 4), ROW_WORDS),
    # the 1 MiB small-block row: all a volume under 10 GB ever dispatches
    ("encode-10.4-small-block", lambda: _parity(10, 4), (1 << 20) // 4),
    ("decode-one-loss", lambda: _decode_rows((3,)), ROW_WORDS),
    ("decode-four-loss", lambda: _decode_rows((0, 5, 11, 13)), ROW_WORDS),
    # a chunk needle's lost interval (ISSUE 35): a span of 128 KiB to 1 MiB,
    # padded to the kernel's 256 KiB granule, is one of four widths a row
    *[
        (f"decode-one-loss-{kib}k", lambda: _decode_rows((3,)), (kib << 10) // 4)
        for kib in (256, 512, 768, 1024)
    ],
]


@pytest.mark.parametrize(
    "make_matrix,words", [c[1:] for c in _GF_CASES], ids=[c[0] for c in _GF_CASES]
)
def test_gf_kernel_compiles_at_served_width(one_chip, make_matrix, words):
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256

    matrix = np.asarray(make_matrix(), dtype=np.uint8)
    x = jax.ShapeDtypeStruct(
        (matrix.shape[1], words // gf256.LANE, gf256.LANE),
        jnp.uint32, sharding=one_chip,
    )
    m = _compile(
        gf256._gf_matmul_pallas.lower(
            tuple(map(tuple, matrix)), x, gf256.DEFAULT_BLOCK_ROWS, False
        ),
        pallas=True,
    )
    assert m.temp_size_in_bytes == 0  # blocks stream through VMEM only


def _cols(sharding, *shape, dtype="uint32"):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype), sharding=sharding)


def test_bulk_lookup_bucketed_compiles_at_10m_rows(one_chip):
    from seaweedfs_tpu.ops import index_kernel as ik

    n, buckets = 10_000_000, ik.IndexSnapshot.MAX_BUCKETS
    u = lambda *s: _cols(one_chip, *s)  # noqa: E731
    i = lambda *s: _cols(one_chip, *s, dtype="int32")  # noqa: E731
    _compile(
        ik._bulk_lookup_bucketed.lower(
            4, u(n), u(n), u(n), u(n), i(buckets + 1),
            u(PROBES), u(PROBES), i(PROBES),
        ),
        pallas=False,
    )


def test_bulk_lookup_unbucketed_compiles_at_10m_rows(one_chip):
    from seaweedfs_tpu.ops import index_kernel as ik

    n = 10_000_000
    u = lambda *s: _cols(one_chip, *s)  # noqa: E731
    _compile(
        ik._bulk_lookup.lower(
            25, u(n), u(n), u(n), u(n), u(PROBES), u(PROBES)
        ),
        pallas=False,
    )


def test_ragged_dispatch_compiles_at_16m_rows(one_chip):
    from seaweedfs_tpu.ops import ragged_lookup as rl

    n, bloom_words = 1 << 24, 1 << 20
    u = lambda *s: _cols(one_chip, *s)  # noqa: E731
    _compile(
        rl._ragged_dispatch.lower(
            6, u(n), u(n), u(n), u(n), u(bloom_words),
            u(4, PROBES), _cols(one_chip, 5, PROBES, dtype="int32"),
        ),
        pallas=False,
    )


@pytest.mark.parametrize(
    "what", ["encode", "reconstruct", "verify", "encode_small_row"]
)
def test_mesh_body_compiles_at_mesh_encode_width(mesh_rows, what):
    """The (vol, blk) bodies on host-packed uint32 words: with the bitcast
    on the device the encode at this width was refused outright (22.0 GB
    of HBM wanted) and took 98-600 s at a sixteenth to a quarter of it."""
    from seaweedfs_tpu.parallel import sharded_ec as se

    mesh, rows = mesh_rows
    # _mesh_encode hands over buf[None]: one volume of up to 16 MB rows,
    # which _put_words pads to the vol axis
    v = mesh.shape["vol"]
    if what == "encode":
        body = se._apply_body(se._matrix_key(_parity(10, 4)), mesh)
        x = _cols(rows, v, 10, ROW_WORDS)
    elif what == "encode_small_row":
        # what the pipeline dispatches for a 1 MiB small-block row
        body = se._apply_body(se._matrix_key(_parity(10, 4)), mesh)
        x = _cols(rows, v, 10, (1 << 20) // 4)
    elif what == "reconstruct":
        body = se._apply_body(se._matrix_key(_decode_rows((2, 12))), mesh)
        x = _cols(rows, v, 10, ROW_WORDS)
    else:
        body = se._verify_body(se._matrix_key(_parity(10, 4)), mesh)
        x = _cols(rows, v, 14, ROW_WORDS)
    m = _compile(body.lower(x), pallas=False)
    # per device: its quarter of the batch and no relayout beside it
    assert m.temp_size_in_bytes <= m.argument_size_in_bytes

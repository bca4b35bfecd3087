"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is only
described, and refuses what the chip would refuse (misaligned blocks, more
VMEM than a kernel may use).  Interpret mode on the CPU cannot see either.
Shapes are those of ``chip_smoke.py``: a 65536 × 8192 matrix encoded at
(n, k) = (12, 10) with 20 chunks, so each worker shard is 6560 × 8192 and
each chunk 328 rows; its ``--four-chips`` phase is compiled over the
described chip's 2x2 mesh.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

D = 8192
SHARD_ROWS = 6560          # 65600 padded rows / k=10
CHUNK_ROWS = 328           # SHARD_ROWS / 20 chunks
N, K, CHUNKS = 12, 10, 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("shard_rows,rows,nvec,dtype", [
    (SHARD_ROWS, CHUNK_ROWS, None, jnp.float32),   # the smoke's B=1 chunk
    (SHARD_ROWS, CHUNK_ROWS, 16, jnp.float32),     # the smoke's B=16 chunk
    (8192, 8192, None, jnp.float32),               # one 8192-row chunk
    (SHARD_ROWS, CHUNK_ROWS, 16, jnp.bfloat16),    # bf16: default precision
])
def test_chunk_matvec(one_chip, shard_rows, rows, nvec, dtype):
    x_shape = (D,) if nvec is None else (D, nvec)
    fn = functools.partial(ops.chunk_matvec, rows=rows, interpret=False)
    text = _compiled_text(fn, _spec((shard_rows, D), one_chip, dtype),
                          _spec(x_shape, one_chip, dtype),
                          _spec((), one_chip, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cols", [CHUNK_ROWS, CHUNK_ROWS * 16])
def test_mds_decode(one_chip, cols):
    """The engine's decode: (C, k, k) weights @ (C, k, rpc·B) partials."""
    fn = functools.partial(ops.mds_decode, interpret=False)
    text = _compiled_text(fn, _spec((CHUNKS, K, K), one_chip),
                          _spec((CHUNKS, K, cols), one_chip))
    assert "tpu_custom_call" in text


def test_mds_encode(one_chip):
    """Encode the smoke's matrix: k data blocks of one shard's shape."""
    fn = functools.partial(ops.mds_encode, interpret=False)
    text = _compiled_text(fn, _spec((N, K), one_chip),
                          _spec((K, SHARD_ROWS, D), one_chip))
    assert "tpu_custom_call" in text


def test_four_chip_coded_matvec(topo):
    """``chip_smoke.py --four-chips``: the shard_map matvec over a 2x2 mesh,
    (n, k) = (4, 3), 16 chunks, A of 49152 × 8192 (a psum decodes)."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from repro.core.coded_matmul import CodedMatvec
    from repro.core.coding import MDSCode
    mesh = Mesh(np.array(topo.devices), ("workers",),
                axis_types=(AxisType.Auto,))
    cm = CodedMatvec(MDSCode(4, 3), chunks=16, mesh=mesh)
    rep = NamedSharding(mesh, P())
    args = (_spec((4, 16384, D), NamedSharding(mesh, P("workers"))),
            _spec((D,), rep), _spec((4,), rep, jnp.int32),
            _spec((4,), rep, jnp.int32), _spec((16, 3, 4), rep))
    compiled = jax.jit(cm.apply).lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
    # each chip holds one 16384 × 8192 float32 partition
    assert compiled.memory_analysis().argument_size_in_bytes < 16384 * D * 5


def test_lstm_cell(one_chip):
    """The speed predictor's cell: 1024 hosts as one batch, input 1, H = 4."""
    b, i, h = 1024, 1, 4
    text = _compiled_text(
        functools.partial(ops.lstm_cell, interpret=False),
        _spec((b, i), one_chip), _spec((b, h), one_chip),
        _spec((b, h), one_chip), _spec((4 * h, i), one_chip),
        _spec((4 * h, h), one_chip), _spec((4 * h,), one_chip))
    assert "tpu_custom_call" in text

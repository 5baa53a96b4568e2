"""The main path's device programs compile for a described v5e at the
widths chip_smoke.py runs: ``SparseLinearModel(2**20).train_step`` on
one chip, ``sharded_spmv`` and ``make_sharded_train_step`` on a 2x2
mesh. Nothing runs — this is the chip's compiler refusing a program
here instead of on the chip (on-chip-measurement guide, section 2).

The topology is described inside fixtures only: loading the TPU
compiler at import would make every xdist worker take libtpu's lock.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

ROWS, NNZ = 8192, 8192 * 45   # chip_smoke.py's batch shape
FEATURES = 2 ** 20


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2. A failure to describe it fails the tests:
    the chip's compiler is what this file checks. The compile cache is
    off while the module runs (a compile for a described chip is written
    to the persistent cache but cannot be read back without one) and is
    put back as it was for the rest of the worker's tests."""
    from jax.experimental import compilation_cache, topologies
    cache_was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)
            compilation_cache.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _batch(sharding, lead=()):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)
    return {"offset": s((ROWS + 1,), jnp.int32),
            "label": s((ROWS,), jnp.float32),
            "weight": s((ROWS,), jnp.float32),
            "index": s((NNZ,), jnp.uint32),
            "value": s((NNZ,), jnp.float32)}


def _params(sharding):
    return {"w": jax.ShapeDtypeStruct((FEATURES,), jnp.float32,
                                      sharding=sharding),
            "b": jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)}


def _nnz_indexed(hlo: str, nnz: int) -> int:
    """Gathers and scatters in compiled HLO text whose index operand (the
    second) has ``nnz`` entries; HLO names are unique in a module."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    ops = re.findall(r" (?:gather|scatter)\(%[\w.\-]+, (%[\w.\-]+)", hlo)
    return sum(shapes[i].split(",")[0] == str(nnz) for i in ops)


def test_train_step_one_chip(topo):
    from dmlc_tpu.models import SparseLinearModel
    one = SingleDeviceSharding(topo.devices[0])
    model = SparseLinearModel(FEATURES)
    compiled = SparseLinearModel.train_step.lower(
        model, _params(one), _batch(one)).compile()
    mem = compiled.memory_analysis()
    # params in and out, one batch, scratch: far inside 16 GB of HBM
    assert mem.argument_size_in_bytes < 64 << 20
    # row ids are counted, not searched: no serial loop in the step
    hlo = compiled.as_text()
    assert " while(" not in hlo
    # row sums by a segmented scan: the only nnz-long gather and scatter
    # are the weight gather and the gradient's scatter-add
    assert _nnz_indexed(hlo, NNZ) == 2


def test_sharded_spmv(mesh):
    from dmlc_tpu.ops.csr import sharded_spmv
    data = NamedSharding(mesh, P("data"))
    w = jax.ShapeDtypeStruct((FEATURES,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda b, w: sharded_spmv(b, w, mesh)).lower(
        _batch(data, (4,)), w).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_train_step_all_reduces(mesh):
    from dmlc_tpu.models import SparseLinearModel
    model = SparseLinearModel(FEATURES)
    step = model.make_sharded_train_step(mesh)
    compiled = step.lower(
        _params(NamedSharding(mesh, P())),
        _batch(NamedSharding(mesh, P("data")), (4,))).compile()
    assert "all-reduce" in compiled.as_text()

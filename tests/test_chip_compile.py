"""Compile the TCAM match kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with JAX, lowers and compiles each
program for a chip that is described and not attached.  It refuses what the
interpret-mode tests cannot see — a block whose last two dimensions break
the (8, 128) tiling, or more VMEM than a kernel may use — so these cases
guard the served layouts at real widths.  The topology is described inside a
fixture: only the test process that runs this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import (CellOperands, match_cells, place_cells,
                           serve_batch, serve_group)

# Give-Me-Some-Credit at S=128 (Table II shape, DATASETS["credit"]):
# 8576 physical rows, 39 column divisions.
CREDIT = dict(s=128, d=39, r=8576)
# scikit-learn's default 100-tree forest on the COVID-19 shape at S=128:
# one plan group of 100 banks, 4096 rows, 4 divisions.
COVID_RF100 = dict(g=100, s=128, d=4, r=4096)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a chip's compile cannot be read back without the chip: keep the
    # persistent cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operand_shapes(engine, s, d, r, lead=(), block_r=128):
    """Shapes and dtypes ``place_cells`` gives a (*lead, r, d·s) grid."""
    rp = r + (-r) % block_r
    km = ((*lead, d, 1, rp), jnp.int32)
    if engine == "mxu":
        plane = ((*lead, d, s, rp), jnp.float32)
    else:
        plane = ((*lead, d, s // 32, rp), jnp.uint32)
    return [plane, plane, km]


def _operands(sharding, engine, s, d, r, lead=()):
    arrays = tuple(jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
                   for shape, dt in _operand_shapes(engine, s, d, r, lead))
    return CellOperands(arrays=arrays, engine=engine, s=s, rows=r,
                        block_r=128)


@pytest.mark.parametrize("engine", ["mxu", "packed"])
def test_operand_shapes_match_placement(engine):
    """The described operands are the ones the server places."""
    rng = np.random.default_rng(0)
    cells = rng.integers(0, 3, size=(2, 200, 3 * 64)).astype(np.int8)
    ops = place_cells(cells, 64, engine=engine)
    want = _operand_shapes(engine, 64, 3, 200, lead=(2,))
    got = [(a.shape, a.dtype) for a in ops.arrays]
    assert got == [(shape, jnp.dtype(dt)) for shape, dt in want]


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel
    return compiled


@pytest.mark.parametrize("engine", ["mxu", "packed"])
def test_credit_width_batch_compiles(one_chip, engine):
    """The server's whole batch program for the credit tree, bucket 256."""
    ops = _operands(one_chip, engine, **CREDIT)
    classes = jax.ShapeDtypeStruct((CREDIT["r"],), jnp.int32,
                                   sharding=one_chip)
    x = jax.ShapeDtypeStruct((256, CREDIT["d"] * CREDIT["s"]), jnp.uint8,
                             sharding=one_chip)
    compiled = _compile_for_chip(
        lambda o, c, xp: serve_batch(o, c, xp, interpret=False),
        ops, classes, x)
    mem = compiled.memory_analysis()
    # the grid is an argument, not baked into the program
    assert mem.argument_size_in_bytes > mem.generated_code_size_in_bytes


@pytest.mark.parametrize("engine,s,b", [
    ("mxu", 16, 256),
    ("mxu", 64, 256),
    ("packed", 64, 256),
    ("mxu", 32, 8),        # smallest serving bucket
    ("packed", 32, 8),
])
def test_narrow_tiles_compile(one_chip, engine, s, b):
    ops = _operands(one_chip, engine, s=s, d=4, r=512)
    x = jax.ShapeDtypeStruct((b, 4 * s), jnp.uint8, sharding=one_chip)
    _compile_for_chip(lambda o, xp: match_cells(o, xp, interpret=False),
                      ops, x)


def test_forest_mxu_vmapped_banks_compile(one_chip):
    """The forest 'mxu' engine: the kernel vmapped over a stack of banks."""
    ops = _operands(one_chip, "mxu", s=128, d=2, r=256, lead=(4,))
    x = jax.ShapeDtypeStruct((4, 256, 2 * 128), jnp.uint8, sharding=one_chip)
    _compile_for_chip(lambda o, xp: match_cells(o, xp, interpret=False),
                      ops, x)


@pytest.mark.parametrize("engine", ["banked", "mxu"])
def test_forest_group_program_compiles(one_chip, engine):
    """The server's whole batch program for one forest plan group at the
    100-tree forest's size, bucket 256: it copies back (3, G, B) int32."""
    g, s, d, r = (COVID_RF100[k] for k in ("g", "s", "d", "r"))
    if engine == "banked":
        arrays = tuple(
            jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((g, r, d * s), jnp.uint8)] * 2
            + [((g, r, d), jnp.int32)])
        ops = CellOperands(arrays=arrays, engine=engine, s=s, rows=r,
                           block_r=128)
    else:
        ops = _operands(one_chip, engine, s, d, r, lead=(g,))
    per_bank = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((g, 256, d * s), jnp.uint8, sharding=one_chip)
    compiled = jax.jit(
        lambda o, rows, d_real, xp: serve_group(o, rows, d_real, xp,
                                                interpret=False)
    ).lower(ops, per_bank, per_bank, x).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (engine == "mxu")
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((3, g, 256), jnp.int32)

"""Compile rehearsal for the TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached, so the main
path's kernels and jitted steps are compiled here for a described
``v5e:2x2`` topology at the widths ``chip_smoke.py`` runs (MovieLens-1M
shape, rank 32).  A compile that the chip's compiler would refuse (a tile
not aligned to the layout, more VMEM than a kernel may use, a program that
does not fit HBM) fails here, at no chip time.  Nothing runs: these tests
say nothing about results or speed.

The topology is described inside a module fixture, never at import, so
every pytest worker collects the same tests and only the worker running
this file loads the TPU library.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.config import GossipMCConfig
from repro.core import gossip, waves
from repro.core import objective as obj
from repro.core.state import State
from repro.kernels.quant.kernel import dequant_score_pallas
from repro.kernels.sddmm.autotune import resolve_chunk
from repro.kernels.sddmm.ops import _MAX_VMEM_BYTES, segment_vmem_bytes
from repro.kernels.sddmm.segment_kernel import sddmm_segment_grad_pallas
from repro.mesh import MeshPlan
from repro.sparse.entries import BlockEntries
from repro.sparse.store import (DEFAULT_BUCKET, SparseProblem,
                                TILE_BYTES_PER_CELL, bucketed_capacity,
                                tile_rule, tile_shape)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 2**30          # one v5e chip
LANE, BE = 128, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ml1m():
    """The smoke's deployment: its size, ratings and append headroom."""

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    size = cs.ML1M
    ds, _, _, headroom = cs.deployment(size, seed=0)
    rows, cols = np.nonzero(ds.train_mask)

    def block(g, headroom=0):
        """(mb, nb, capacity) of the sparse store on a g x g grid."""

        mb, nb = -(-size.users // g), -(-size.items // g)
        nnz = np.bincount(rows // mb * g + cols // nb, minlength=g * g)
        return mb, nb, bucketed_capacity(int(nnz.max()), DEFAULT_BUCKET,
                                         headroom)

    return size, block, headroom


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store(p, q, mb, nb, E, sharding, tile=None):
    """SparseProblem of shapes: every leaf stacked over the (p, q) grid,
    with the dense masked tile in the encoding ``tile`` names."""

    i32, f32 = jnp.int32, jnp.float32
    grid = (p, q)
    tiles = dict.fromkeys(("tile_vals", "tile_mask", "tile_codes",
                           "tile_grid"))
    if tile is not None:
        shape = grid + tile_shape(mb, nb, tile)
    if tile == "f32":
        tiles.update(tile_vals=_sds(shape, f32, sharding),
                     tile_mask=_sds(shape, jnp.bool_, sharding))
    elif tile == "codes":
        tiles.update(tile_codes=_sds(shape, jnp.uint8, sharding),
                     tile_grid=_sds(grid + (2,), f32, sharding))
    return SparseProblem(
        BlockEntries(
            rows=_sds(grid + (E,), i32, sharding),
            cols=_sds(grid + (E,), i32, sharding),
            vals=_sds(grid + (E,), f32, sharding),
            valid=_sds(grid + (E,), f32, sharding),
            col_perm=_sds(grid + (E,), i32, sharding),
            row_ptr=_sds(grid + (mb + 1,), i32, sharding),
            col_ptr=_sds(grid + (nb + 1,), i32, sharding),
            **tiles,
        ),
        _sds(grid, i32, sharding),
    )


def _state(p, q, mb, nb, r, sharding):
    f32 = jnp.float32
    return State(_sds((p, q, mb, r), f32, sharding),
                 _sds((p, q, nb, r), f32, sharding),
                 _sds((), jnp.int32, sharding))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _round_up(x, m):
    return -(-x // m) * m


@pytest.mark.parametrize("side", ["u", "w"])
def test_segment_kernel_compiles_at_largest_admitted_block(topo, one_chip,
                                                           ml1m, side):
    """The largest ML-1M block the VMEM guard in ``kernels/sddmm/ops.py``
    lets through to the kernel compiles for the chip: the guard's estimate
    is not below what Mosaic needs."""

    size, block, _ = ml1m
    admitted = [(g, *block(g)) for g in range(1, 17)
                if segment_vmem_bytes(*block(g)[:2], size.rank,
                                      block(g)[2]) <= _MAX_VMEM_BYTES]
    g, mb, nb, E = admitted[0]                  # smallest grid = largest block
    m_pad, n_pad = _round_up(mb, 8), _round_up(nb, 8)
    e_pad = _round_up(E + 1, BE)
    s = m_pad if side == "u" else n_pad
    i32, f32 = jnp.int32, jnp.float32
    args = [_sds((1, e_pad), dt, one_chip) for dt in (i32, i32, f32, f32)]
    args += [_sds((1, s), i32, one_chip)] * 2
    args += [_sds((m_pad, LANE), f32, one_chip),
             _sds((n_pad, LANE), f32, one_chip)]
    compiled = sddmm_segment_grad_pallas.lower(
        *args, side=side, be=BE, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert g <= size.kernel_grid, (g, mb, nb)


def test_int8_score_kernel_compiles_at_top_bucket(one_chip, ml1m):
    size, _, _ = ml1m
    b = size.buckets[-1]
    bn = 512
    n_pad = _round_up(size.items, bn)
    i8, f32 = jnp.int8, jnp.float32
    compiled = dequant_score_pallas.lower(
        _sds((b, LANE), i8, one_chip), _sds((b, 1), f32, one_chip),
        _sds((n_pad, LANE), i8, one_chip), _sds((1, n_pad), f32, one_chip),
        bn=bn, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _wave_tables(p, q, sharding):
    tables = max(waves.wave_tables(p, q), key=lambda t: t.blocks.shape[0])
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tables)


def test_wave_step_fits_one_chip_at_ml1m(one_chip, ml1m):
    """The smoke's training step (XLA segment path, 4x4 grid, the store
    with its append headroom) compiles and fits one chip's HBM."""

    size, block, headroom = ml1m
    g = size.grid
    mb, nb, E = block(g, headroom)
    compiled = waves.wave_step.lower(
        _store(g, g, mb, nb, E, one_chip),
        _state(g, g, mb, nb, size.rank, one_chip),
        _wave_tables(g, g, one_chip),
        rho=1e2, lam=1e-6, a=1e-3, b=5e-7,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("tile", ["codes", "f32"])
def test_wave_step_with_tile_fits_one_chip_at_ml1m(one_chip, ml1m, tile):
    """The smoke's training step on dense masked tiles (the path the
    ML-1M blocks take on the chip) compiles and fits one chip's HBM, and
    reads each block's tile inside its products: no block is copied out
    first, so its temporaries stay under one block's tile in its
    encoding (four fifths of it: a copy of the block's cells would pass
    that).  ``"codes"`` is what ingest builds there, integer ratings
    minus their mean; ``"f32"`` (values + mask) what stores off a grid
    take."""

    size, block, headroom = ml1m
    g = size.grid
    mb, nb, E = block(g, headroom)
    assert tile_rule(E, mb, nb, g * g, "tpu", HBM_BYTES, tile)
    tables = _wave_tables(g, g, one_chip)
    compiled = waves.wave_step.lower(
        _store(g, g, mb, nb, E, one_chip, tile=tile),
        _state(g, g, mb, nb, size.rank, one_chip),
        tables,
        rho=1e2, lam=1e-6, a=1e-3, b=5e-7,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    one_tile = mb * nb * TILE_BYTES_PER_CELL[tile] * 4 // 5
    assert compiled.memory_analysis().temp_size_in_bytes < one_tile


# the ML-10M deployment of bench/configs/ml10m-r64.json: 71,567 x 10,681
# on a 4x4 grid, rank 64, 573,440 slots a block
ML10M = dict(g=4, mb=17892, nb=2671, r=64, E=573440)


@pytest.mark.parametrize("tile", ["codes", None])
def test_wave_step_and_cost_fit_one_chip_at_ml10m(one_chip, tile):
    """The ML-10M training step and the chunk cost compile for the chip
    and fit it, one block per loop step.

    ``"codes"``: the store ingest builds there.  Its tiles of 1-byte
    codes (0.77 GB for all sixteen) pass ``tile_rule``; float32 tiles
    with a mask (3.85 GB) would not.  A visit's temporaries are its
    tile's float32 residual and product: under three blocks' residuals,
    where the sixteen the chunk cost once vmapped took 3.1 GB each.

    ``None``: tile-less blocks (a mesh-placed store), on the chip's
    segment chunk.  A visit's temporaries are one block's per-entry
    (E, r) float32 arrays: the row gathers, the contributions and their
    chunk prefixes, at most eight alive at once (1.17 GB).  A wave
    vmapped over its twelve blocks needs 1.76 GB for a single such
    array, and the slab gather of the engine this one replaced took
    13.1 GB alone."""

    g, mb, nb, r, E = (ML10M[k] for k in ("g", "mb", "nb", "r", "E"))
    assert tile_rule(E, mb, nb, g * g, "tpu", HBM_BYTES, "codes")
    assert not tile_rule(E, mb, nb, g * g, "tpu", HBM_BYTES, "f32")
    if tile is None:
        bound = 8 * E * r * 4
    else:
        tm, tn = tile_shape(mb, nb, tile)
        bound = 3 * tm * tn * 4
    store = _store(g, g, mb, nb, E, one_chip, tile=tile)
    state = _state(g, g, mb, nb, r, one_chip)
    step = waves.wave_step.lower(
        store, state, _wave_tables(g, g, one_chip),
        rho=1e2, lam=1e-6, a=1e-3, b=5e-7,
        chunk=resolve_chunk(None, backend="tpu"),
    ).compile()
    cost = jax.jit(lambda sp, u, w: obj.total_cost(sp, u, w, 1e-6)).lower(
        store, state.U, state.W).compile()
    for compiled in (step, cost):
        assert _device_bytes(compiled) < HBM_BYTES
        assert compiled.memory_analysis().temp_size_in_bytes < bound


def test_wave_step_with_kernel_compiles_at_kernel_grid(one_chip, ml1m,
                                                      monkeypatch):
    """The smoke's ``use_kernel=True`` fit: the segment kernel inside the
    wave step, one block per loop step, compiles to Mosaic on the kernel
    grid."""

    # the kernel wrappers pick interpret mode off-TPU from the default
    # backend, which is the CPU here: steer them to the chip's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    size, block, _ = ml1m
    g = size.kernel_grid
    mb, nb, E = block(g)
    compiled = waves.wave_step.lower(
        _store(g, g, mb, nb, E, one_chip),
        _state(g, g, mb, nb, size.rank, one_chip),
        _wave_tables(g, g, one_chip),
        rho=1e2, lam=1e-6, a=1e-3, b=5e-7, use_kernel=True,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_gossip_step_compiles_for_four_chips(topo, ml1m):
    """``chip_smoke.py --chips 4``: gossip rounds on a 2x2 mesh of
    described chips, 2x2 blocks per chip, with the halo collectives."""

    size, block, _ = ml1m
    g = size.grid
    mb, nb, E = block(g)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    plan = MeshPlan.build(g, g, mesh=mesh)
    cfg = GossipMCConfig(m=mb * g, n=nb * g, p=g, q=g, rank=size.rank,
                         rho=1e2, lam=1e-6, a=1e-3, b=5e-7)
    step, (problem_spec, carry_spec) = gossip.make_gossip_step(
        None, (g, g), cfg, plan=plan, steps_per_call=size.gossip_rounds,
        layout="sparse")
    placed = lambda tree, specs: jax.tree.map(  # noqa: E731
        lambda x, s: _sds(x.shape, x.dtype, NamedSharding(mesh, s)),
        tree, specs)
    store = _store(g, g, mb, nb, E, None)
    carry = jax.eval_shape(gossip.init_carry,
                           _state(g, g, mb, nb, size.rank, None))
    compiled = step.lower(placed(store, problem_spec),
                          placed(carry, carry_spec)).compile()
    text = compiled.as_text()
    assert "collective-permute" in text
    assert _device_bytes(compiled) < HBM_BYTES

"""The admission path's device kernels, compiled for a described TPU v5e
chip — no chip attached, nothing runs.

One realistic admission chunk (the first memory chunk of a 4096-job
planner-scale batch, ~19.8k cells) is laid out by the same host code the
planner uses, and three programs are compiled for one v5e chip at its
shapes with ``interpret=False``: the Pallas rate/prefix kernel, the
Pallas per-cell sweep kernel, and the jitted lattice kernel
(``grid_jax._kernel``, under x64). A compile that the chip's compiler
refuses — a 64-bit type in a kernel, a block that does not tile, more
VMEM than a kernel may use, a program larger than device memory — fails
here at no chip time.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every pytest worker imports
this file.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.scheduler import grid_jax, grid_pallas  # noqa: E402
from repro.core.scheduler.planner import CarbonPlanner  # noqa: E402
from repro.core.workloads.scenarios import (  # noqa: E402
    PLANNER_SCALE_FTNS, planner_scale_job)

V5E_HBM = 16 * 2 ** 30
DT_S, STRIDE, SLOT_S = 60.0, 60, 3600.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def chunk():
    """Cells and SLA rows of the first memory chunk of a 4096-job batch."""
    pl = CarbonPlanner(list(PLANNER_SCALE_FTNS), batch_backend="pallas")
    cells, sla, _ = pl._batch_cells(
        [planner_scale_job(i) for i in range(4096)], DT_S, STRIDE)
    idx = next(grid_jax._iter_chunks(cells, STRIDE, grid_jax._MAX_ELEMS))
    assert len(idx) > 16_000, "the chunk should be fleet-sized"
    return pl.field, [cells[j] for j in idx], np.asarray(sla)[idx]


@pytest.fixture(scope="module")
def kernel_inputs(chunk):
    field, cells, sla = chunk
    return grid_pallas._kernel_inputs(field, cells, sla, dt_s=DT_S,
                                      slot_stride=STRIDE, slot_s=SLOT_S,
                                      scale_fn=None)


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                sharding=sharding)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM, mem


def test_pallas_rate_kernel_compiles_for_v5e(one_chip, kernel_inputs):
    x = kernel_inputs
    assert all(np.asarray(a).dtype.itemsize == 4 for a in x)
    args = [_spec(a, one_chip) for a in (x.hp, x.zk, x.hk, x.ti, x.tf)]
    compiled = jax.jit(
        grid_pallas._rate_table,
        static_argnames=("stride", "dt_s", "interpret")).lower(
            *args, stride=STRIDE, dt_s=DT_S, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_pallas_sweep_kernel_compiles_for_v5e(one_chip, kernel_inputs):
    x = kernel_inputs
    a_pad, _, h_hops, width = x.zk.shape
    tab = jax.ShapeDtypeStruct((a_pad, STRIDE, 3, h_hops, width),
                               np.float32, sharding=one_chip)
    args = [_spec(a, one_chip)
            for a in (x.pidx, x.ph, x.sh, x.nval, x.w, x.cf, x.scl)]
    compiled = jax.jit(
        grid_pallas._sweep,
        static_argnames=("dt_s", "slot_s", "interpret")).lower(
            tab, *args, dt_s=DT_S, slot_s=SLOT_S, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


def test_lattice_kernel_compiles_for_v5e(one_chip, chunk):
    field, cells, _ = chunk
    t = grid_jax._chunk_tables(field, cells, dt_s=DT_S, slot_stride=STRIDE,
                               cell_bucket=grid_jax._B_CELLS)
    args = [*t.zcols, t.znoise, t.cal_a, t.cal_b, t.h_of_day0,
            t.day_frac_s, np.int32(t.dow0), t.rel0a, t.anchor_idx,
            t.zone_idx, t.band, t.hnoise, t.path_idx, t.pair_idx, t.w_dev,
            t.n_steps, t.rem]
    with jax.enable_x64(True):
        compiled = jax.jit(grid_jax._kernel, static_argnames=(
            "n_grid", "n_slots", "slot_stride", "dt_s", "n_dev",
            "mesh")).lower(
                *[_spec(a, one_chip) for a in args], n_grid=t.n_grid_pad,
                n_slots=t.n_slots_pad, slot_stride=STRIDE, dt_s=DT_S,
                n_dev=1).compile()
    _assert_fits(compiled)

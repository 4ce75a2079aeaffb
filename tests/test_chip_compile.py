"""The engine's device programs compile for a TPU v5e.

Every program `chip_smoke.py` dispatches is compiled here for a
*described* v5e:2x2 topology — the TPU compiler runs on the host and
refuses what the chip would refuse (unsupported layouts, programs that
do not fit), at no chip time.  Nothing runs, so nothing here is a
result or a speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under several
test workers an import-time call would leave the others unable to
collect.  The persistent compilation cache is off around these
compiles: an executable for a described device cannot be read back.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.compat import enable_x64
from repro.core import engine_jax as ej

V5E_HBM_BYTES = 16 * 1024 ** 3

# chip_smoke.py's shapes: the sweep phase pads 2048 lanes; deadline
# lanes build a 96-row table per 4-day chunk with 32 progress buckets,
# against a 51-member carbon ensemble.  The fleet phase runs 256 groups
# of 2 campaigns (512 lanes, 512 padded groups) with 24-row periodic
# tables and one carbon column.
SWEEP = dict(A=2048, R=96, B=32, C=96, E=51)
FLEET = dict(A=512, R=24, B=1, C=96, E=1, G=512)
N_CHIPS = 4


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices[:N_CHIPS]), ("lanes",))


def _dtypes(precision):
    return ((jnp.float32, jnp.float64) if precision == "mixed"
            else (jnp.float64, jnp.float64))


def _plain_args(sharding, A, R, B, C, E, precision="fp64"):
    """Argument shapes of `_scan_chunk_jax_impl`, in call order."""
    cdt, adt = _dtypes(precision)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    ins = (s((A, R, B), cdt), s((A, R, B), cdt), s((A, C), jnp.int32),
           s((A, C), cdt), s((A, E, C), cdt), s((A, C), cdt), s((A, C), cdt))
    state = (s((A,), adt), s((A,), adt), s((A,), adt), s((A, E), adt),
             s((A,), adt))
    return ins, state, tuple(s((A,), cdt) for _ in range(8))


def _coupled_args(sharding, A, R, B, C, E, G, precision="fp64"):
    """Argument shapes of `_scan_chunk_jax_coupled_impl`, in call order."""
    cdt, adt = _dtypes(precision)
    ins, state, scalars = _plain_args(sharding, A, R, B, C, E, precision)
    group = (jax.ShapeDtypeStruct((A,), jnp.int32, sharding=sharding),
             jax.ShapeDtypeStruct((G,), cdt, sharding=sharding),
             jax.ShapeDtypeStruct((G, C), cdt, sharding=sharding))
    speak = jax.ShapeDtypeStruct((A,), adt, sharding=sharding)
    return ins + group + state + (speak,) + scalars


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    return used


@pytest.mark.parametrize("precision", ["fp64", "mixed"])
def test_plain_chunk_compiles_for_v5e(one_chip, precision):
    ins, state, scalars = _plain_args(one_chip, **SWEEP, precision=precision)
    with enable_x64():
        compiled = ej._scan_chunk_jax.lower(
            *ins, *state, *scalars, B=SWEEP["B"]).compile()
    _fits(compiled)


@pytest.mark.parametrize("precision", ["fp64", "mixed"])
def test_coupled_chunk_compiles_for_v5e(one_chip, precision):
    args = _coupled_args(one_chip, **FLEET, precision=precision)
    with enable_x64():
        compiled = ej._scan_chunk_jax_coupled.lower(
            *args, B=FLEET["B"], G=FLEET["G"]).compile()
    _fits(compiled)


def test_sharded_plain_chunk_compiles_on_four_chips(mesh):
    """The `shard_map` lane sharding `_sharded_plain` builds, over the
    described chips: no collective is needed (lanes never interact)."""
    spec = PartitionSpec("lanes")
    ins, state, scalars = _plain_args(NamedSharding(mesh, spec), **SWEEP)
    fn = jax.jit(jax.shard_map(
        functools.partial(ej._scan_chunk_jax_impl, B=SWEEP["B"]),
        mesh=mesh, in_specs=(spec,) * 20, out_specs=(spec,) * 5,
        check_vma=False))
    with enable_x64():
        compiled = fn.lower(*ins, *state, *scalars).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "all-reduce" not in hlo and "all-gather" not in hlo


def test_sharded_coupled_chunk_compiles_on_four_chips(mesh):
    """The group-partitioned coupled sharding `_sharded_coupled` builds:
    each chip holds its own groups (G per chip), so the site-cap
    segment sums stay on the chip."""
    spec = PartitionSpec("lanes")
    shape = dict(FLEET, G=FLEET["G"] // N_CHIPS * N_CHIPS)
    args = _coupled_args(NamedSharding(mesh, spec), **shape)
    fn = jax.jit(jax.shard_map(
        functools.partial(ej._scan_chunk_jax_coupled_impl, B=FLEET["B"],
                          G=FLEET["G"] // N_CHIPS),
        mesh=mesh, in_specs=(spec,) * 24, out_specs=(spec,) * 6,
        check_vma=False))
    with enable_x64():
        compiled = fn.lower(*args).compile()
    _fits(compiled)
    assert "all-reduce" not in compiled.as_text()


def test_trace_objective_value_and_grad_compiles_for_v5e(one_chip):
    """The optimizer's differentiated objective at a 256-candidate
    population over a 292-slot horizon (chip_smoke's optimize phase)."""
    from repro.core import (MachineProfile, SweepCase, TraceSignal,
                            calibrate_workload, parametric_schedule)
    from repro.core.workload import OEM_CASE_1

    wl, m = calibrate_workload(OEM_CASE_1, MachineProfile())
    trace = TraceSignal(tuple(0.3 + 0.2 * np.sin(np.arange(400) / 3.8)))
    to = ej.TraceObjective(SweepCase(parametric_schedule(24), wl, m,
                                     carbon=trace, deadline_h=214.0),
                           horizon_h=214.0 * 1.25 + 24.0)

    def loss(u):
        met = to.evaluate(u)
        return jnp.sum(met.co2_kg + 1e3 * met.unfinished)

    u = jax.ShapeDtypeStruct((256, 24), jnp.float64, sharding=one_chip)
    with enable_x64():
        compiled = jax.jit(jax.value_and_grad(loss)).lower(u).compile()
    _fits(compiled)

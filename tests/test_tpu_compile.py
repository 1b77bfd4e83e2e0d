"""Compile the main path for a TPU v5e that is described, not attached.

The Pallas switch kernel at both tier shapes of the Fig 2 site (plain
and vmapped over a scenario batch) and the whole FBSite() chunk program
with the kernel in it, on one chip and sharded over a 2x2 mesh. Nothing
runs: this catches what Mosaic or XLA:TPU refuses (unsupported
primitives and layouts, a kernel that cannot be partitioned, a program
that does not fit) before any chip time is spent. The chunk program
must also keep the names a profile is read by: the kernel's
``lcdc_switch_step`` under each tier's scope, and every part scope.

The topology is described inside a fixture: loading the TPU compiler
at import time would break collection under several pytest workers.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo
from repro.core import simulator as S
from repro.kernels import lcdc_switch, ops

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(monkeypatch):
    """No persistent cache (a TPU executable written here cannot be read
    back without a chip), and ``ops.switch_step`` steered to the
    Pallas kernel as on a TPU backend."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(x, sharding, rows=None):
    shape = tuple(x.shape)
    if rows is not None:
        shape = (rows,) + shape[1:]
    return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)


def _footprint(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


#: the step's part scopes and the fold's and guard's, which a profile
#: reads from the ops' op_name (chipbench/progtrace.py), and the nested
#: ``faults`` (chipbench/metrics/faults_us_per_tick.py)
SCOPES = ("edge", "flows", "rsw", "csw", "down", "stats", "gating", "fold",
          "guard", "draws", "faults")


def _assert_names(txt):
    """The switch kernel runs under its own name once in each tier's
    scope, and every part scope is in the program's op_names."""
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="[^"]*/vmap\((\w+)\)/lcdc_switch_step/'
                       r'pallas_call"', txt)
    assert sorted(calls) == ["csw", "rsw"]
    for scope in SCOPES:
        assert re.search(rf'op_name="[^"]*[/(]{scope}[)/]', txt), scope


# (name, switches, links, components, serve rate): the Fig 2 site's
# RSW tier (128 racks, 4 uplinks, [intra, inter]) and CSW tier (16 CSWs,
# 4 40G uplinks)
TIERS = [("rsw", 128, 4, 2, 1.0), ("csw", 16, 4, 1, 4.0)]


@pytest.mark.parametrize("batch", [None, 10])
@pytest.mark.parametrize("tier", TIERS, ids=[t[0] for t in TIERS])
def test_switch_kernel_compiles_for_v5e(one_chip, tpu_compile, tier,
                                        batch):
    _, n, L, K, rate = tier
    pre = () if batch is None else (batch,)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(pre + shape, dtype, sharding=one_chip)

    args = (arg((n, L, K), jnp.float32), arg((n,), jnp.int32),
            arg((n, K), jnp.float32), arg((n,), jnp.bool_),
            arg((n, L), jnp.bool_), arg((), jnp.float32))

    def step(q, stage, arr, drain, valid, cap):
        return lcdc_switch.switch_step(q, stage, arr, drain, valid=valid,
                                       cap=cap, serve_rate=rate,
                                       interpret=False)

    fn = step if batch is None else jax.vmap(step)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _footprint(compiled.memory_analysis()) < 1 << 20


def _chunk_operands():
    """Shapes of the chunk program's operands for phase (b) of
    chip_smoke.py: FBSite() x five traces x gating on/off."""
    batch = S.make_multi_site_batch(S.grid_runs())
    scen, state, fold, guard, tol = jax.eval_shape(
        lambda: S._prepare_sweep_args(batch, validate=True, shard=False))
    return batch, scen, state, fold, guard, tol


def test_chunk_program_compiles_for_one_v5e(one_chip, tpu_compile):
    """The FBSite() chunk program as the TPU backend builds it (carry
    donated), both switch kernels inside, no gather, the carry aliased
    in place, and well inside one chip's HBM."""
    batch, scen, state, fold, guard, tol = _chunk_operands()
    chunk = S.CHUNK_TICKS

    def put(tree):
        return jax.tree.map(lambda x: _spec(x, one_chip), tree)

    runner = jax.jit(S._sweep_chunk_impl,
                     static_argnames=("site", "length", "validate"),
                     donate_argnames=("state", "fold"))
    compiled = runner.lower(
        batch.hull, put(scen), put(state), chunk,
        _spec(jax.ShapeDtypeStruct((chunk,), jnp.bool_), one_chip),
        put(fold), put(guard),
        _spec(jax.ShapeDtypeStruct((), jnp.int32), one_chip),
        _spec(tol, one_chip), True).compile()
    txt = compiled.as_text()
    assert txt.count("tpu_custom_call") == 2     # the RSW and CSW tiers
    assert "gather(" not in txt                  # table lookups are selects
    _assert_names(txt)
    mem = compiled.memory_analysis()
    carry = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves((state, fold)))
    # every carry byte is aliased (the device's tiled layouts pad the
    # small leaves, so the aliased bytes exceed the logical ones)
    assert mem.alias_size_in_bytes >= carry
    assert _footprint(mem) < V5E_HBM_BYTES // 16


def test_sharded_chunk_program_compiles_for_v5e_2x2(topo, tpu_compile):
    """Scenario-axis sharding over four chips: the shard_mapped chunk
    program holds the per-device kernels and no collective (RL008's
    allow-list is empty)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(topo.devices), ("scen",))
    lane = NamedSharding(mesh, PartitionSpec("scen"))
    rep = NamedSharding(mesh, PartitionSpec())
    batch, scen, state, fold, guard, tol = _chunk_operands()
    rows = -(-len(batch) // 4) * 4               # padded to 4 devices

    def put(tree):
        return jax.tree.map(lambda x: _spec(x, lane, rows), tree)

    chunk = S.CHUNK_TICKS
    a_scen = put(scen)
    compiled = S._runner_for(a_scen).lower(
        batch.hull, a_scen, put(state), chunk,
        _spec(jax.ShapeDtypeStruct((chunk,), jnp.bool_), rep),
        put(fold), put(guard),
        _spec(jax.ShapeDtypeStruct((), jnp.int32), rep), _spec(tol, rep),
        True).compile()
    txt = compiled.as_text()
    assert txt.count("tpu_custom_call") == 2
    _assert_names(txt)
    assert hlo.parse_collectives(txt).by_op() == {}
    assert _footprint(compiled.memory_analysis()) < V5E_HBM_BYTES // 16

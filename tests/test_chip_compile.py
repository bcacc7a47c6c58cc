"""Compile the main path for one described TPU v5e chip.

Nothing runs here.  Each test lowers and compiles a jitted step at the
main path's widths -- the default `IngestConfig` (2^20 node slots, 2^21
edge slots, 8,192-edge batches) with uint64 keys -- for a chip that is
described, not attached, so what the chip's compiler refuses shows up
without chip time.  `repro.kernels.ops.IMPL` declares the XLA
implementation for every hot op, so no compiled program may hold a
Mosaic kernel (`tpu_custom_call`).

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler's library at a time, and every
test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compress.dictionary import init_dictionary
from repro.compress.stage import rewrite_table
from repro.configs.paper_ingest import IngestConfig
from repro.core.edge_table import build_edge_table
from repro.graphstore.store import commit_compressed, ingest_step, init_store
from repro.kernels import ops
from repro.query.sketch import init_sketch, sketch_update

CFG = IngestConfig()
N = CFG.max_edges_per_batch
SAMPLER_BLOCK = 2048  # ScenarioSource's default block
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here, or its library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an executable compiled for a described chip cannot be read back
        # without one: keep these compiles out of any persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def x64():
    with jax.enable_x64(True):
        yield


def _on(chip, tree):
    """Abstract shapes of `tree`, placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)


def _compile(chip, jitted, *shapes):
    compiled = jitted.lower(*[_on(chip, s) for s in shapes]).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    return compiled


def _vec(dtype, n=N):
    return jax.ShapeDtypeStruct((n,), dtype)


def _edge_table():
    return jax.eval_shape(build_edge_table, _vec(jnp.uint64), _vec(jnp.uint64),
                          _vec(jnp.int32), _vec(jnp.bool_))


def _store():
    return jax.eval_shape(lambda: init_store(CFG.store_nodes, CFG.store_edges,
                                             key_dtype=jnp.uint64))


def test_build_edge_table_compiles_for_v5e(one_chip, x64):
    _compile(one_chip, build_edge_table, _vec(jnp.uint64), _vec(jnp.uint64),
             _vec(jnp.int32), _vec(jnp.bool_))


def test_ingest_step_compiles_for_v5e(one_chip, x64):
    compiled = _compile(one_chip, ingest_step, _store(), _edge_table())
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES


def test_commit_compressed_compiles_for_v5e(one_chip, x64):
    dct = jax.eval_shape(lambda: init_dictionary(4096, jnp.uint64))
    _, cc = jax.eval_shape(
        functools.partial(rewrite_table, star_min=4, hot_min=2),
        dct, _edge_table())
    _compile(one_chip, commit_compressed, _store(), cc)


def test_sketch_update_compiles_for_v5e(one_chip, x64):
    sketch = jax.eval_shape(lambda: init_sketch(depth=4, width=256, hh_slots=64,
                                                key_dtype=jnp.uint64))
    _compile(one_chip, sketch_update, sketch, _edge_table())


def test_traffic_sampler_compiles_for_v5e(one_chip, x64):
    sample = jax.jit(lambda seed, ctr0, ip, fp: ops.traffic_sample(
        seed, ctr0, SAMPLER_BLOCK, ip, fp))
    u32 = jax.ShapeDtypeStruct((), jnp.uint32)
    _compile(one_chip, sample, u32, u32, _vec(jnp.int32, 4),
             _vec(jnp.float32, 5))


def test_pattern_mine_compiles_for_v5e(one_chip, x64):
    mine = jax.jit(functools.partial(ops.pattern_mine, star_min=4, hot_min=2))
    _compile(one_chip, mine, _vec(jnp.uint64), _vec(jnp.uint64),
             _vec(jnp.int32), _vec(jnp.int32), _vec(jnp.bool_))

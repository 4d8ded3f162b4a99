"""Rehearsal 3 of the on-chip guide for the benchmark's cells: the batched
step of ``gpt2-large.seq16`` and the single steps of both configurations
compile for a described ``v5e:2x2`` at the published sizes, from shapes alone.

No weight is drawn: the decoder's ``_build`` runs under ``jax.eval_shape``
with numpy's generator replaced by one that hands out abstract zeros, which
leaves the program's own jitted step and the shapes of its parameters and
caches. ``memory_analysis()`` of each compile is what PERF.md's reckoning of
the cells' bytes quotes (run with ``-s`` to see it).
"""

import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


class _AbstractNormals:
    def standard_normal(self, shape):
        import jax.numpy as jnp

        return jnp.zeros(shape, jnp.float32)


# the third is a cell PERF.md keeps for a later PR: the first cell's model on
# the stream path
DEFERRED = {"gpt2-large.stream16": (
    "gpt2-large", {"builder": "tiny_lm_generate", "args": {}})}


def _cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if name in DEFERRED:
        config_name, cell = DEFERRED[name]
    else:
        config_name = next(
            w for w in bench["workloads"] if w["name"] == name)["config"]
        with open(os.path.join(ROOT, "benchmark", "cells", name + ".json")) as f:
            cell = json.load(f)
    config = next(c for c in bench["configs"] if c["name"] == config_name)
    with open(os.path.join(ROOT, config["file"])) as f:
        return json.load(f), cell


def _on(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("name", [
    "gpt2-large.seq16", "cerebras-gpt-1.3b.stream6", "gpt2-large.stream16"])
def test_cell_step_compiles_for_v5e(name, one_chip, no_compile_cache, monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import builders, family

    config, cell = _cell(name)
    model, decoder = builders.resolve(cell["builder"])(config, 0, **cell["args"])
    monkeypatch.setattr(np.random, "default_rng", lambda seed: _AbstractNormals())

    def build():
        model._ensure_built()
        caches = getattr(model, "_caches", None)
        return decoder._params, caches if caches is not None else decoder._fresh_cache()

    try:
        params, caches = jax.eval_shape(build)
    finally:
        model.unload()  # the batcher's worker thread, where there is one
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    assert n_params == family.arithmetic(config).total_params(config)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if hasattr(model, "_batched_step"):
        slots = model.slots
        row = lambda dtype: jax.ShapeDtypeStruct((slots,), dtype, sharding=one_chip)
        lowered = model._batched_step.lower(
            _on(params, one_chip), _on(caches, one_chip),
            row(jnp.int32), row(jnp.int32), row(jnp.bool_))
    else:
        lowered = decoder._step_fn.lower(
            _on(params, one_chip), _on(caches, one_chip), scalar, scalar)
    memory = lowered.compile().memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    print(f"\n{name}: arguments {memory.argument_size_in_bytes} outputs "
          f"{memory.output_size_in_bytes} temporaries {memory.temp_size_in_bytes} "
          f"aliased {memory.alias_size_in_bytes} -> {total} bytes of one v5e chip")
    assert total < V5E_HBM

"""Rehearsal 3 of the on-chip guide for the gated-convolution family's cell:
the round and the slot prefill of ``lfm2-24b-a2b.sharegpt32``, at both rungs
of the ladder, compile for a described ``v5e:2x2`` at the published widths,
from shapes alone, over the cell's own table of thirty-two slots; the table
is written where it lies (aliased to the outputs), weights, table and the
compiler's own count of temporaries fit one chip, and the grouped products
are kernels. No weight is drawn: the builder leaves the weights as shapes.
``memory_analysis()`` of each compile is what PERF.md's reckoning of the
cell's bytes quotes (run with ``-s`` to see it).
"""

import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E_HBM = 16e9
CELL = "lfm2-24b-a2b.sharegpt32"


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


@pytest.fixture(scope="module")
def cell():
    from benchmark import builders, run

    resolved = run.resolve_cell(ROOT, CELL)
    model, decoder = builders.resolve(resolved["cell"]["builder"])(
        resolved["config"], 0, **resolved["cell"]["args"])
    decoder._ensure_built()  # shapes alone: the builder draws nothing
    return resolved["config"], model, decoder


def _on(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _lowered(model, decoder, program, live, one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from client_tpu import ops
    from client_tpu.models.gated_conv_decoder import FED_TALLY

    # the grouped products compile for the chip, not for the interpreter of
    # the CPU this test runs on (on-chip guide, section 2: steered in the test)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    slots = model.slots
    params = _on(decoder._params, one_chip)
    table = _on(jax.eval_shape(lambda: decoder._fresh_table(slots)), one_chip)
    tables = _on(decoder._tables, one_chip)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    fed = ints(slots + FED_TALLY)
    if program == "round":
        return decoder._step_program.lower(
            params, table, tables, fed, ints(3, slots), live=live)
    return decoder._prefill_program.lower(
        params, table, tables, fed, ints(decoder.sizes.prefill_chunk), ints(5),
        live=live)


def test_the_builder_leaves_shapes_that_the_arithmetic_counts(cell):
    import jax

    from benchmark import family

    config, model, decoder = cell
    leaves = jax.tree_util.tree_leaves(decoder._params)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
    held = sum(int(np.prod(leaf.shape)) for leaf in leaves)
    arithmetic = family.arithmetic(config)
    assert held == arithmetic.total_params(config) == 5_312_168_704
    assert decoder.ladder() == (512, 2048) and model.slots == 32
    table = jax.eval_shape(lambda: decoder._fresh_table(model.slots))
    reserved = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(table))
    assert reserved == model.slots * (
        2048 * arithmetic.cache_row_bytes(config) + arithmetic.conv_state_bytes(config))


@pytest.mark.parametrize("program,live", [
    ("round", 512), ("round", 2048), ("slot_prefill", 512), ("slot_prefill", 2048)])
def test_the_cell_s_programs_compile_for_v5e(cell, program, live, one_chip,
                                             no_compile_cache, monkeypatch):
    config, model, decoder = cell
    compiled = _lowered(model, decoder, program, live, one_chip, monkeypatch).compile()
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    print(f"\n{CELL} {program} at {live}: arguments {memory.argument_size_in_bytes} "
          f"outputs {memory.output_size_in_bytes} temporaries "
          f"{memory.temp_size_in_bytes} aliased {memory.alias_size_in_bytes} "
          f"-> {total} bytes of one v5e chip")
    # weights, the whole table and the program's temporaries fit the chip
    assert total < V5E_HBM
    # the table is written where it lies
    from benchmark import family

    arithmetic = family.arithmetic(config)
    assert memory.alias_size_in_bytes >= model.slots * 2048 * arithmetic.cache_row_bytes(
        config)
    assert "tpu_custom_call" in compiled.as_text()  # the grouped products

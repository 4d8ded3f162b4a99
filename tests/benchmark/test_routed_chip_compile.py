"""Rehearsal 3 of the on-chip guide for the routed family's cell: the step and
the prefill chunk of ``keye-vl-2.0-30b-a3b.longdoc4`` compile for a described
``v5e:2x2`` at the published widths, from shapes alone, at the longest rung
of either kind; at the longest (32,768) the three caches of every layer are written
where they lie (aliased to the outputs) and the program holds no copy of a
whole cache or of a layer's stacked experts from HBM to HBM. No weight is drawn: the builder
leaves the weights as shapes. ``memory_analysis()`` of each compile is what
PERF.md's reckoning of the cell's bytes quotes (run with ``-s`` to see it).
"""

import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E_HBM = 16e9
CELL = "keye-vl-2.0-30b-a3b.longdoc4"


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
    return resolved["config"], decoder


def _on(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _lowered(decoder, program, live, one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp

    from client_tpu import ops

    # the kernels compile for the chip, not for the interpreter of the CPU
    # this test runs on (on-chip guide, section 2: steered in the test)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    params = _on(decoder._params, one_chip)
    caches = _on(jax.eval_shape(decoder._fresh_cache), one_chip)
    tables = _on(decoder._tables, one_chip)
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    i32 = scalar(jnp.int32)
    if program == "step":
        return decoder._step_program.lower(params, caches, tables, i32, i32, live=live)
    tokens = jax.ShapeDtypeStruct((decoder.sizes.chunk,), jnp.int32, sharding=one_chip)
    return decoder._prefill_program.lower(params, caches, tables, tokens, i32, i32, i32,
                                          scalar(jnp.bool_), live=live)


def test_the_builder_leaves_shapes_that_the_arithmetic_counts(cell):
    import jax

    from benchmark import family

    config, decoder = cell
    leaves = jax.tree_util.tree_leaves(decoder._params)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
    held = sum(int(np.prod(leaf.shape)) for leaf in leaves)
    assert held == family.arithmetic(config).total_params(config) == 4_374_622_464
    assert decoder.ladder() == (512, 2048, 8192, 32768)
    assert family.arithmetic(config).max_len(config) == decoder.MAX_LEN == 32768


# the longest rung of each kind: at 2,048 every causal position is kept, at
# 32,768 the indexer selects (512 and 8,192 are the same programs, shorter)
@pytest.mark.parametrize("live", [2048, 32768])
@pytest.mark.parametrize("program", ["step", "prefill"])
def test_the_cell_s_programs_compile_for_v5e(cell, program, live, one_chip,
                                             no_compile_cache, monkeypatch):
    config, decoder = cell
    compiled = _lowered(decoder, program, live, one_chip, monkeypatch).compile()
    memory = compiled.memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    print(f"\n{CELL} {program} at {live}: arguments {memory.argument_size_in_bytes} "
          f"outputs {memory.output_size_in_bytes} temporaries "
          f"{memory.temp_size_in_bytes} aliased {memory.alias_size_in_bytes} "
          f"-> {total} bytes of one v5e chip")
    # beside this program's arguments the chip holds the other users' caches
    s = decoder.sizes
    cache = s.layers * s.max_len * 2 * (2 * s.kv_heads * s.head_dim + s.index_dim)
    assert total + 3 * cache < V5E_HBM
    # the caches are written where they lie
    assert memory.alias_size_in_bytes >= cache
    if live != s.max_len:
        return
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the grouped product is a kernel
    # (what the compiler stages through fast memory, memory space ``S(1)``,
    # for a consumer that reads every row of it is no copy from HBM to HBM,
    # and neither is what it lays out anew there: those are passed over)
    whole = [f"bf16[{s.max_len},{s.kv_heads * s.head_dim}]",
             f"bf16[{s.max_len},{s.index_dim}]",
             f"bf16[{s.experts},{s.d_model},{s.expert_width}]",
             f"bf16[{s.experts},{s.expert_width},{s.d_model}]"]
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \S+ (copy|copy-start)\(", line) and "S(1)" not in line
              and any(line.split("=", 1)[1].lstrip().startswith(shape) or
                      f"({shape}" in line.split("=", 1)[1][:120] for shape in whole)]
    # (of a chunk's 18 caches the compiler copies one, 34 MB, 0.04 ms, between
    # the write of its block and the kernel that reads it; none in the step)
    assert len(copies) <= (1 if program == "prefill" else 0), copies

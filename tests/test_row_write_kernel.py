"""ops/row_write.py, in interpret mode: the kernel that writes a round's key
and value rows on the chip leaves the table that the loop it replaces
(``decoder.write_rows_in_turns``, what the CPU runs) leaves, bit for bit,
at the row shapes of the two published tables, with a short table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from client_tpu.models.decoder import write_rows_in_turns
from client_tpu.ops import row_write

# name: (slots, rows of lanes a position, lanes): gpt2-large's table
# [16, 10, 1024, 128] and cerebras-gpt-1.3b's [16, 16, 2048, 128], cut to
LENGTH = 32  # positions, four windows
TABLES = {"gpt2-large": (16, 10, 128), "cerebras-gpt-1.3b": (16, 16, 128)}
MEMBERS = {"none": [], "one": [5], "all": list(range(16)),
           "scattered": [0, 3, 4, 9, 15]}
# a window's first and last positions, the next window's first two, the
# last; and one past the table, which the loop's update clamps onto the last
POSITIONS = (0, 7, 8, 15, 16, 17, LENGTH - 1, LENGTH + 3)


def _noise(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32),
                       jnp.bfloat16)


def _bits(table):
    return np.asarray(table).view(np.uint16)


by_kernel = jax.jit(
    lambda tables, rows, pos, active: row_write.write_table_rows(
        tables, rows, pos, active, interpret=True))
by_turns = jax.jit(write_rows_in_turns)


@pytest.mark.parametrize("at", ["each"] + list(POSITIONS))
@pytest.mark.parametrize("members", MEMBERS)
@pytest.mark.parametrize("table", TABLES)
def test_the_kernel_writes_the_loops_table(table, members, at):
    """``at``: every member at that position, or ("each") the slots through
    ``POSITIONS`` in turn. A slot that is not a member keeps every byte."""
    slots, rows, lanes = TABLES[table]
    rng = np.random.default_rng(len(MEMBERS[members]) * 100 + slots)
    tables = tuple(_noise(rng, slots, rows, LENGTH, lanes) for _ in range(2))
    new = tuple(_noise(rng, slots, rows, 1, lanes) for _ in range(2))
    pos = np.array([POSITIONS[slot % len(POSITIONS)] if at == "each" else at
                    for slot in range(slots)], np.int32)
    active = np.zeros(slots, bool)
    active[MEMBERS[members]] = True
    before = [_bits(t) for t in tables]

    turned = [_bits(t) for t in by_turns(tables, new, pos, active)]
    written = [_bits(t) for t in by_kernel(tables, new, pos, active)]

    for was, loop, kernel, row in zip(before, turned, written, new):
        np.testing.assert_array_equal(kernel, loop)
        np.testing.assert_array_equal(kernel[~active], was[~active])
        for slot in np.flatnonzero(active):
            np.testing.assert_array_equal(
                kernel[slot, :, min(pos[slot], LENGTH - 1)],
                _bits(row)[slot, :, 0])


def test_the_kernels_outputs_are_its_tables():
    """The two tables are the kernel's last operands, aliased to its two
    outputs: the program that donates them writes them where they lie."""
    slots, rows, lanes = TABLES["gpt2-large"]
    table = jax.ShapeDtypeStruct((slots, rows, LENGTH, lanes), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((slots, rows, 1, lanes), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda tables, rows, pos, active: row_write.write_table_rows(
            tables, rows, pos, active))(
        (table, table), (row, row), jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_))
    (call,) = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    assert len(call.invars) == 7
    assert dict(call.params["input_output_aliases"]) == {5: 0, 6: 1}
    assert [v.aval.shape for v in call.invars[5:]] == [table.shape] * 2


@pytest.mark.parametrize("shape, taken", [
    ((16, 10, 1024, 128), True), ((16, 16, 2048, 128), True),
    ((4, 1, 128, 128), True), ((16, 10, 12, 128), False),
    ((16, 20, 1024, 64), False), ((8, 4, 128, 32), False)],
    ids=["gpt2-large", "cerebras-gpt-1.3b", "fixture", "part_of_a_window",
         "narrow_rows_64", "narrow_rows_32"])
def test_the_kernel_takes_rows_of_whole_tiles_in_whole_windows(shape, taken):
    """A DMA moves whole tiles: rows across the 128 lanes, positions in
    windows of eight. A table that is not so is refused, not written."""
    assert row_write.takes(shape) is taken
    if taken:
        return
    table = jnp.zeros(shape, jnp.bfloat16)
    row = jnp.zeros(shape[:2] + (1, shape[3]), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole tiles"):
        row_write.write_table_rows((table, table), (row, row),
                                   jnp.zeros(shape[0], jnp.int32),
                                   jnp.ones(shape[0], bool), interpret=True)

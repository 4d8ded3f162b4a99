"""A round's key and value rows into a table of caches, one kernel a layer.

Every member of a round writes one key row and one value row a layer into
its slot of a stacked table ``[slots, H/P, M, P x Dh]`` that the program
owns (donated; ``models/decoder.py:_fresh_table`` lays it, a position's row
of a slot across the lanes). As a ``while`` of one dynamic update an active
slot the writes cost 2.75 ms of a 5.54 ms round of sixteen gpt2-large
members on a v5e, for 2.9 MB of rows: the loop's turns, not the bytes
(PERF.md section 5).

Here the tables stay in HBM, aliased to the outputs; the active slots
(active first, in slot order: ``order``), their count and every slot's
position arrive as scalar prefetch. The chip's compiler refuses a DMA of
one bfloat16 position (a tile of the table in HBM holds ``WINDOW``), so each
member's window of ``WINDOW`` aligned positions round its own is moved into
fast memory, its two rows are put in by a select, and the window is moved
back where it was read: every read is started, then waited on, then every
write. A member owns its slot, so no two windows meet; an inactive slot is
never touched, as the loop left it.

Runs in interpret mode on the CPU (the tests hold it bit-equal to the loop
there); compiled to Mosaic on the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the positions of a window: the rows of a tile of a bfloat16 table in HBM,
# ``T(8,128)(2,1)``, the least a DMA moves along the positions
WINDOW = 8
# the lanes of a tile: a DMA moves whole tiles across them too
LANES = 128


def takes(shape) -> bool:
    """Whether the kernel writes a table of ``shape`` [slots, R, M, C]: its
    rows lie across whole tiles of lanes and its positions are whole
    windows."""
    return shape[-1] % LANES == 0 and shape[-2] % WINDOW == 0


def _row_write_kernel(order_ref, count_ref, pos_ref, k_rows, v_rows,
                      k_in, v_in, k_out, v_out, k_win, v_win, sems):
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del k_in, v_in  # aliased to ``k_out``, ``v_out``: the same buffers
    pairs = ((k_rows, k_out, k_win), (v_rows, v_out, v_win))

    def copies(turn, back):
        """The DMAs of member ``turn``'s two windows: from the tables into
        fast memory, or ``back``."""
        slot = order_ref[turn]
        start = pl.multiple_of(pos_ref[slot] // WINDOW * WINDOW, WINDOW)
        made = []
        for n, (_, table, win) in enumerate(pairs):
            there = table.at[pl.ds(slot, 1), :, pl.ds(start, WINDOW), :]
            here = win.at[pl.ds(turn, 1)]
            made.append(pltpu.make_async_copy(
                *((here, there) if back else (there, here)),
                sems.at[int(back), n]))
        return made

    def put_rows(turn):
        slot = order_ref[turn]
        at = pos_ref[slot] % WINDOW
        for rows, _, win in pairs:
            held = win[turn]  # [R, WINDOW, C]
            hit = lax.broadcasted_iota(jnp.int32, held.shape, 1) == at
            win[turn] = jnp.where(hit, rows[slot][:, None, :], held)

    def each_member(*steps):
        def body(turn, carry):
            for step in steps:
                step(turn)
            return carry

        lax.fori_loop(0, count_ref[0], body, 0)

    def start(back):
        return lambda turn: [copy.start() for copy in copies(turn, back)]

    def wait(back):
        return lambda turn: [copy.wait() for copy in copies(turn, back)]

    each_member(start(False))
    each_member(wait(False))
    each_member(put_rows, start(True))
    each_member(wait(True))


def write_table_rows(tables, rows, pos, active, *, interpret=False):
    """``tables`` (k, v), each [slots, R, M, C], with ``rows`` (k, v), each
    [W, R, 1, C], of the table's leading W slots at ``pos`` int32 [W],
    where ``active`` bool [W]; the tables, the same buffers, are returned.
    The table is one the kernel ``takes``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, heads, length, lanes = tables[0].shape
    if not takes(tables[0].shape):
        raise ValueError(
            f"a table of {tables[0].shape} is not rows of whole tiles of "
            f"{LANES} lanes and positions in whole windows of {WINDOW}")
    width = rows[0].shape[0]
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    count = jnp.sum(active, dtype=jnp.int32).reshape(1)
    # where the loop's update would clamp a position, and never past the
    # table: a DMA is not clamped
    pos = jnp.clip(pos.astype(jnp.int32), 0, length - 1)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    win = pltpu.VMEM((width, heads, WINDOW, lanes), tables[0].dtype)
    k, v = pl.pallas_call(
        _row_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[win, win, pltpu.SemaphoreType.DMA((2, 2))]),
        # outputs in HBM by name: where they named no memory, the chip's
        # compiler set a layer's tables aside in fast memory round the call
        out_shape=[pltpu.HBM(t.shape, t.dtype) for t in tables],
        # operands: order, count, pos, the two rows, the two tables
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name="row_write",
    )(order, count, pos, *(r.reshape(width, heads, lanes) for r in rows),
      *tables)
    return k, v

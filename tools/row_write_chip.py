"""A round's row writes on the real chip, a layer's pair of tables at a time.

Every member of a round writes one key row and one value row a layer into a
stacked table ``[slots, H, M, Dh]`` that the program owns (donated).
``models/decoder.py:write_table_rows`` is that write; this tool times the
forms it could take, each alone, so that the choice among them rests on a
number that is in the repository and not in prose:

- ``loop``: a ``while`` of one dynamic turn an active slot (what stood until
  PR 35): the slot index and the position are both the program's to find;
- ``flat_readback``: one update a slot at a static slot index, dynamic in the
  position alone; a slot that is not active writes back the row it read;
- ``flat_noread``: the same with no read: an inactive slot's row lands at its
  position as the products gave it;
- ``flat_window``: one update a slot of the aligned window of ``WINDOW``
  positions round the row: the window is read, the row is put into it by a
  select (nothing, for a slot that is not active), and it is written back
  where it was read;
- ``slots_window``: the same update in a ``while`` of one turn a slot,
  active or not;
- ``loop_window``: the same update in ``loop``'s own ``while``, one turn an
  active slot (what the decoder took where a row is narrower than the
  lanes, until it laid its table ``heads_a_row`` heads a row: sixteen
  updates written out flat double the program that the chip has to load,
  and sixteen turns cost A's rounds of seven more than seven);
- ``packed``: ``loop`` over the same table laid as the decoder lays it,
  ``heads_a_row`` heads side by side a row, [slots, H/P, M, P x Dh]
  (gpt2-large's [16, 10, 1024, 128]): a position's row of a slot fills the
  lanes, and the turn updates it alone (the table and the rows are the
  other forms', laid so; at heads of 128 it is ``loop``);
- ``kernel``: the same table and rows written by ``ops/row_write.py``, one
  Pallas kernel a dispatch's layer that DMAs every active slot's aligned
  window of positions round its row into fast memory, puts the row in, and
  DMAs it back (what the decoder takes on the chip);

at the two tables the benchmark's cells hold (gpt2-large: 16 slots, 20 heads
of 64, 1,024 positions, 36 layers; cerebras-gpt-1.3b: 16 heads of 128, 2,048
positions, 24 layers) and 4, 8 and 16 active slots.

One dispatch writes a layer's rows into each of ``layers`` donated pairs of
tables, as a round's program holds them (over one pair for all the layers, a
form that has the compiler lay a table out anew would pay for that once a
dispatch and not once a layer); the rows are inputs, so no product is in the
time. Reported: the median dispatch in ms (a round's row writes at that
depth) and us a layer. ``agreement`` says that the forms leave the same
table wherever a step could read it: bit for bit ``loop``'s, but for
``flat_noread`` at row ``pos`` of a slot that is not active (``packed``'s
and ``kernel``'s read a head a row).

Run on the chip (or with --small off the chip for a pipeline check):
    python tools/row_write_chip.py [--json-out PATH] [--small]
        [--forms loop_window,packed,kernel]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (table shape [slots, H, M, Dh], layers a round writes)
TABLES = {
    "gpt2-large": ((16, 20, 1024, 64), 36),
    "cerebras-gpt-1.3b": ((16, 16, 2048, 128), 24),
}
# heads of 64, two a row of 128 lanes as the decoder lays them: a table the
# ``kernel`` form takes (``ops/row_write.py:takes``)
SMALL_TABLES = {"small": ((4, 2, 16, 64), 2)}
FORMS = ("loop", "flat_readback", "flat_noread", "flat_window", "slots_window",
         "loop_window", "packed", "kernel")
# the forms over the table as the decoder lays it (``packed``)
PACKED = ("packed", "kernel")
# the positions a window holds: the lanes of a tile
WINDOW = 128


def packed(table, heads=None):
    """A table [.., H, M, Dh] (or a round's rows, M of 1) laid as the decoder
    lays it, ``heads_a_row`` heads a row: [.., H/P, M, P x Dh]; with
    ``heads``, a table so laid back a head a row."""
    from client_tpu.models.decoder import heads_a_row

    *lead, rows, length, width = table.shape
    if heads is not None:
        P = heads // rows
        return table.reshape(*lead, rows, length, P, width // P).swapaxes(
            -3, -2).reshape(*lead, heads, length, width // P)
    P = heads_a_row(rows, width)
    return table.reshape(*lead, rows // P, P, length, width).swapaxes(
        -3, -2).reshape(*lead, rows // P, length, P * width)


def forms(jnp, lax):
    """Every form, each ``(caches, rows, pos, active) -> caches`` over
    caches (k, v) of [slots, H, M, Dh], rows of [slots, H, 1, Dh], ``pos``
    int32 [slots] and ``active`` bool [slots]; those of ``PACKED`` over
    both laid as ``packed`` lays them."""
    from client_tpu import ops
    from client_tpu.ops.row_write import write_table_rows

    def loop(caches, rows, pos, active):
        active_first = jnp.argsort(~active, stable=True)

        def write(turn, caches):
            slot = active_first[turn]
            return tuple(
                lax.dynamic_update_slice(
                    cache,
                    lax.dynamic_index_in_dim(slot_rows, slot, keepdims=True),
                    (slot, 0, pos[slot], 0))
                for cache, slot_rows in zip(caches, rows))

        return lax.fori_loop(0, jnp.sum(active, dtype=jnp.int32), write, caches)

    def flat(read_back):
        def write(caches, rows, pos, active):
            for slot in range(rows[0].shape[0]):
                at = (slot, 0, pos[slot], 0)
                new = []
                for cache, slot_rows in zip(caches, rows):
                    row = slot_rows[slot:slot + 1]
                    if read_back:
                        row = jnp.where(
                            active[slot], row,
                            lax.dynamic_slice(cache, at, row.shape))
                    new.append(lax.dynamic_update_slice(cache, row, at))
                caches = tuple(new)
            return caches

        return write

    def window(turns):
        """The aligned-window update, ``turns`` of it: "flat" (every slot, at
        a slot index the program knows), "slots" (a ``while`` of one turn a
        slot) or "active" (a ``while`` of one turn an active slot)."""

        def write(caches, rows, pos, active):
            slots, heads, length, dim = caches[0].shape
            span = WINDOW if length % WINDOW == 0 else length
            aligned = jnp.uint32(-span % 2 ** 32 if span < length else 0)
            lanes = jnp.arange(span, dtype=jnp.uint32)
            zero = jnp.uint32(0)
            ats = jnp.minimum(pos, length - 1).astype(jnp.uint32)

            def write_window(slot, at, is_active, caches):
                start = at & aligned
                where = (slot, zero, start, zero)
                hit = (lanes == at - start) & is_active
                return tuple(
                    lax.dynamic_update_slice(
                        cache,
                        jnp.where(
                            hit[None, None, :, None],
                            lax.dynamic_index_in_dim(
                                slot_rows, slot, keepdims=True),
                            lax.dynamic_slice(
                                cache, where, (1, heads, span, dim))),
                        where)
                    for cache, slot_rows in zip(caches, rows))

            width = rows[0].shape[0]
            if turns == "flat":
                for slot in range(width):
                    caches = write_window(
                        jnp.uint32(slot), ats[slot], active[slot], caches)
                return caches
            if turns == "slots":
                return lax.fori_loop(
                    0, width,
                    lambda slot, caches: write_window(
                        slot.astype(jnp.uint32), ats[slot], active[slot],
                        caches),
                    caches)
            order = jnp.argsort(~active, stable=True)
            by_turn = order.astype(jnp.uint32), ats[order]
            return lax.fori_loop(
                0, jnp.sum(active, dtype=jnp.int32),
                lambda turn, caches: write_window(
                    by_turn[0][turn], by_turn[1][turn], True, caches),
                caches)

        return write

    def kernel(caches, rows, pos, active):
        return write_table_rows(caches, rows, pos, active,
                                interpret=not ops._on_tpu())

    return dict(zip(FORMS, (loop, flat(True), flat(False), window("flat"),
                            window("slots"), window("active"), loop, kernel)))


def layered(jax, form):
    """One dispatch: a layer's rows into each of ``layers`` donated pairs of
    tables, as a round's program holds them: a pair a layer."""

    def dispatch(tables, rows, pos, active):
        return [form(caches, tuple(r[layer] for r in rows), pos, active)
                for layer, caches in enumerate(tables)]

    return jax.jit(dispatch, donate_argnums=0)


def _operands(jnp, np, shape, layers, width, seed=0, form=None):
    """A pair of tables of noise a layer, every layer's rows for every slot,
    positions apart from each other, and ``width`` (a divisor of the slots)
    slots active; tables and rows laid as ``packed`` lays them for the
    forms of ``PACKED``."""
    slots, heads, length, dim = shape
    rng = np.random.default_rng(seed)
    noise = lambda *dims: jnp.asarray(
        rng.standard_normal(dims, dtype=np.float32), jnp.bfloat16)
    first = (noise(*shape), noise(*shape))
    # the first layer's pair is noise, so that any write shows; the others
    # are copies of it on the device (a draw a layer is minutes on the host)
    tables = [first] + [tuple(cache + 0 for cache in first)
                        for _ in range(layers - 1)]
    rows = (noise(layers, slots, heads, 1, dim),
            noise(layers, slots, heads, 1, dim))
    if form in PACKED:
        tables = [tuple(packed(cache) for cache in pair) for pair in tables]
        rows = tuple(packed(r) for r in rows)
    pos = jnp.asarray((3 + 5 * np.arange(slots)) % length, jnp.int32)
    # the active slots spread over the table, as a table that streams have
    # come to and left holds them
    active = np.zeros((slots,), bool)
    active[::slots // width] = True
    return tables, rows, pos, jnp.asarray(active)


def check_agreement(jax, jnp, np, lax, tables, chosen=FORMS):
    """Each form of ``chosen`` its first pair of tables after one dispatch
    against ``loop``'s, read a head a row."""
    rows_out, ok = [], True
    for name, (shape, layers) in tables.items():
        slots, length = shape[0], shape[2]
        width = max(1, slots // 2)
        left = {}
        for form_name, form in forms(jnp, lax).items():
            if form_name != "loop" and form_name not in chosen:
                continue
            # two layers: a dispatch of one kernel alone on a donated pair
            # is refused by the chip's compiler (an output in another memory
            # than the buffer it aliases), which no served program is
            pairs, rows, pos, active = _operands(jnp, np, shape, 2, width,
                                                 form=form_name)
            out = layered(jax, form)(pairs, rows, pos, active)
            if form_name in PACKED:
                out = [[packed(c, shape[1]) for c in out[0]]]
            left[form_name] = [np.asarray(c).view(np.uint16) for c in out[0]]
        pos, active = np.asarray(pos), np.asarray(active)
        # where a step could read: everything but the row an inactive slot
        # was handed for its next write
        readable = np.ones((slots, 1, length, 1), bool)
        readable[~active, 0, pos[~active], 0] = False
        case = {"table": name, "shape": list(shape)}
        for form_name in [f for f in FORMS[1:] if f in left]:
            where = readable if form_name == "flat_noread" else True
            case[form_name] = all(
                np.array_equal(a * where, b * where)
                for a, b in zip(left["loop"], left[form_name]))
            ok = ok and case[form_name]
        rows_out.append(case)
    return {"ok": ok, "cases": rows_out}


def bench_forms(jax, jnp, np, lax, tables, widths, repeats, chosen=FORMS):
    """The median dispatch of every form of ``chosen`` at every table and
    width; the shape a row reports is the table as the form lays it."""
    out = []
    for name, (shape, layers) in tables.items():
        for form_name, form in forms(jnp, lax).items():
            if form_name not in chosen:
                continue
            program = layered(jax, form)
            for width in widths:
                caches, rows, pos, active = _operands(
                    jnp, np, shape, layers, width, form=form_name)
                row = {"table": name, "shape": list(caches[0][0].shape),
                       "layers": layers, "form": form_name, "active": width}
                try:
                    caches = program(caches, rows, pos, active)
                    jax.block_until_ready(caches)  # compiled and warm
                    times = []
                    for _ in range(repeats):
                        t0 = time.perf_counter()
                        caches = program(caches, rows, pos, active)
                        jax.block_until_ready(caches)
                        times.append(time.perf_counter() - t0)
                    ms = sorted(times)[len(times) // 2] * 1000
                    row["ms_a_dispatch"] = round(ms, 4)
                    row["us_a_layer"] = round(ms * 1000 / layers, 2)
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {e}"[:300]
                out.append(row)
                del caches
    return out


def run(small: bool, repeats: int = 15, chosen=FORMS):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    tables = SMALL_TABLES if small else TABLES
    widths = (1, 4) if small else (4, 8, 16)
    device = jax.devices()[0]
    result = {"platform": jax.default_backend(),
              "device_kind": device.device_kind}
    try:
        result["agreement"] = check_agreement(jax, jnp, np, lax, tables,
                                              chosen)
    except Exception as e:
        result["agreement"] = {
            "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
    result["forms"] = bench_forms(jax, jnp, np, lax, tables, widths, repeats,
                                  chosen)
    return result


def _chosen(names):
    """An argument's type: a comma-separated choice among ``names``."""
    def parse(text):
        chosen = tuple(text.split(","))
        unknown = [name for name in chosen if name not in names]
        if unknown:
            raise argparse.ArgumentTypeError(f"no form {unknown[0]!r}")
        return chosen

    return parse


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--json-out", default=None)
    parser.add_argument("--small", action="store_true",
                        help="a table of four slots, two layers: a pipeline "
                        "check off the chip, no number of the chip's")
    parser.add_argument("--repeats", type=int, default=15,
                        help="timed dispatches a row; the median is reported")
    parser.add_argument("--forms", type=_chosen(FORMS), default=FORMS,
                        help="the forms to time, by name, a comma between "
                        "(every one unless given); each is checked against "
                        "loop")
    args = parser.parse_args(argv)

    result = run(args.small, args.repeats, args.forms)
    text = json.dumps(result, indent=1)
    print(text)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(text + "\n")
    return 0 if result["agreement"].get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())

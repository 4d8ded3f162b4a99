"""Ahead-of-time compiles for the chip, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
DESCRIBED ``v5e:2x2`` topology. Interpret-mode tests cannot see what Mosaic
refuses (a blocked rank-1 SMEM operand, more VMEM than a kernel may hold),
so the kernels of the serving path are compiled here at the shapes
``chip_smoke.py`` serves. Nothing runs: a pass says the chip's compiler
accepts the program, not that its results are right — the interpret-mode
tests and the chip smoke hold those.
"""

import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import client_tpu.ops as ops
from client_tpu.ops import decode_attention as decode_mod

# ops.flash_attention the attribute is the wrapper function, not the module
flash_mod = importlib.import_module("client_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_chip(monkeypatch):
    """Steer ``_on_tpu()`` for code that picks interpret mode by asking the
    backend (the decoder steps): here JAX still reports the CPU. Cached
    traces carry the choice, so they are dropped on the way in and out."""
    for mod in (ops, decode_mod, flash_mod):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compiles_with_kernel(fn, chip, *shapes):
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        shapes)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("batch,heads,max_len,dim", [
    (1, 4, 128, 32),      # the decoder fixture's own shape
    (8, 16, 4096, 128),   # a serving-sized cache
])
def test_decode_attention_compiles(chip, batch, heads, max_len, dim):
    bf16 = jnp.bfloat16
    _compiles_with_kernel(
        lambda q, k, v, p: decode_mod.decode_attention(
            q, k, v, p, interpret=False),
        chip,
        _s((batch, heads, dim), bf16), _s((batch, heads, max_len, dim), bf16),
        _s((batch, heads, max_len, dim), bf16), _s((batch,), jnp.int32))


def test_decode_attention_compiles_under_vmap(chip):
    """The table step maps a single slot's attention over its slots."""
    bf16 = jnp.bfloat16
    _compiles_with_kernel(
        jax.vmap(lambda q, k, v, p: decode_mod.decode_attention(
            q, k, v, p, interpret=False)),
        chip,
        _s((8, 1, 4, 32), bf16), _s((8, 1, 4, 128, 32), bf16),
        _s((8, 1, 4, 128, 32), bf16), _s((8, 1), jnp.int32))


def _shapes_of(tree):
    return jax.tree_util.tree_map(lambda x: _s(x.shape, x.dtype), tree)


def test_decoder_step_compiles_with_kernel(chip, as_on_chip):
    from client_tpu.models.decoder import TinyDecoderModel

    model = TinyDecoderModel(attention_impl="pallas")
    model._ensure_built()
    _compiles_with_kernel(
        model._step_fn, chip, _shapes_of(model._params),
        _shapes_of(model._fresh_cache()), _s((), jnp.int32),
        _s((), jnp.int32))


def test_batched_decoder_step_compiles_with_kernel(chip, as_on_chip):
    from client_tpu.models.decoder_batched import BatchedDecoderModel

    model = BatchedDecoderModel(slots=8, attention_impl="pallas")
    model._ensure_built()
    try:
        slots = (model.slots,)
        _compiles_with_kernel(
            model._batched_step, chip, _shapes_of(model._decoder._params),
            _shapes_of(model._caches), _s(slots, jnp.int32),
            _s(slots, jnp.int32), _s(slots, jnp.bool_))
    finally:
        model.unload()


@pytest.mark.parametrize("shape,dtype,causal", [
    ((4, 2048, 8, 128), jnp.bfloat16, True),
    ((1, 300, 4, 16), jnp.float32, False),  # long_context_encoder, padded
])
def test_flash_attention_compiles(chip, shape, dtype, causal):
    _compiles_with_kernel(
        lambda q, k, v: flash_mod.flash_attention(
            q, k, v, causal=causal, interpret=False),
        chip, _s(shape, dtype), _s(shape, dtype), _s(shape, dtype))


@pytest.mark.parametrize("fn,shape,dtype", [
    (lambda x: ops.normalize_image(x, 2.0 / 255.0, -1.0),
     (224, 224, 3), jnp.float32),
    (ops.softmax_probabilities, (1, 1000), jnp.float32),
    (lambda x: ops.quantize_int8(x, 0.05), (1024, 1024), jnp.float32),
    (lambda q: ops.dequantize_int8(q, 0.05), (1024, 1024), jnp.int8),
    # the data plane's own large size: 64 MiB does not fit VMEM whole
    (lambda x: ops.quantize_int8(x, 0.05), (4096, 4096), jnp.float32),
    # the widest row the budget admits, where a block is one row tile
    (ops.softmax_probabilities, (256, 16384), jnp.float32),
], ids=["normalize_image", "softmax", "quantize_int8", "dequantize_int8",
        "quantize_int8_64MiB", "softmax_widest"])
def test_elementwise_kernels_compile(chip, as_on_chip, fn, shape, dtype):
    _compiles_with_kernel(fn, chip, _s(shape, dtype))


def test_row_too_wide_raises_typed_error():
    """Above the width that fits, a typed error at trace time — never an
    opaque compiler failure at request time."""
    with pytest.raises(ops.KernelTooLargeError, match="16384"):
        jax.eval_shape(lambda x: ops.quantize_int8(x, 0.05),
                       _s((1, 1 << 24), jnp.float32))


# -- the cache is written where it lies --------------------------------------


# width, heads, positions: gpt2-large's (64-wide heads, narrower than a
# tile's lanes) and cerebras-gpt-1.3b's (128-wide: a row fills the lanes)
PUBLISHED_WIDTHS = {64: (1280, 20, 1024), 128: (2048, 16, 2048)}


def _published_widths_decoder(head=64):
    """The decoder at a published model's head and cache widths, two layers
    deep and with the fixture's vocabulary: the chip's compiler chooses
    layouts by these widths."""
    from client_tpu.models.decoder import TinyDecoderModel

    width, heads, length = PUBLISHED_WIDTHS[head]
    cls = type("WideDecoder", (TinyDecoderModel,), {
        "D_MODEL": width, "HEADS": heads, "LAYERS": 2, "MAX_LEN": length})
    decoder = cls(seed=0)
    decoder._ensure_built()
    return decoder


def _cache_updates(text, shape):
    """Of a compiled program, every in-place update of an array of ``shape``
    (a stacked cache): ``(the update's dimensions, whether it is fused with
    what it writes)``."""
    import re

    found, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.lstrip("%").startswith("fused_computation")
        update = re.search(
            rf"= bf16\[{shape}\]\{{[^}}]*\}} dynamic-update-slice\("
            r"[^,]+, (%?[\w.-]+),", line)
        if update:  # operands are named without their shapes
            dims = re.search(
                rf"{re.escape(update.group(1))} = bf16\[([0-9,]+)\]",
                text).group(1)
            found.append((tuple(int(n) for n in dims.split(",")), fused))
    return found


def _is_written_a_row_at_a_time(text, dims, layers):
    """A table's rows go in as ``write_table_rows`` says on the chip: the
    table holds as many heads a row as fill the lanes, [slots, H x Dh / 128,
    M, 128], and a layer's pair of tables is written by one ``row_write``
    kernel (ops/row_write.py) that takes both where they lie in HBM and
    aliases them to its outputs: no other operation updates a table, and
    neither the kernel's operands nor its outputs are set aside in fast
    memory (``S(1)``), which the compiler did round a kernel whose outputs
    named no memory."""
    import re

    from client_tpu.models.decoder import LANES

    slots, rows, length, width = dims
    assert width == LANES, dims
    shape = ",".join(str(n) for n in dims)
    assert not _cache_updates(text, shape), _cache_updates(text, shape)
    table = rf"bf16\[{shape}\]\{{[^}}S]*\}}"
    kernels = re.findall(
        rf"%row_write[\w.]* = \({table}, {table}\) custom-call\((.*)", text)
    assert len(kernels) == layers, len(kernels)
    for kernel in kernels:
        assert 'custom_call_target="tpu_custom_call"' in kernel, kernel[:300]
        # operands: order, count, pos, the two rows, the two tables
        assert ("output_to_operand_aliasing={{0}: (5, {}), {1}: (6, {})}"
                in kernel), kernel[:600]
    near = re.findall(rf"= bf16\[{shape}\]\{{[^}}]*S\(1\)", text)
    assert not near, f"{len(near)} tables in fast memory: {near[:2]}"


def _reads_heads_side_by_side_exactly(text, head):
    """Where a table holds heads side by side (heads narrower than the
    lanes), the products that read it (``decoder.py:scored``, ``weighed``:
    each head's query in its own lanes, one product over the row) are at
    ``HIGHEST``: at the default precision the chip's matrix unit takes their
    float32 in bfloat16 passes, 1e-3 to 2e-3 off a head's own float32
    products. And the weighing gives the whole row, and no bitcast changes
    how many elements an array holds: where each head's lanes were cut out
    of the weighing by slices, the compiler gave the second head the first
    one's lanes, by a bitcast of [.., 2, 128] to [.., 2, 64] or by a
    product of 64 lanes, and the cells served tokens 3.5 to 5 under the
    reference's best logit."""
    import math
    import re

    from client_tpu.models.decoder import LANES

    sizes = {name: dims for name, dims in re.findall(
        r"%([\w.-]+) = \w+\[([0-9,]*)\]", text)}
    count = lambda dims: math.prod(int(n) for n in dims.split(",") if n)
    resized = [(name, dims, sizes.get(source)) for name, dims, source in
               re.findall(r"%([\w.-]+) = \w+\[([0-9,]*)\]\{[^}]*\} "
                          r"bitcast\(%([\w.-]+)\)", text)
               if source in sizes and count(dims) != count(sizes[source])]
    assert not resized, resized[:2]

    products = [line for line in text.splitlines()
                if re.search(r"= \S+ (?:convolution|dot)\(", line)
                and re.search(r"hj[cm],s?hmc->s?hj[cm]", line)]
    if head >= LANES:
        assert not products, products[:1]
        return
    assert products, "no product over a row of heads side by side"
    slow = [p for p in products
            if "operand_precision={highest,highest}" not in p]
    assert not slow, f"{len(slow)} of {len(products)}: {slow[0][:300]}"
    narrow = [p for p in products if re.search(r"hjm,s?hmc", p)
              and not re.search(rf"= \w+\[[0-9,]*,{LANES}\]", p)]
    assert not narrow, narrow[0][:300]


@pytest.mark.parametrize("program, head, live", [
    (program, head, live)
    for head, live in [(64, 256), (64, 1024), (128, 512)]
    for program in ("jit_step", "jit_batched_step")
] + [
    # the batcher at cerebras' widths and its top rung (PERF.md section 7,
    # open cell 2)
    ("jit_batched_step", 128, 2048),
])
def test_the_step_writes_its_donated_caches_in_place_on_the_chip(
        chip, as_on_chip, program, head, live):
    """What the CPU cannot show: compiled for a v5e, the step aliases every
    cache to an output and moves no whole cache into another layout, at
    every rung of the ladder. (A scatter of the rows, which ``vmap`` of a
    single slot's row write makes, has each stacked cache copied to a
    row-major layout and back every round.) At the short rung the attention takes the prefix
    of the cache as it lies: no slice of it is materialised, and no whole
    cache is moved through fast memory ahead of the read. The batcher reads
    its caches where they lie at its top rung too (its one product a stacked
    cache had every cache staged through fast memory and back: PR 37). The
    batcher's rows go in by one DMA kernel a layer over its tables where
    they lie, as many heads a row as fill the lanes, and the products over
    such rows read each head's float32 math."""
    import re

    from client_tpu.models.decoder_batched import BatchedDecoderModel

    decoder = _published_widths_decoder(head)
    assert live in decoder._rungs[:-1] or live == decoder.MAX_LEN
    scalar = _s((), jnp.int32)
    if program == "jit_step":
        fn, caches = decoder._step_fn, decoder._fresh_cache()
        rest = (scalar, scalar)
    else:
        model = BatchedDecoderModel(slots=16)
        model._decoder = decoder  # composed before the batcher builds
        model._ensure_built()
        model.unload()  # the worker thread; the program stays
        fn, caches = model._batched_step, model._fresh_caches()
        rest = (_s((16,), jnp.int32), _s((16,), jnp.int32),
                _s((16,), jnp.bool_))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (_shapes_of(decoder._params), _shapes_of(caches)) + rest)
    text = fn.lower(*args, live=live).compile().as_text()
    assert f"HloModule {program}," in text
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert aliased.count("-alias)") == 2 * decoder.LAYERS
    dims = caches[0]["k"].shape
    shape = ",".join(str(n) for n in dims)
    entry = text[text.index("\nENTRY "):]
    relaid = re.findall(rf"= bf16\[{shape}\]\{{[^}}]*\}} copy\(", entry)
    assert not relaid, f"{len(relaid)} whole caches copied to another layout"
    if program == "jit_batched_step":
        _is_written_a_row_at_a_time(text, dims, decoder.LAYERS)
        _reads_heads_side_by_side_exactly(text, head)
    if live < decoder.MAX_LEN:
        prefix = ",".join(str(n) for n in dims[:-2] + (live, dims[-1]))
        sliced = re.findall(rf"= bf16\[{prefix}\]\{{[^}}]*\}} [a-z-]+\(", entry)
        assert not sliced, f"{len(sliced)} prefixes materialised: {sliced[:2]}"
    if live < decoder.MAX_LEN or program == "jit_batched_step":
        # in every computation: the batcher's top rung reads in a ``while``
        staged = re.findall(
            rf"bf16\[[0-9,]*{dims[-2]},{dims[-1]}\]\{{[^}}]*\}}[^=]*"
            r"(?:copy-start|slice-start)\(", _outside_fusions(text))
        assert not staged, f"{len(staged)} caches staged: {staged[:2]}"


def _outside_fusions(text):
    """The operations the compiled program runs as such: the lines of every
    computation but the fused ones (what a fusion computes inside itself
    never lies in memory)."""
    lines, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            fused = line.startswith(("%fused_computation", "fused_computation"))
        elif not fused:
            lines.append(line)
    return "\n".join(lines)


@pytest.mark.parametrize("head, live", [
    (64, 256), (64, 1024), (128, 512), (128, 2048)])
def test_the_round_writes_its_table_in_place_and_reads_it_where_it_lies(
        chip, as_on_chip, head, live):
    """The stream model's round (``decoder._round_fn``, traced as
    ``jit_step``) at every rung, held to what the steps above are held to:
    every stacked cache aliased to an output and none laid out anew; and
    what its attention reads, a prefix of the slots' positions, is read from
    the table as it lies: nothing longer or wider is cut out of it. Where a
    head fills the lanes, the slots of a turn may be set aside in fast
    memory and nowhere else; where heads are narrower (there, a head a row,
    the compiler laid each turn's slice out anew, 3.0 of a 7.0 ms round on a
    v5e), nothing of the table is set aside at all. Its rows go in by one
    DMA kernel a layer, at every head width, and the products over heads
    side by side read each head's float32 math."""
    import re

    from client_tpu.models.decoder import LANES, SLOTS_A_TURN, heads_a_row

    decoder = _published_widths_decoder(head)
    caches = decoder._fresh_table(16)
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (_shapes_of(decoder._params), _shapes_of(caches),
         _s((16,), jnp.int32), _s((3, 16), jnp.int32)))
    text = decoder._round_fn.lower(*args, live=live).compile().as_text()
    assert "HloModule jit_step," in text
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert aliased.count("-alias)") == 2 * decoder.LAYERS
    run = _outside_fusions(text)
    slots, heads, length, dim = caches[0]["k"].shape
    # the attention's turns, where it takes the slots in turns or reads the
    # top rung in halves; the rows' turns are the kernel's own
    assert (" while(" in run) == (head >= LANES or live == length), head
    _is_written_a_row_at_a_time(text, (slots, heads, length, dim),
                                decoder.LAYERS)
    _reads_heads_side_by_side_exactly(text, head)
    relaid = re.findall(
        rf"= bf16\[{slots},{heads},{length},{dim}\]\{{[^}}]*\}} copy\(", run)
    assert not relaid, f"{len(relaid)} whole caches copied to another layout"
    # what the attention reads is its slots' prefix and no position beyond
    set_aside = re.findall(
        rf"= (?:bf16|f32)\[(\d+),{heads},(\d+),{dim}\](\{{[^}}]*\}}) "
        r"(?!parameter|get-tuple-element|while|tuple|dynamic-update-slice)",
        run)
    for some, positions, layout in set_aside:
        if int(positions) in (1, heads_a_row(decoder.HEADS, head)):
            # a token's new row, on its way into the table; the queries of
            # a row's heads, each in its own lanes
            continue
        assert head >= LANES, (some, positions, layout)
        assert (int(some), int(positions)) == (SLOTS_A_TURN, live), (
            some, positions)
        assert "S(1)" in layout, layout
    staged = re.findall(
        rf"bf16\[[0-9,]*{length},{dim}\]\{{[^}}]*\}}[^=]*"
        r"(?:copy-start|slice-start)\(", run)
    assert not staged, f"{len(staged)} caches staged: {staged[:2]}"

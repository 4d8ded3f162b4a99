"""The rows a model's rounds write into its table, counted on the host by
how they were written (``client_tpu_server_rows_written{model,path}``): the
stream model's rounds and the slot batcher's both write a member's key and
value row a layer through ``decoder.py:write_table_rows``; off the chip by
the loop, on it by ``ops/row_write.py``'s kernel, which runs here in
interpret mode where a test asks for it and gives the loop's tokens."""

import numpy as np
import pytest

from client_tpu.models.decoder import TinyDecoderModel
from client_tpu.models.decoder_batched import BatchedDecoderModel
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.server import ServerCore

PROMPTS = ([5, 17, 250], [9], [33, 2, 71, 100, 4])
MAX_TOKENS = 4


def _decoder(path=None):
    """The fixture's decoder, built; ``path`` overrides how its programs,
    not yet traced, write a round's rows."""
    decoder = TinyDecoderModel(seed=0)
    decoder._ensure_built()
    if path is not None:
        decoder.rows_path = path
    return decoder


def _stream(model, prompt):
    return [int(r["NEXT_TOKEN"][0, 0]) for r in model.execute_decoupled(
        {"TOKENS": np.array([prompt], np.int32),
         "MAX_TOKENS": np.array([MAX_TOKENS], np.int32)}, {})]


def _sequence(model, prompt, seq_id):
    """A sequence of the prompt and then its greedy continuation, a token a
    request, on the sequence API."""
    tokens, feed = [], prompt
    for n in range(MAX_TOKENS):
        out = model.execute({"TOKENS": np.array([feed], np.int32)}, {
            "sequence_id": seq_id, "sequence_start": n == 0,
            "sequence_end": n == MAX_TOKENS - 1})
        tokens.append(int(out["NEXT_TOKEN"][0, 0]))
        feed = tokens[-1:]
    return tokens


def _stream_model(decoder):
    model = TinyGenerateModel(decoder=decoder, slots=4)
    model._ensure_built()
    assert model._rounds is not None
    return model


def _batcher(decoder):
    model = BatchedDecoderModel(seed=0, slots=4)
    model._decoder = decoder  # composed before the batcher builds
    model._ensure_built()
    return model


SERVED = {"stream_rounds": (_stream_model, _stream),
          "slot_batcher": (_batcher, _sequence)}


def _serve(kind, decoder):
    make, drive = SERVED[kind]
    model = make(decoder)
    core = ServerCore([model])
    try:
        tokens = [drive(model, prompt, *([n + 1] if kind == "slot_batcher"
                                         else []))
                  for n, prompt in enumerate(PROMPTS)]
        snapshot = core.metrics_registry().snapshot()
    finally:
        model.unload()
    written = {row["labels"]["path"]: row["value"]
               for row in snapshot["client_tpu_server_rows_written"]["series"]
               if row["labels"]["model"] == model.name}
    members = sum(width * n for width, n in model.batch_histogram.items())
    return tokens, written, members


@pytest.mark.parametrize("kind", SERVED)
def test_a_cpu_round_counts_its_rows_as_the_loops(kind):
    decoder = _decoder()
    assert decoder.rows_path == "loop"
    _, written, members = _serve(kind, decoder)
    assert members > 0
    assert written == {"loop": members * decoder.LAYERS * 2}


@pytest.mark.parametrize("kind", SERVED)
def test_the_kernels_rounds_give_the_loops_tokens_and_count_as_its(kind):
    by_loop, _, _ = _serve(kind, _decoder())
    decoder = _decoder("kernel")
    by_kernel, written, members = _serve(kind, decoder)
    assert by_kernel == by_loop
    assert written == {"kernel": members * decoder.LAYERS * 2}


def test_a_decoder_whose_programs_write_no_table_counts_nothing():
    from client_tpu.models.decoder import RungCount

    decoder = _decoder()
    decoder.rows_path = None
    count = RungCount()
    decoder.count_rows_written(count, 16)
    assert count.written() == {}
    decoder.rows_path = "loop"
    decoder.count_rows_written(count, 3)
    decoder.count_rows_written(count, 2)
    assert count.written() == {"loop": 5 * decoder.LAYERS * 2}


@pytest.mark.parametrize("path, kernels", [("loop", 0), ("kernel", 1)])
def test_the_round_program_holds_the_kernel_where_its_path_says(path, kernels):
    """The round's layer is one jitted call, traced once: one kernel where
    the rows go by ``ops/row_write.py``, none where they go by the loop."""
    import jax

    decoder = _decoder(path)
    jaxpr = jax.make_jaxpr(
        lambda params, table, fed, ctl: decoder._round_fn(
            params, table, fed, ctl, live=decoder.MAX_LEN))(
        decoder._params, decoder._fresh_table(4), np.zeros(4, np.int32),
        np.zeros((3, 4), np.int32))
    assert str(jaxpr).count("pallas_call") == kernels


@pytest.mark.parametrize("attention_impl, path", [
    ("einsum", "kernel"), ("pallas", "loop")])
def test_on_the_chip_the_kernel_writes_every_table_it_takes(
        monkeypatch, attention_impl, path):
    """The path follows the table's shape alone: the einsum decoder's table
    holds heads side by side across the 128 lanes, which the kernel takes;
    the Pallas attention's holds a head of 32 a row, which it does not, and
    keeps the loop."""
    from client_tpu import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    decoder = TinyDecoderModel(seed=0, attention_impl=attention_impl)
    decoder._ensure_built()
    assert decoder.rows_path == path

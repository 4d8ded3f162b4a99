"""The step's attention reads the live prefix of the cache: the ladder of
lengths, the rung the host picks, and that a rung is never compiled on demand.

A subclass with 1,024 positions at a small width has two rungs (256, 1,024);
the fixture (128 positions) has one and runs the program it always ran.
Counts, tokens and logits, no clock.
"""

import threading

import numpy as np
import pytest

from client_tpu.models.decoder import SHORTEST_RUNG, TinyDecoderModel
from client_tpu.models.decoder_batched import BatchedDecoderModel
from client_tpu.models.decoder_prefill import PrefillDecoderModel
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.server import ServerCore, timeline

LongDecoder = type("LongDecoder", (TinyDecoderModel,), {
    "D_MODEL": 64, "HEADS": 2, "LAYERS": 2, "MAX_LEN": 1024})

# a prompt that ends under the first rung and an output that crosses it
PROMPT = [int(t) for t in np.random.default_rng(31).integers(0, 256, 250)]
OUTPUT = 12


def _decoder(held=False):
    """The long decoder, built; ``held`` to the whole length, it is the
    parent's program at every position."""
    decoder = LongDecoder(seed=0)
    decoder._ensure_built()
    if held:
        decoder._rungs = (decoder.MAX_LEN,)
    return decoder


def _stepping_alone(decoder):
    """The decoder as one that offers no round program: its streams step
    their own sequences through ``decode_step`` (tests/test_stream_rounds.py
    has the streams that share a round)."""
    decoder._ensure_built()
    decoder._round_fn = None
    return decoder


def _sized(max_len):
    return type("Sized", (TinyDecoderModel,), {"MAX_LEN": max_len})


@pytest.mark.parametrize("max_len,rungs", [
    (128, (128,)), (1024, (256, 1024)), (2048, (512, 2048)),
    (4096, (256, 1024, 4096)), (1000, (1000,))])
def test_the_ladder_is_a_function_of_the_length_alone(max_len, rungs):
    assert _sized(max_len).ladder() == rungs
    assert min(rungs) >= min(SHORTEST_RUNG, max_len)


@pytest.mark.parametrize("reach,rung", [
    (1, 256), (255, 256), (256, 256), (257, 1024), (1024, 1024)])
def test_the_shortest_rung_that_covers_the_step(reach, rung):
    assert _decoder().rung_for(reach) == rung


def test_a_decoder_of_one_rung_runs_the_program_it_ran():
    """The fixture: nothing is built ahead and the step is called as the
    parent called it, without ``live``."""
    decoder = TinyDecoderModel(seed=0)
    decoder._ensure_built()
    assert decoder._rungs == (128,)
    step, calls = decoder._step_fn, []

    def watched(*args, **kwargs):
        calls.append(kwargs)
        return step(*args, **kwargs)

    decoder._step_fn = watched
    decoder.decode_step(decoder._fresh_cache(), 1, 0)
    assert calls == [{}]
    assert decoder.steps_by_rung.by_rung() == {128: 1}


def test_the_pallas_kernel_and_a_step_of_its_own_take_the_whole_length():
    from client_tpu.models.decoder_tp import TPDecoderModel

    pallas = LongDecoder(seed=0, attention_impl="pallas")
    pallas._ensure_built()
    assert pallas._rungs == (1024,)
    tp = type("LongTP", (TPDecoderModel,), {"MAX_LEN": 1024})(seed=0, tp=1)
    tp._ensure_built()
    assert tp._rungs == (1024,)


def test_the_short_rung_gives_the_whole_lengths_logits_at_every_position():
    decoder = _decoder()
    short, whole = decoder._rungs
    a, b = decoder._fresh_cache(), decoder._fresh_cache()
    tokens = np.random.default_rng(7).integers(0, 256, short)
    for pos, token in enumerate(tokens):
        got, a = decoder._step_at(a, int(token), pos, short)
        want, b = decoder._step_at(b, int(token), pos, whole)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-5, err_msg=f"at {pos}")


def _sequence_tokens(decoder):
    """Greedy tokens through the sequence API: the prompt, then a token a
    request."""
    def request(tokens, **controls):
        out = decoder.execute({"TOKENS": np.array([tokens], np.int32)},
                              {"sequence_id": 5, **controls})
        return int(out["NEXT_TOKEN"][0, 0])

    tokens = [request(PROMPT, sequence_start=True)]
    while len(tokens) < OUTPUT:
        tokens.append(request([tokens[-1]],
                              sequence_end=len(tokens) == OUTPUT - 1))
    return tokens


def _stream_tokens(model):
    return [int(r["NEXT_TOKEN"][0, 0]) for r in model.execute_decoupled(
        {"TOKENS": np.array([PROMPT], np.int32),
         "MAX_TOKENS": np.array([OUTPUT], np.int32)}, {})]


def test_a_sequence_that_crosses_the_rung_yields_the_whole_lengths_tokens():
    """Sequence API, decoupled stream and stateless scoring, each against a
    decoder held to the whole length; and each counts its own steps."""
    laddered, held = _decoder(), _decoder(held=True)
    want = _sequence_tokens(held)
    assert _sequence_tokens(laddered) == want
    steps = len(PROMPT) + OUTPUT - 1  # positions 0 to 260: five past the rung
    assert laddered.steps_by_rung.by_rung() == {256: 256, 1024: steps - 256}
    assert held.steps_by_rung.by_rung() == {1024: steps}

    stream = TinyGenerateModel(decoder=laddered)
    assert _stream_tokens(stream) == want
    assert _stream_tokens(TinyGenerateModel(decoder=held)) == want
    assert stream.steps_by_rung.by_rung() == {256: 256, 1024: steps - 256}

    scoring = PrefillDecoderModel()
    scoring._inner = laddered
    rows = np.array([PROMPT + want[:8], PROMPT + want[:8]], np.int32)
    out = scoring.execute({"TOKENS": rows}, {})
    assert out["NEXT_TOKEN"].reshape(-1).tolist() == [want[8], want[8]]
    assert scoring.steps_by_rung.by_rung() == {256: 512, 1024: 4}
    # the composed models counted for themselves, not for the decoder
    assert laddered.steps_by_rung.by_rung() == {256: 256, 1024: steps - 256}


def _batched(decoder):
    model = BatchedDecoderModel(seed=0, slots=4)
    model._decoder = decoder  # composed before the batcher builds
    model._ensure_built()
    return model


def _drive_slots(model):
    """One long sequence and two short ones, each from a thread of its own,
    teacher-forced: ``{sequence: [(token, logits), ...]}``. Which requests
    share a round is the scheduler's; a slot's logits do not depend on it."""
    feeds = {1: (PROMPT, [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]),
             2: ([7, 8, 9], [2, 7, 1, 8, 2, 8]),
             3: ([11] * 40, [1, 6, 1, 8])}
    out = {seq: [] for seq in feeds}
    errors = []

    def user(seq, prompt, rest):
        try:
            def request(tokens, **controls):
                r = model.execute({"TOKENS": np.array([tokens], np.int32)},
                                  {"sequence_id": seq, **controls})
                out[seq].append((int(r["NEXT_TOKEN"][0, 0]),
                                 np.array(r["LOGITS"][0])))

            request(prompt, sequence_start=True)
            for i, token in enumerate(rest):
                request([token], sequence_end=i == len(rest) - 1)
        except Exception as e:  # shown by the main thread
            errors.append(e)

    users = [threading.Thread(target=user, args=(seq, *feed))
             for seq, feed in feeds.items()]
    for u in users:
        u.start()
    for u in users:
        u.join(timeout=120)
    assert not errors, errors
    return out


def test_the_slot_batcher_steps_a_round_at_its_furthest_members_rung():
    laddered, held = _batched(_decoder()), _batched(_decoder(held=True))
    try:
        got, want = _drive_slots(laddered), _drive_slots(held)
        for seq in want:
            assert [t for t, _ in got[seq]] == [t for t, _ in want[seq]]
            for (_, a), (_, b) in zip(got[seq], want[seq]):
                np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
        by_rung = laddered.steps_by_rung.by_rung()
        # the long one's last four steps reach past 256, and so does every
        # round they are in; no other round does
        assert by_rung[1024] == 4 and by_rung[256] >= 256
        assert sum(by_rung.values()) == sum(
            laddered.batch_histogram.values())
        assert set(held.steps_by_rung.by_rung()) == {1024}
        # after the long sequence has ended, its slot holds position 260 and
        # rides along inactive: a short round reads the short rung still
        before = laddered.steps_by_rung.by_rung()
        laddered.execute({"TOKENS": np.array([[5, 6]], np.int32)},
                         {"sequence_id": 9, "sequence_start": True,
                          "sequence_end": True})
        after = laddered.steps_by_rung.by_rung()
        assert (after[256], after[1024]) == (before[256] + 2, before[1024])
    finally:
        laddered.unload()
        held.unload()


def _wait_warm(batched):
    """The batcher's worker builds the rungs before it takes a request."""
    import time

    deadline = time.monotonic() + 120
    while not batched._warm:
        assert time.monotonic() < deadline, "the rungs were never built"
        time.sleep(0.01)


def test_no_rung_is_compiled_on_demand():
    """After ``_ensure_built`` every rung's program is there: the first step
    of any rung, in the middle of serving, compiles nothing."""
    timeline.COMPILES.listen()
    stream = TinyGenerateModel(decoder=_stepping_alone(LongDecoder(seed=0)))
    stream._ensure_built()
    decoder = stream._decoder
    assert decoder._warm
    caches = decoder._fresh_cache()
    np.asarray(caches[0]["k"][0, 0, 0])  # whatever a cache costs, up front
    before = timeline.COMPILES.count
    for pos in (0, 255, 256, 1023):
        logits, caches = decoder.decode_step(caches, 1, pos)
    logits.block_until_ready()
    assert timeline.COMPILES.count == before
    assert decoder.steps_by_rung.by_rung() == {256: 2, 1024: 2}

    batched = BatchedDecoderModel(seed=0, slots=2)
    batched._decoder = LongDecoder(seed=0)
    batched._ensure_built()
    try:
        _wait_warm(batched)
        # a round at the short rung, to have everything but the rungs warm
        batched.execute({"TOKENS": np.array([[1, 2]], np.int32)},
                        {"sequence_id": 1, "sequence_start": True,
                         "sequence_end": True})
        before = timeline.COMPILES.count
        batched.execute({"TOKENS": np.array([PROMPT + [1] * 10], np.int32)},
                        {"sequence_id": 2, "sequence_start": True,
                         "sequence_end": True})
        assert timeline.COMPILES.count == before
        assert batched.steps_by_rung.by_rung() == {256: 258, 1024: 4}
    finally:
        batched.unload()


def test_steps_counted_from_many_threads_are_all_counted():
    """Sixteen streams count into one ``RungCount``: no step is lost."""
    import sys

    from client_tpu.models.decoder import RungCount

    count, threads, each = RungCount(), 32, 2000
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def user(i):
            for n in range(each):
                count.add(256 if (i + n) % 4 else 1024)

        users = [threading.Thread(target=user, args=(i,))
                 for i in range(threads)]
        for u in users:
            u.start()
        for u in users:
            u.join(timeout=60)
        assert not any(u.is_alive() for u in users)
    finally:
        sys.setswitchinterval(interval)
    assert count.by_rung() == {256: threads * each * 3 // 4,
                               1024: threads * each // 4}


def test_a_failed_build_of_the_rungs_is_the_first_steps_to_report():
    """Nothing falls back to another rung, and nothing comes online later:
    the step that needs the rungs raises what their build raised, and the
    next one tries again."""
    decoder = _stepping_alone(_decoder())
    step = decoder._step_fn

    def refused(*args, **kwargs):
        raise RuntimeError("no room for the program")

    decoder._step_fn = refused
    with pytest.raises(RuntimeError, match="no room"):
        TinyGenerateModel(decoder=decoder)._ensure_built()
    assert not decoder._warm
    with pytest.raises(RuntimeError, match="no room"):
        decoder.decode_step(decoder._fresh_cache(), 1, 0)
    decoder._step_fn = step
    decoder.decode_step(decoder._fresh_cache(), 1, 0)
    assert decoder._warm


def test_the_registry_has_a_series_a_model_and_a_rung():
    decoder = _decoder()
    stream = TinyGenerateModel(decoder=decoder)
    batched = _batched(_decoder())
    core = ServerCore([decoder, stream, batched])
    try:
        core.infer("decoder_lm", "", {
            "id": "", "parameters": {"sequence_id": 3, "sequence_start": True,
                                     "sequence_end": True},
            "inputs": [{"name": "TOKENS", "datatype": "INT32", "shape": [1, 3],
                        "array": np.array([[1, 2, 3]], np.int32)}]})
        batched.execute({"TOKENS": np.array([PROMPT + [1] * 8], np.int32)},
                        {"sequence_id": 4, "sequence_start": True,
                         "sequence_end": True})
        assert len(_stream_tokens(stream)) == OUTPUT
        registry = core.metrics_registry()
        series = {
            (row["labels"]["model"], row["labels"]["live"]): row["value"]
            for row in registry.snapshot()[
                "client_tpu_server_decode_steps"]["series"]}
        assert series == {
            ("decoder_lm", "256"): 3,
            ("decoder_lm_batched", "256"): 256,
            ("decoder_lm_batched", "1024"): 2,
            ("tiny_lm_generate", "256"): 256,
            ("tiny_lm_generate", "1024"): 5}
        assert ('client_tpu_server_decode_steps{model="tiny_lm_generate",'
                'live="1024"} 5') in registry.prometheus_text()
    finally:
        batched.unload()

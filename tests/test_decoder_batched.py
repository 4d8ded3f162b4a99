"""Slot-based sequence batcher (decoder_lm_batched).

The reference's sequence batcher (direct mode) pins: per-sequence state in
batch slots, one execution advancing every live slot, per-CORRID
serialization, slot exhaustion as a request error. Here the batched model
must additionally be bit-comparable with the unbatched decoder_lm (the
vmapped step is the same math) — the strongest regression net available.
"""

import random
import threading
import time

import numpy as np
import pytest

from client_tpu.models import decoder_batched
from client_tpu.models.decoder import TinyDecoderModel
from client_tpu.models.decoder_batched import (
    ROUNDS_IN_FLIGHT,
    BatchedDecoderModel,
)
from client_tpu.server import ServerCore
from tests.conftest import (
    GatedStep,
    check_the_phases_tile_the_workers_time,
    spans_into_phases,
)


def _drive(model, seq, prompt, n=6, jitter=None):
    p = {"sequence_id": seq, "sequence_start": True, "sequence_end": False}
    out = model.execute({"TOKENS": np.array([prompt], np.int32)}, p)
    tok = int(out["NEXT_TOKEN"][0, 0])
    toks = [tok]
    for i in range(n - 1):
        if jitter is not None:
            time.sleep(jitter.random() * 0.003)
        p = {"sequence_id": seq, "sequence_start": False,
             "sequence_end": i == n - 2}
        out = model.execute({"TOKENS": np.array([[tok]], np.int32)}, p)
        tok = int(out["NEXT_TOKEN"][0, 0])
        toks.append(tok)
    return toks


def test_concurrent_sequences_match_unbatched():
    ref = TinyDecoderModel(seed=0)
    bat = BatchedDecoderModel(seed=0, slots=4)
    prompts = {101: [1, 2, 3], 102: [9, 8, 7, 6], 103: [42]}
    expected = {s: _drive(ref, s, p) for s, p in prompts.items()}

    results, errors = {}, []

    def worker(s, p):
        try:
            results[s] = _drive(bat, s, p)
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(s, p))
               for s, p in prompts.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert results == expected
    assert bat.live_sequences() == 0
    # the point of the component: concurrent steps shared dispatches
    assert any(width > 1 for width in bat.batch_histogram), bat.batch_histogram


def test_stress_window_composition_invariance():
    """Invariant: what else a round carries never changes any sequence's
    tokens.

    20 seeded iterations of randomly-timed concurrent clients — including
    mid-flight restarts, the round-3 flake's second repro — against one
    batcher; every sequence's greedy tokens must equal the unbatched
    decoder's every time. Guards the round-3 nondeterminism (in-place
    mutation of the host pos buffer racing the async dispatch)."""
    ref = TinyDecoderModel(seed=0)
    bat = BatchedDecoderModel(seed=0, slots=4)
    pool = [[1, 2, 3], [9, 8, 7, 6], [42], [5, 6], [77, 1], [3]]
    expected = {}

    def exp(prompt, n):
        key = (tuple(prompt), n)
        if key not in expected:
            expected[key] = _drive(ref, 999, prompt, n=n)
        return expected[key]

    for it in range(20):
        rng = random.Random(1000 + it)
        jobs = []  # (seq_id, prompt, n, restart_mid_flight)
        for s in range(4):
            jobs.append((it * 10 + s + 1, rng.choice(pool),
                         rng.randint(2, 7), rng.random() < 0.3))
        results, errors = {}, []

        def worker(seq, prompt, n, restart, seed):
            r = random.Random(seed)
            try:
                if restart:
                    # open the sequence, then sequence_start again on a
                    # live slot (restart in place) via _drive below
                    bat.execute(
                        {"TOKENS": np.array([prompt], np.int32)},
                        {"sequence_id": seq, "sequence_start": True})
                    time.sleep(r.random() * 0.003)
                results[seq] = _drive(bat, seq, prompt, n=n, jitter=r)
            except Exception as e:
                errors.append((seq, e))

        threads = [threading.Thread(target=worker, args=(s, p, n, re, i))
                   for i, (s, p, n, re) in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, (it, errors)
        for seq, prompt, n, _ in jobs:
            assert results[seq] == exp(prompt, n), (it, seq)
    assert bat.live_sequences() == 0
    assert any(width > 1 for width in bat.batch_histogram), bat.batch_histogram


def test_slot_exhaustion_is_a_request_error():
    bat = BatchedDecoderModel(seed=0, slots=2)
    for seq in (1, 2):
        bat.execute({"TOKENS": np.array([[5]], np.int32)},
                    {"sequence_id": seq, "sequence_start": True})
    with pytest.raises(ValueError, match="no free sequence slot"):
        bat.execute({"TOKENS": np.array([[5]], np.int32)},
                    {"sequence_id": 3, "sequence_start": True})
    # ending one frees its slot for a new sequence
    bat.execute({"TOKENS": np.array([[6]], np.int32)},
                {"sequence_id": 1, "sequence_start": False,
                 "sequence_end": True})
    bat.execute({"TOKENS": np.array([[5]], np.int32)},
                {"sequence_id": 3, "sequence_start": True,
                 "sequence_end": True})
    bat.execute({"TOKENS": np.array([[5]], np.int32)},
                {"sequence_id": 2, "sequence_start": False,
                 "sequence_end": True})
    assert bat.live_sequences() == 0


def test_validation_errors():
    bat = BatchedDecoderModel(seed=0, slots=2)
    with pytest.raises(ValueError, match="sequence_id"):
        bat.execute({"TOKENS": np.array([[1]], np.int32)}, {})
    with pytest.raises(ValueError, match="no live state"):
        bat.execute({"TOKENS": np.array([[1]], np.int32)},
                    {"sequence_id": 77})
    with pytest.raises(ValueError, match="exactly one token"):
        bat.execute({"TOKENS": np.array([[1, 2]], np.int32)},
                    {"sequence_id": 77})
    with pytest.raises(ValueError, match="out of range"):
        bat.execute({"TOKENS": np.array([[999]], np.int32)},
                    {"sequence_id": 77, "sequence_start": True})
    with pytest.raises(ValueError, match="empty prompt"):
        bat.execute({"TOKENS": np.zeros((1, 0), np.int32)},
                    {"sequence_id": 77, "sequence_start": True})
    # the model must still serve after rejected requests (worker alive)
    out = bat.execute({"TOKENS": np.array([[3]], np.int32)},
                      {"sequence_id": 78, "sequence_start": True,
                       "sequence_end": True})
    assert out["NEXT_TOKEN"].shape == (1, 1)


def test_overflow_frees_slot():
    bat = BatchedDecoderModel(seed=0, slots=1)
    too_long = list(range(10, 10 + TinyDecoderModel.MAX_LEN + 1))
    with pytest.raises(ValueError, match="max_len"):
        bat.execute({"TOKENS": np.array([too_long], np.int32)},
                    {"sequence_id": 5, "sequence_start": True})
    # the failed start must not leak its slot
    bat.execute({"TOKENS": np.array([[5]], np.int32)},
                {"sequence_id": 6, "sequence_start": True,
                 "sequence_end": True})
    assert bat.live_sequences() == 0


def test_restart_in_place():
    """sequence_start on a live sequence restarts it in its slot."""
    ref = TinyDecoderModel(seed=0)
    bat = BatchedDecoderModel(seed=0, slots=2)
    _drive(bat, 9, [1, 2, 3], n=2)  # leaves seq 9 ended... start fresh:
    bat.execute({"TOKENS": np.array([[4]], np.int32)},
                {"sequence_id": 9, "sequence_start": True})
    # restart mid-flight (_drive opens with sequence_start and ends the
    # sequence on its last request)
    toks_restart = _drive(bat, 9, [1, 2, 3], n=4)
    assert toks_restart == _drive(ref, 9, [1, 2, 3], n=4)
    assert bat.live_sequences() == 0


def test_unload_rejects_and_strands_nothing():
    bat = BatchedDecoderModel(seed=0, slots=2)
    bat.execute({"TOKENS": np.array([[3]], np.int32)},
                {"sequence_id": 1, "sequence_start": True,
                 "sequence_end": True})
    bat.unload()
    with pytest.raises(ValueError, match="shutting down"):
        bat.execute({"TOKENS": np.array([[3]], np.int32)},
                    {"sequence_id": 2, "sequence_start": True})


def test_idle_sequences_are_reaped():
    """Abandoned mid-sequence clients must not hold slots forever.

    Reference semantics: max_sequence_idle_microseconds in tritonserver's
    sequence batcher. Fill every slot with sequences that never end (the
    120 s-timeout abandonment shape: client walked away mid-sequence),
    wait past the TTL, then start `slots` fresh sequences — all must be
    admitted because the reaper freed the abandoned slots at window start.
    """
    slots = 3
    bat = BatchedDecoderModel(seed=0, slots=slots, idle_ttl_s=1.0)
    # warm up (first dispatch jit-compiles, which would eat the TTL and
    # reap earlier starts before the fill loop even finishes)
    bat.execute({"TOKENS": np.array([[1]], np.int32)},
                {"sequence_id": 999, "sequence_start": True,
                 "sequence_end": True})
    for seq in range(1, slots + 1):
        bat.execute({"TOKENS": np.array([[5]], np.int32)},
                    {"sequence_id": seq, "sequence_start": True})
    assert bat.live_sequences() == slots
    # capacity genuinely exhausted before the TTL expires
    with pytest.raises(ValueError, match="no free sequence slot"):
        bat.execute({"TOKENS": np.array([[5]], np.int32)},
                    {"sequence_id": 100, "sequence_start": True})
    time.sleep(1.5)
    for seq in range(201, 201 + slots):
        out = bat.execute({"TOKENS": np.array([[7]], np.int32)},
                          {"sequence_id": seq, "sequence_start": True,
                           "sequence_end": True})
        assert out["NEXT_TOKEN"].shape == (1, 1)
    assert bat.live_sequences() == 0


def test_active_sequences_survive_the_reaper():
    """A sequence making requests is never reaped even when each request
    gap is a large fraction of the TTL and OTHER sequences keep running
    reap-triggering windows — activity must refresh the idle clock."""
    ref = TinyDecoderModel(seed=0)
    bat = BatchedDecoderModel(seed=0, slots=2, idle_ttl_s=0.3)
    # warm up so compile time doesn't count against the TTL
    bat.execute({"TOKENS": np.array([[1]], np.int32)},
                {"sequence_id": 999, "sequence_start": True,
                 "sequence_end": True})

    class _SlowJitter:
        def random(self):
            return 0.15 / 0.003  # _drive sleeps jitter.random()*0.003

    stop = threading.Event()
    churn_errors = []

    def churn():
        # seq 12 churns fast windows; each one runs the reaper, so a
        # missing last_seen refresh on seq 11 would reap it mid-drive
        seq = 500
        while not stop.is_set():
            try:
                _drive(bat, seq, [3], n=2)
            except Exception as e:
                churn_errors.append(e)
                return
            seq += 1

    t = threading.Thread(target=churn)
    t.start()
    try:
        # ~0.45 s of slow-gap activity: total > TTL, every gap < TTL
        toks = _drive(bat, 11, [1, 2, 3], n=4, jitter=_SlowJitter())
    finally:
        stop.set()
        t.join()
    assert not churn_errors, churn_errors
    assert toks == _drive(ref, 11, [1, 2, 3], n=4)
    assert bat.live_sequences() == 0


def test_served_over_grpc_sequence_api():
    """End-to-end over the wire via the genai sequence harness."""
    from client_tpu.genai_perf import GenAiPerfRunner
    from client_tpu.server import GrpcInferenceServer, ServerCore

    bat = BatchedDecoderModel(seed=0, slots=8)
    with GrpcInferenceServer(ServerCore([bat])) as server:
        runner = GenAiPerfRunner(server.url, "decoder_lm_batched", "sequence",
                                 prompt_tokens=6, output_tokens=5)
        out = runner.run(3, 6)
        assert out["errors"] == 0, out["error_sample"]
        assert out["sessions"] == 6
    assert bat.live_sequences() == 0
    assert any(width > 1 for width in bat.batch_histogram), (
        "3 concurrent wire sessions never shared a dispatch")


# -- the policy: scheduling by the round. Counts and orderings only. ---------

def _traced(model):
    core = ServerCore([model])
    core.trace_settings.update(trace_level=["TIMESTAMPS"], trace_rate="1")
    return core


def _request(tokens, request_id, **parameters):
    return {"id": request_id, "parameters": parameters, "inputs": [{
        "name": "TOKENS", "datatype": "INT32", "shape": [1, len(tokens)],
        "array": np.array([tokens], np.int32)}]}


class _Callers:
    """Requests sent from threads of their own; ``join`` hands back each
    one's answer, or the error it raised, by request id."""

    def __init__(self, core):
        self._core, self._threads, self.answers = core, [], {}

    def send(self, tokens, request_id, **parameters):
        def call():
            try:
                self.answers[request_id] = self._core.infer(
                    "decoder_lm_batched", "",
                    _request(tokens, request_id, **parameters))
            except Exception as e:
                self.answers[request_id] = e

        self._threads.append(threading.Thread(target=call))
        self._threads[-1].start()

    def join(self):
        for t in self._threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in self._threads)
        return self.answers


def _records(core):
    return {r["request_id"]: r for r in core.recent_traces(1000)}


def test_a_request_joins_a_round_while_a_prompt_is_in_progress():
    model = BatchedDecoderModel(seed=0, slots=2)
    core, gate = _traced(model), GatedStep(model)
    callers = _Callers(core)
    try:
        callers.send([1, 2, 3, 4, 5, 6], "prompt", sequence_id=1,
                     sequence_start=True)
        gate.at(0)  # round 0, the prompt's first token
        callers.send([7], "single", sequence_id=2, sequence_start=True)
        gate.queued(1)
        gate.let(6)
        answers = callers.join()
    finally:
        gate.let(100)
        model.unload()
    assert not any(isinstance(a, Exception) for a in answers.values()), answers
    single, prompt = (_records(core)[name] for name in ("single", "prompt"))
    counts = single["counts"]
    assert counts["rounds_waited"] <= ROUNDS_IN_FLIGHT
    # it waited for no round to run: only for the next one's dispatch call
    assert counts["rounds_own"] == 1
    assert counts["rounds_held"] <= ROUNDS_IN_FLIGHT
    assert prompt["counts"]["rounds_held"] == 0
    assert counts["round_widths"] == [2]  # it shared its round with the prompt
    # it joined, and was answered, before the prompt's last round was sent
    assert single["first_round_id"] == 1 < prompt["first_round_id"] + 5
    assert prompt["counts"]["rounds_own"] == 6
    assert (single["timestamps"]["resolved"]
            < prompt["timestamps"]["last_dispatch"])
    assert gate.calls == 6  # the single request cost no round of its own


def test_rounds_dispatched_ahead_of_the_device_are_bounded():
    """A prompt's rounds are not enqueued at once: never are more than
    ``ROUNDS_IN_FLIGHT`` dispatched beyond the last one read back."""
    model = BatchedDecoderModel(seed=0, slots=2)
    model._ensure_built()
    step, read_back = model._batched_step, model._read_back
    dispatched, back, ahead = [], [], []

    def counted_step(*args):
        dispatched.append(1)
        ahead.append(len(dispatched) - len(back))
        return step(*args)

    def counted_read_back():
        read_back()
        back.append(1)

    model._batched_step, model._read_back = counted_step, counted_read_back
    try:
        model.execute({"TOKENS": np.array([list(range(1, 61))], np.int32)},
                      {"sequence_id": 1, "sequence_start": True,
                       "sequence_end": True})
    finally:
        model.unload()
    assert len(dispatched) == len(back) == 60
    assert max(ahead) == ROUNDS_IN_FLIGHT


def test_one_host_transfer_a_round_whatever_the_width(monkeypatch):
    spans = []

    class counting_span(decoder_batched.span):
        def __init__(self, name, into=None):
            spans.append(name)
            super().__init__(name, into)

    monkeypatch.setattr(decoder_batched, "span", counting_span)
    model = BatchedDecoderModel(seed=0, slots=4)
    core, gate = _traced(model), GatedStep(model)
    callers = _Callers(core)
    try:
        callers.send([1, 2, 3], "prompt", sequence_id=1, sequence_start=True)
        gate.at(0)
        for seq in (2, 3, 4):  # three more for round 1: it is four wide
            callers.send([seq], f"single-{seq}", sequence_id=seq,
                         sequence_start=True, sequence_end=True)
        gate.queued(3)
        gate.let(3)
        answers = callers.join()
    finally:
        gate.let(100)
        model.unload()
    assert not any(isinstance(a, Exception) for a in answers.values()), answers
    assert model.batch_histogram == {1: 2, 4: 1}
    assert spans.count(decoder_batched.SPAN_ROUND_DISPATCH) == 3
    # every round is waited for; the first answers nobody (the prompt is
    # midway) and has nothing to transfer
    assert spans.count(decoder_batched.SPAN_BATCH_DEVICE_WAIT) == 3
    assert spans.count(decoder_batched.SPAN_BATCH_READBACK) == 2
    # the three answered by the four-wide round hold rows of one array: the
    # round's logits came to the host once, and each got a view
    rows = [answers[f"single-{seq}"][0]["outputs"][0]["array"]
            for seq in (2, 3, 4)]
    row_bytes = rows[0].nbytes
    for row in rows:
        assert not row.flags.owndata and row.dtype == np.float32
        apart = abs(row.ctypes.data - rows[0].ctypes.data)
        assert apart % row_bytes == 0 and apart < model.slots * row_bytes


def test_sequence_end_frees_its_slot_at_its_own_round():
    """... while its round-mate's prompt still runs, and a new sequence takes
    that slot in the next round."""
    model = BatchedDecoderModel(seed=0, slots=2)
    core, gate = _traced(model), GatedStep(model)
    callers = _Callers(core)
    try:
        callers.send(list(range(1, 9)), "prompt", sequence_id=1,
                     sequence_start=True)
        gate.at(0)
        callers.send([7], "ending", sequence_id=2, sequence_start=True,
                     sequence_end=True)
        gate.queued(1)
        gate.let()
        gate.at(1)  # round 1: the prompt and the ending sequence
        callers.send([9], "new", sequence_id=3, sequence_start=True)
        gate.queued(1)
        gate.let(7)
        answers = callers.join()
    finally:
        gate.let(100)
        model.unload()
    # both slots were taken when the new sequence arrived: it got the one
    # the ended sequence gave up as round 1 was read back
    assert not any(isinstance(a, Exception) for a in answers.values()), answers
    records = _records(core)
    assert records["ending"]["first_round_id"] == 1
    assert records["new"]["first_round_id"] == 2
    assert records["new"]["counts"]["round_widths"] == [2]
    assert records["prompt"]["counts"]["rounds_own"] == 8
    assert gate.calls == 8


def test_a_step_that_raises_fails_its_own_rounds_requests_only():
    expected = _drive(TinyDecoderModel(seed=0), 1, [1, 2, 3], n=3)
    model = BatchedDecoderModel(seed=0, slots=3)
    core, gate = _traced(model), GatedStep(model, fail_at=4)
    callers = _Callers(core)

    def bystander(tokens, **parameters):
        reply = core.infer("decoder_lm_batched", "", _request(
            tokens, "bystander", sequence_id=1, **parameters))
        return int(reply[0]["outputs"][1]["array"][0, 0])

    try:
        gate.let(3)
        tokens = [bystander([1, 2, 3], sequence_start=True)]
        # sequence 1 is live now and has no request in progress
        callers.send([4, 5, 6], "struck", sequence_id=2, sequence_start=True)
        gate.let()  # call 3: its first token
        gate.at(4)  # call 4, which will raise, is at the gate
        callers.send([8], "later", sequence_id=3, sequence_start=True,
                     sequence_end=True)
        gate.queued(1)
        gate.let(100)
        answers = callers.join()
        assert isinstance(answers["struck"], Exception)
        assert "step 4 failed" in str(answers["struck"])
        assert not isinstance(answers["later"], Exception), answers["later"]
        # the failed round ended its own sequence and no other: the
        # bystander goes on from the cache it had, to the unbatched tokens
        assert model.live_sequences() == 1
        tokens.append(bystander(tokens[-1:]))
        tokens.append(bystander(tokens[-1:], sequence_end=True))
    finally:
        gate.let(100)
        model.unload()
    assert tokens == expected
    assert model.live_sequences() == 0
    assert gate.calls == 8


def test_a_turn_that_fails_outside_a_dispatch_strands_nobody():
    """A round whose logits cannot be read fails every request begun and
    ends every live sequence; the worker lives and serves the next one."""
    model = BatchedDecoderModel(seed=0, slots=2)
    one = {"TOKENS": np.array([[5]], np.int32)}
    model.execute(one, {"sequence_id": 1, "sequence_start": True})
    read_back = model._read_back

    def unreadable():
        model._read_back = read_back
        raise RuntimeError("the round's logits are gone")

    model._read_back = unreadable
    try:
        with pytest.raises(RuntimeError, match="logits are gone"):
            model.execute(one, {"sequence_id": 2, "sequence_start": True})
        assert model.live_sequences() == 0
        with pytest.raises(ValueError, match="no live state"):
            model.execute(one, {"sequence_id": 1})
        out = model.execute(one, {"sequence_id": 3, "sequence_start": True,
                                  "sequence_end": True})
        assert out["NEXT_TOKEN"].shape == (1, 1)
    finally:
        model.unload()
    assert model.live_sequences() == 0


def test_each_phase_is_counted_with_its_rounds_and_the_phases_add_up(monkeypatch):
    """One user, one request at a time: a prompt of three tokens and four
    continuation requests are seven rounds, five of which answer somebody."""
    noted = spans_into_phases(decoder_batched, monkeypatch)
    model = BatchedDecoderModel(seed=0, slots=2)
    try:
        _drive(model, 1, [1, 2, 3], n=5)
    finally:
        model.unload()  # the worker's last wait has ended: every span is in
    counts = {phase: count for phase, (count, _) in model.phases.rows().items()}
    turns = [name for name, _, _, _ in noted].count(decoder_batched.SPAN_BATCH_TURN)
    assert sum(model.batch_histogram.values()) == 7
    assert counts["prepare"] == counts["dispatch"] == counts["device_wait"] == 7
    assert counts["readback"] == 5  # a prompt midway has nothing to transfer
    assert counts["record"] == 7 + 5  # after a dispatch, and after a transfer
    assert counts["admit"] == 5  # a request a turn, in five turns
    # once a turn, whatever the turn found to do
    assert counts["collect"] == counts["hand_out"] == turns
    # a request cannot come before the answer before it: the worker waited
    # for each, and for the sentinel
    assert counts["wait_work"] == 5 + 1
    check_the_phases_tile_the_workers_time(model.phases, noted)
    # every phase lies inside a turn, the wait for work too
    turn_spans = [(start, end) for name, start, end, _ in noted
                  if name == decoder_batched.SPAN_BATCH_TURN]
    for name, start, end, feeds in noted:
        if feeds:
            assert any(s <= start and end <= e for s, e in turn_spans), name


def test_a_sequence_that_sits_a_round_out_has_a_stride_of_two():
    model = BatchedDecoderModel(seed=0, slots=2)
    core, gate = _traced(model), GatedStep(model)
    callers = _Callers(core)
    try:
        callers.send([5], "a-start", sequence_id=1, sequence_start=True)
        gate.at(0)  # round 0: sequence 1 alone
        callers.send([1, 2], "b-prompt", sequence_id=2, sequence_start=True)
        gate.queued(1)
        gate.let()
        gate.at(1)  # round 1: the prompt's first token; sequence 1 sits out
        callers.send([7], "a-next", sequence_id=1)
        gate.queued(1)
        gate.let()
        gate.at(2)  # round 2: the prompt's second token and sequence 1
        gate.let()
        callers.join()
        callers.send([8], "a-last", sequence_id=1, sequence_end=True)
        gate.at(3)  # round 3: sequence 1 again, the round after
        gate.let()
        callers.join()
    finally:
        gate.let(100)
        model.unload()
    records = _records(core)
    assert [records[r]["first_round_id"]
            for r in ("a-start", "b-prompt", "a-next", "a-last")] == [0, 1, 2, 3]
    strides = {r: records[r]["counts"]["stride_rounds"] for r in records}
    # a sequence's first request has no token before it
    assert strides == {"a-start": None, "b-prompt": None, "a-next": 2, "a-last": 1}
    series = {name: metric["series"][0]["value"] for name, metric in
              core.metrics_registry().snapshot().items()
              if name.startswith("client_tpu_server_sequence_stride")}
    assert series == {"client_tpu_server_sequence_stride_rounds": 3,
                      "client_tpu_server_sequence_stride_count": 2}

"""The one traffic engine: seeded token sessions over GRPC, closed loop.

A traffic file (``benchmark/traffic/<mix>.json``) says which API the users
speak, over how many seconds they start one after another, and names the file
of lengths (``benchmark/lengths/<name>.json``) their sessions are drawn from;
how many users there are belongs to the cell (``cells/<cell>.json``):

- ``"api": "sequence"``: unary ``infer`` calls with the sequence parameters:
  the prompt with ``sequence_start``, then one request a token, fed back from
  ``NEXT_TOKEN``, ``sequence_end`` on the last. Default outputs, so every
  response carries the logits row as well, and the user reads it.
- ``"api": "stream"``: one request a session on a decoupled bidi stream,
  carrying ``MAX_TOKENS``; one response a token.

Each user runs session after session with no think time. The lengths are the
mid-quantiles of the lognormals the lengths file states; the seed draws their
order, their pairing and the token ids, so every seed holds the same set of
sizes in another order.

This module never imports jax: the users live in a process that must not
touch the chip.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np

_SEED_MASK = (1 << 63) - 1
BLOCK = 8  # sessions in a row that hold one length of each stratum


class _Cut(Exception):
    """The run closed while this session was in flight: it is abandoned,
    neither finished nor failed."""


def length_pool(spec: Dict[str, float], count: int) -> np.ndarray:
    """``count`` lengths, sorted, at the mid-quantiles of the lognormal with
    the given ``mean`` and ``sigma`` (so its median is ``mean * exp(-sigma^2 /
    2)``), clipped to ``[min, max]``."""
    normal = NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / count) for i in range(count)])
    median = spec["mean"] * math.exp(-spec["sigma"] ** 2 / 2.0)
    lengths = np.rint(median * np.exp(spec["sigma"] * z))
    return np.clip(lengths, spec["min"], spec["max"]).astype(np.int64)


def seeded_order(count: int, rng: np.random.Generator) -> List[int]:
    """The indices ``0 .. count-1`` of a sorted pool in an order drawn from
    ``rng`` that stays balanced: the pool is cut into ``BLOCK`` strata of
    neighbours, and every ``BLOCK`` entries in a row, counted from the start,
    hold one index of each stratum. Which member of a stratum a block gets,
    and the order inside a block, are the draw. A pool that does not divide
    into blocks is one block."""
    block = BLOCK if count % BLOCK == 0 else count
    members = count // block
    of_stratum = [stratum * members + rng.permutation(members)
                  for stratum in range(block)]
    order: List[int] = []
    for b in range(members):
        order.extend(int(of_stratum[stratum][b])
                     for stratum in rng.permutation(block))
    return order


class SessionPlan:
    """Session ``i`` of a seed: its prompt tokens and its output length.

    One cycle is the whole pool, every prompt length and every output length
    once. The seed draws, cycle by cycle, a balanced order for the prompts
    and another for the outputs (``seeded_order``), so any eight sessions in
    a row hold short and long prompts, and short and long outputs, in the
    pool's own proportions, paired as the seed has it; and it draws the token
    ids (and, in the serving process, the weights)."""

    def __init__(self, traffic: Dict[str, Any], vocab: int, seed: int):
        lengths = traffic["lengths"]
        self.vocab = int(vocab)
        self.seed = int(seed) & _SEED_MASK
        self.count = int(lengths["pool"])
        self.prompts = length_pool(lengths["prompt"], self.count)
        self.outputs = length_pool(lengths["output"], self.count)
        self.longest = int(self.prompts.max() + self.outputs.max())
        self._cycles: Dict[int, Any] = {}

    def _cycle(self, c: int):
        if c not in self._cycles:
            rng = np.random.default_rng([self.seed, 3, c])
            self._cycles[c] = (seeded_order(self.count, rng),
                               seeded_order(self.count, rng))
        return self._cycles[c]

    def session(self, index: int) -> Dict[str, Any]:
        prompt_order, output_order = self._cycle(index // self.count)
        k = index % self.count
        rng = np.random.default_rng([self.seed, 1, index])
        prompt = rng.integers(0, self.vocab,
                              size=int(self.prompts[prompt_order[k]]),
                              dtype=np.int32)
        return {"index": index, "prompt": prompt,
                "tokens_out": int(self.outputs[output_order[k]])}


class _Counter:
    def __init__(self, start: int = 0):
        self._lock = threading.Lock()
        self._next = start

    def take(self) -> int:
        with self._lock:
            value = self._next
            self._next += 1
            return value


def _tensor(grpcclient, name: str, array: np.ndarray):
    tensor = grpcclient.InferInput(name, list(array.shape), "INT32")
    tensor.set_data_from_numpy(array)
    return tensor


class _SequenceUser:
    """One user on the sequence API, with a channel of its own."""

    def __init__(self, grpcclient, url: str, model: str, user: int,
                 timeout_s: float):
        self._grpc = grpcclient
        self._client = grpcclient.InferenceServerClient(url)
        self._model = model
        self._user = user
        self._timeout_s = timeout_s

    def run(self, session: Dict[str, Any], record: Dict[str, Any],
            close_at: float) -> None:
        # a sequence id no other user or session of this run has had
        seq_id = (self._user + 1) * 10_000_000 + session["index"] + 1
        n = session["tokens_out"]
        feed = session["prompt"][None, :]
        record["t_send"] = time.perf_counter()
        for i in range(n):
            if time.perf_counter() >= close_at:
                raise _Cut()
            result = self._client.infer(
                self._model, [_tensor(self._grpc, "TOKENS", feed)],
                sequence_id=seq_id, sequence_start=(i == 0),
                sequence_end=(i == n - 1), client_timeout=self._timeout_s)
            token = int(result.as_numpy("NEXT_TOKEN").reshape(-1)[0])
            record["token_times"].append(time.perf_counter())
            record["tokens"].append(token)
            # the user has the logits row and uses it: the token the server
            # names has to be the row's largest
            if int(result.as_numpy("LOGITS").reshape(-1).argmax()) != token:
                record["argmax_mismatch"] += 1
            feed = np.array([[token]], dtype=np.int32)

    def stat(self) -> Dict[str, int]:
        return self._client.client_infer_stat()

    def close(self) -> None:
        self._client.close()


class _StreamUser:
    """One user on a decoupled bidi stream that stays open across sessions."""

    def __init__(self, grpcclient, url: str, model: str, user: int,
                 timeout_s: float):
        self._grpc = grpcclient
        self._client = grpcclient.InferenceServerClient(url)
        self._model = model
        self._timeout_s = timeout_s
        self._inbox: "queue.Queue" = queue.Queue()
        self._client.start_stream(
            callback=lambda result, error: self._inbox.put(
                (time.perf_counter(), result, error)))

    def run(self, session: Dict[str, Any], record: Dict[str, Any],
            close_at: float) -> None:
        n = session["tokens_out"]
        inputs = [_tensor(self._grpc, "TOKENS", session["prompt"][None, :]),
                  _tensor(self._grpc, "MAX_TOKENS", np.array([n], np.int32))]
        record["t_send"] = time.perf_counter()
        self._client.async_stream_infer(
            self._model, inputs, request_id=str(session["index"]),
            enable_empty_final_response=True)
        while True:
            try:
                at, result, error = self._inbox.get(timeout=max(0.0, min(
                    self._timeout_s, close_at - time.perf_counter())))
            except queue.Empty:
                if time.perf_counter() >= close_at:
                    raise _Cut() from None
                raise
            if error is not None:
                raise error
            if result.is_final_response() and result.is_null_response():
                return
            index = int(result.as_numpy("INDEX").reshape(-1)[0])
            if index != len(record["tokens"]):
                raise RuntimeError(
                    f"token {index} arrived where {len(record['tokens'])} was due")
            record["token_times"].append(at)
            record["tokens"].append(
                int(result.as_numpy("NEXT_TOKEN").reshape(-1)[0]))

    def stat(self) -> Dict[str, int]:
        return self._client.client_infer_stat()

    def close(self) -> None:
        self._client.stop_stream(cancel_requests=True)
        self._client.close()


class SessionEngine:
    """``users`` threads, each running sessions one after another."""

    def __init__(self, url: str, model: str, api: str, users: int,
                 session_timeout_s: float = 120.0):
        import client_tpu.grpc as grpcclient

        if api not in ("sequence", "stream"):
            raise ValueError(f"unknown api {api!r} in the traffic file")
        kind = _SequenceUser if api == "sequence" else _StreamUser
        self.users = [kind(grpcclient, url, model, user, session_timeout_s)
                      for user in range(int(users))]

    def client_stats(self) -> Dict[str, int]:
        """The users' ``InferStat`` counters, summed."""
        total: Dict[str, int] = {}
        for user in self.users:
            for key, value in user.stat().items():
                total[key] = total.get(key, 0) + int(value)
        return total

    def run(self, plan: SessionPlan, first_index: int,
            seconds: Optional[float] = None,
            sessions_per_user: Optional[int] = None,
            drain: bool = False, stagger: float = 0.0) -> Dict[str, Any]:
        """Run until ``seconds`` have passed or each user has run
        ``sessions_per_user``. The users begin one after another, evenly over
        the first ``stagger`` seconds. No session is begun after ``seconds``;
        one in flight then is abandoned where it stands (its record says
        ``cut``), or with ``drain`` runs to its end. Returns the records and
        the start time."""
        counter = _Counter(first_index)
        records: List[Dict[str, Any]] = []
        records_lock = threading.Lock()
        start = threading.Barrier(len(self.users) + 1)
        window = {"t0": 0.0}

        def loop(user, wait: float) -> None:
            start.wait()
            time.sleep(wait)
            done = 0
            close_at = (window["t0"] + seconds
                        if seconds is not None and not drain else float("inf"))
            while True:
                if seconds is not None and \
                        time.perf_counter() >= window["t0"] + seconds:
                    return
                if sessions_per_user is not None and done >= sessions_per_user:
                    return
                session = plan.session(counter.take())
                record = {"index": session["index"],
                          "prompt": session["prompt"],
                          "tokens_out": session["tokens_out"],
                          "t_send": 0.0, "token_times": [], "tokens": [],
                          "argmax_mismatch": 0, "error": None, "cut": False}
                try:
                    user.run(session, record, close_at)
                except _Cut:
                    record["cut"] = True
                except Exception as e:  # a failed session is counted, and
                    # the user goes on to its next one as a real user would
                    record["error"] = f"{type(e).__name__}: {e}"
                with records_lock:
                    records.append(record)
                done += 1

        threads = [threading.Thread(
            target=loop, args=(u, stagger * i / len(self.users)), daemon=True,
            name=f"user-{i}")
                   for i, u in enumerate(self.users)]
        for t in threads:
            t.start()
        window["t0"] = time.perf_counter()
        start.wait()
        for t in threads:
            t.join()
        records.sort(key=lambda r: r["index"])
        return {"records": records, "t0": window["t0"]}

    def close(self) -> None:
        for user in self.users:
            try:
                user.close()
            except Exception:
                pass

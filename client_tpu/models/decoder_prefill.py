"""Stateless batched prompt scoring over the decoder fixtures.

``decoder_lm`` serves one sequence per request behind the v2 sequence API —
the right contract for incremental decode, but useless as a scatter-gather
target: a sharded logical request must be stateless (any shard may land on
any pinned replica with no prior server-side state) and must carry an axis
the client can split. This module is that contract:

- ``decoder_lm_prefill``: TOKENS INT32 ``[-1, T]`` (a batch of equal-length
  prompts) -> LOGITS FP32 ``[-1, VOCAB]`` + NEXT_TOKEN INT32 ``[-1, 1]``,
  each row scored independently by running the decoder's compiled
  single-token step over the prompt with a fresh KV cache — the SAME step
  function ``decoder_lm`` serves, so row b's logits are bit-identical to
  scoring that prompt as a one-shot sequence.
- ``decoder_lm_tp_prefill``: the same contract over ``decoder_lm_tp``'s
  mesh-sharded step (Megatron-style head-sharded attention, see
  models/decoder_tp.py). TPDecoderModel's guarantee is BIT-equality with
  the single-device decoder, so the tp-prefill replica fleet is
  bit-comparable against a local single-process ``decoder_lm_prefill``
  reference — exactly the exactness oracle the sharded scatter-gather
  client (client_tpu/shard.py) is verified against: rows sharded across N
  tp replicas and gathered must equal the reference batch, bit for bit.

Rows are independent by construction (fresh cache per row), which is what
makes the batch axis a legal ``ShardLayout`` axis: splitting [B, T] into
contiguous row blocks and concatenating the per-shard [b_i, VOCAB] logits
reassociates nothing.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from .base import Model, TensorSpec
from .decoder import RungCount, TinyDecoderModel
from .decoder_tp import TPDecoderModel


class PrefillDecoderModel(Model):
    """``decoder_lm_prefill`` / ``decoder_lm_tp_prefill``: batched
    stateless prompt scoring (one fresh-cache decode per row).

    ``mesh``/``axis``/``tp_degree`` pass through to
    :class:`TPDecoderModel` so a multi-replica *in-process* test topology
    can give each replica a disjoint device slice. TP executions are
    additionally serialized by a process-wide lock: two replica servers
    hosted in ONE process (the test/bench topology) would otherwise run
    two SPMD programs concurrently over the same virtual devices and
    stall XLA's collective rendezvous — real deployments run one replica
    per process and never contend."""

    platform = "jax"
    max_batch_size = 0
    stateful = False

    _TP_EXEC_LOCK = threading.Lock()

    def __init__(self, tp: bool = False, seed: int = 0, mesh=None,
                 axis: str = "model", tp_degree: Optional[int] = None):
        super().__init__()
        self._tp = tp
        self._inner = (
            TPDecoderModel(seed=seed, tp=tp_degree, mesh=mesh, axis=axis)
            if tp else TinyDecoderModel(seed=seed))
        self.name = "decoder_lm_tp_prefill" if tp else "decoder_lm_prefill"
        self.steps_by_rung = RungCount()

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("TOKENS", "INT32", [-1, -1])]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("LOGITS", "FP32", [-1, self._inner.VOCAB]),
            TensorSpec("NEXT_TOKEN", "INT32", [-1, 1]),
        ]

    def execute(self, inputs: Dict[str, np.ndarray],
                parameters: Dict[str, Any]) -> Dict[str, np.ndarray]:
        inner = self._inner
        inner._ensure_built()
        tokens = np.asarray(inputs["TOKENS"])
        if tokens.ndim != 2 or tokens.shape[1] < 1:
            raise ValueError(
                f"TOKENS must be [batch, prompt_len >= 1], got "
                f"{list(tokens.shape)}")
        if tokens.shape[1] > inner.MAX_LEN:
            raise ValueError(
                f"prompt longer than max_len {inner.MAX_LEN}")
        tokens = tokens.astype(np.int64)
        if np.any(tokens < 0) or np.any(tokens >= inner.VOCAB):
            raise ValueError(f"tokens out of range [0, {inner.VOCAB})")
        rows = []
        guard = (self._TP_EXEC_LOCK if self._tp
                 else contextlib.nullcontext())
        with guard:
            for row in tokens:
                caches = inner._fresh_cache()
                logits = None
                # one compiled step per token, fresh cache per row: the
                # same executables (and therefore the same bits) as serving
                # the row through the sequence API in one start+end request
                for pos, tok in enumerate(row.tolist()):
                    logits, caches = inner.decode_step(
                        caches, int(tok), pos, self.steps_by_rung)
                rows.append(
                    np.asarray(logits, dtype=np.float32).reshape(-1))
        logits_np = np.stack(rows).astype(np.float32)
        return {
            "LOGITS": logits_np,
            "NEXT_TOKEN": logits_np.argmax(axis=1).astype(
                np.int32).reshape(-1, 1),
        }

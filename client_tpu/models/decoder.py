"""Autoregressive decoder with a KV cache behind the v2 sequence API.

The reference's sequence extension (sequence_id/start/end request
parameters — SURVEY §2.4 sequence examples; simple_sequence is the
accumulator fixture) exists precisely for stateful models. This is the
real thing: a transformer decoder whose per-sequence KV cache lives in
server-side sequence state, exercised one token per request the way an
LLM serving loop drives it.

TPU-first choices:
- the KV cache is STATIC-SHAPE ([max_len, ...] preallocated,
  ``lax.dynamic_update_slice`` at the current position) so the decode step
  compiles once a RUNG and every token reuses one of a few executables — no
  shape-polymorphic retraces;
- the attention mask is position-based (iota <= pos) rather than
  shape-based, so one compiled step serves every position under its rung;
- the attention reads the LIVE PREFIX of the cache, not every reserved
  position: ``live`` is a compile-time length from a short ladder
  (``ladder``: max_len, max_len/4, ... down to 256), the host, which holds
  every position as an int, picks the shortest rung that covers the step
  (``rung_for``), and every rung is compiled before the first step is
  dispatched (``_ensure_warm``), never on demand. The row at ``pos`` is
  written into the whole cache first; the prefix that is read contains it;
- weights and math are bf16 (MXU-native) with fp32 softmax/logits.

Wire contract (stateful, one token per request after the start request):
  inputs:  TOKENS INT32[1, -1] — full prompt when sequence_start, exactly
           one token otherwise
  outputs: LOGITS FP32[1, vocab] (next-token logits, fp32)
           NEXT_TOKEN INT32[1, 1] (greedy argmax, a convenience)
"""

from __future__ import annotations

import functools
import math
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .base import Model, TensorSpec

# the shortest rung of a ladder: under it a step is its weights and the
# prefix is not worth a program
SHORTEST_RUNG = 256
# the slots whose caches one turn of a round's attention reads, at most: where
# it reads in turns, the round reads the occupied slots rounded up to a
# multiple of ``slots_a_turn``
SLOTS_A_TURN = 4


# the lanes of a tile of the chip's memory: a row of a cache this wide or
# wider lies along them, and a narrower one has its positions there
LANES = 128


def heads_a_row(heads: int, head_dim: int) -> int:
    """The heads that one row of a table of caches holds side by side: as
    many as fill the ``LANES``, and a divisor of ``heads``; one where a head
    fills them alone."""
    return math.gcd(heads, max(1, LANES // head_dim))


def slots_a_turn(slots: int) -> int:
    """The slots a turn of a round's attention takes of a table of
    ``slots``: the turns are whole, so it divides them."""
    return math.gcd(slots, SLOTS_A_TURN)


def in_whole_turns(slots: int, occupied: int) -> int:
    """The slots that whole turns of ``slots_a_turn(slots)`` take to cover
    the lowest ``occupied`` of ``slots``."""
    a_turn = slots_a_turn(slots)
    return -(-occupied // a_turn) * a_turn


def ladder_of(max_len: int) -> Tuple[int, ...]:
    """The lengths a step's attention may read, shortest first: ``max_len``,
    a quarter of it, a sixteenth ... for as long as the rung is at least
    ``SHORTEST_RUNG``. The ratio is the set-up budget speaking: every rung is
    one more program to build before serving (PERF.md section 6, PR 31)."""
    rungs = [max_len]
    while rungs[0] % 4 == 0 and rungs[0] // 4 >= SHORTEST_RUNG:
        rungs.insert(0, rungs[0] // 4)
    return tuple(rungs)


def write_rows_in_turns(caches, rows, pos, active):
    """``TinyDecoderModel``'s ``write_table_rows`` off the chip: a ``while``
    of one turn an active slot, each writing that slot's two rows with one
    update a cache, where they lie."""
    import jax.numpy as jnp
    from jax import lax

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


class RungCount:
    """What a served model's decoder was asked for, counted: steps
    dispatched by the rung they read, which ``ServerCore.metrics_registry``
    reads as ``client_tpu_server_decode_steps{model,live}``; of those the
    steps that attended to a chosen subset of the cache (``selecting_steps``);
    and the prompts' side: tokens prefilled, the dispatches that took
    (``prefill_chunks``) and the host's time from a stream's cache to its
    last prefill dispatch's return (``prefill_ns``); and, for a decoder that
    keeps a window beside summaries of what came before it, the rows of each
    kind its decode steps attended to and the summaries its tokens completed
    (``window_rows_read``, ``summary_rows_read``, ``summaries_written``), all
    counted on the host from positions; and, for a decoder whose dispatches
    tally on the device the experts their grouped products read, those and
    the dispatches, by program (``reached``); and the key and value rows
    that rounds wrote into a table, by how they were written (``written``:
    ``TinyDecoderModel.rows_path``)."""

    TOTALS = ("selecting_steps", "prefill_tokens", "prefill_chunks", "prefill_ns")
    ROWS = ("window_rows_read", "summary_rows_read", "summaries_written")

    def __init__(self):
        self._lock = threading.Lock()
        self._steps: Dict[int, int] = {}
        self._totals = dict.fromkeys(self.TOTALS, 0)
        self._rows = dict.fromkeys(self.ROWS, 0)
        self._reached: Dict[str, Tuple[int, int]] = {}
        self._written: Dict[str, int] = {}

    def add(self, live: int) -> None:
        with self._lock:
            self._steps[live] = self._steps.get(live, 0) + 1

    def add_selecting(self) -> None:
        with self._lock:
            self._totals["selecting_steps"] += 1

    def add_prefill(self, tokens: int, chunks: int = 1) -> None:
        """``tokens`` prompt tokens prefilled in ``chunks`` dispatches."""
        with self._lock:
            self._totals["prefill_tokens"] += tokens
            self._totals["prefill_chunks"] += chunks

    def add_prefill_ns(self, ns: int) -> None:
        with self._lock:
            self._totals["prefill_ns"] += ns

    def add_rows(self, window: int, summary: int, written: int) -> None:
        with self._lock:
            for name, n in zip(self.ROWS, (window, summary, written)):
                self._rows[name] += n

    def add_reached(self, program: str, experts: int, dispatches: int) -> None:
        """``dispatches`` of ``program`` whose routed layers read ``experts``
        experts, summed over the layers."""
        with self._lock:
            reached, n = self._reached.get(program, (0, 0))
            self._reached[program] = (reached + experts, n + dispatches)

    def add_written(self, path: str, rows: int) -> None:
        with self._lock:
            self._written[path] = self._written.get(path, 0) + rows

    def by_rung(self) -> Dict[int, int]:
        with self._lock:
            return dict(self._steps)

    def totals(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._totals)

    def rows(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._rows)

    def reached(self) -> Dict[str, Tuple[int, int]]:
        """``{program: (experts, dispatches)}``, empty for a decoder that
        tallies none."""
        with self._lock:
            return dict(self._reached)

    def written(self) -> Dict[str, int]:
        """``{path: rows}``, empty for a decoder whose rounds wrote none."""
        with self._lock:
            return dict(self._written)


class TinyDecoderModel(Model):
    """``decoder_lm``: a pre-norm transformer decoder of the GPT-2 family,
    at the fixture's sizes below unless a subclass sets them
    (``benchmark/builders.py`` does)."""

    name = "decoder_lm"
    platform = "jax"
    max_batch_size = 0
    stateful = True

    VOCAB = 256
    D_MODEL = 128
    HEADS = 4
    LAYERS = 2
    MAX_LEN = 128

    def __init__(self, seed: int = 0, attention_impl: str = "einsum"):
        """``attention_impl``: "einsum" (dense, default) or "pallas" (the
        ops/decode_attention.py flash-decoding kernel — same math, K/V
        blocks streamed through VMEM; interpret mode off-TPU)."""
        if attention_impl not in ("einsum", "pallas"):
            raise ValueError(f"unknown attention_impl {attention_impl!r}")
        super().__init__()
        self._seed = seed
        self._attention_impl = attention_impl
        self._lock = threading.Lock()
        self._params = None
        self._step_fn = None
        # the step of a table of slots (``_fresh_table``) as the slot
        # batcher dispatches it (decoder_batched.py), and the round built on
        # the same step for a model whose streams share a dispatch
        # (generate.py); None where this decoder has no such program: the
        # stream path takes no Pallas kernel, and a subclass that jits a
        # step of its own brings neither
        self._table_fn = None
        self._round_fn = None
        # a prompt's chunk into a slot of that table, for a decoder whose
        # prompts are too long for a round a token: ``(params, table, fed,
        # tokens, ctl, live=)`` gives ``(fed, table)`` (stream_rounds.py);
        # None where a prompt rides the rounds a token at a time; and the
        # positions a chunk holds
        self._slot_prefill_fn = None
        self._slot_prefill_chunk = 0
        # the lengths ``_step_fn`` takes as ``live``, shortest first: the
        # ladder where ``_build`` below made the step, the whole length alone
        # where a subclass jits a step of its own (decoder_tp.py)
        self._rungs = (self.MAX_LEN,)
        self._warm = False  # every rung's program is compiled
        self._warm_lock = threading.Lock()
        # how ``write_table_rows`` writes a round's rows where this decoder's
        # programs call it: "kernel" or "loop" (set by ``_build``); None
        # where they never call it
        self.rows_path: Optional[str] = None
        self.steps_by_rung = RungCount()
        self._sequences: Dict[Any, Dict[str, Any]] = {}
        # per-sequence serialization: concurrent requests on one sequence_id
        # must not interleave read-compute-write (lost KV updates otherwise;
        # the reference's sequence batcher serializes per CORRID the same way)
        self._seq_locks: Dict[Any, threading.Lock] = {}

    def inputs(self) -> List[TensorSpec]:
        return [TensorSpec("TOKENS", "INT32", [1, -1])]

    def outputs(self) -> List[TensorSpec]:
        return [
            TensorSpec("LOGITS", "FP32", [1, self.VOCAB]),
            TensorSpec("NEXT_TOKEN", "INT32", [1, 1]),
        ]

    # -- the ladder ----------------------------------------------------------
    @classmethod
    def ladder(cls) -> Tuple[int, ...]:
        """``ladder_of(MAX_LEN)``: a function of ``MAX_LEN`` alone."""
        return ladder_of(cls.MAX_LEN)

    def rung_for(self, reach: int) -> int:
        """The shortest rung that covers ``reach`` positions: a step at
        ``pos`` reaches ``pos + 1``."""
        return next(live for live in self._rungs if live >= reach)

    # -- model ---------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from .. import ops
        from ..ops import row_write

        D, H, L, V, M = (self.D_MODEL, self.HEADS, self.LAYERS, self.VOCAB,
                         self.MAX_LEN)
        Dh = D // H
        rng = np.random.default_rng(self._seed)

        def w(*shape, scale=None):
            scale = scale if scale is not None else (shape[0] ** -0.5)
            return jnp.asarray(
                rng.standard_normal(shape).astype(np.float32) * scale,
                dtype=jnp.bfloat16)

        params = {
            "embed": w(V, D, scale=0.02),
            "pos": w(M, D, scale=0.02),
            "layers": [
                {
                    "qkv": w(D, 3 * D),
                    "proj": w(D, D),
                    "mlp_in": w(D, 4 * D),
                    "mlp_out": w(4 * D, D),
                }
                for _ in range(L)
            ],
            "unembed": w(D, V, scale=0.02),
        }

        def norm(x):
            x32 = x.astype(jnp.float32)
            mu = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            return ((x32 - mu) * lax.rsqrt(var + 1e-5)).astype(x.dtype)

        def write_table_rows(caches, rows, pos, active):
            """Stacked caches (k, v), each [slots, H/P, M, P x Dh] as
            ``_fresh_table`` lays them, with ``rows``, each [W, H/P, 1,
            P x Dh], of the table's leading W slots at ``pos`` [W], where
            ``active`` [W]: one turn an active slot, each writing that
            slot's two rows into the donated buffers where they lie, so the
            work follows the number of active slots and an inactive one (a
            full one, a freed one) is not touched at all.

            The table holds ``P`` heads a row of the chip's ``LANES``
            (``heads_a_row``), so that a position's row of a slot fills a
            tile's lanes at every head width: it is one sublane of H x Dh /
            128 tiles, and the turn updates it alone, 1.05 us a row at
            heads of 128 (PERF.md section 6). (Laid a head a row, rows of 64
            have their *positions* on the lanes, where an update of the row
            alone costs 6.7 us and one of the aligned 128 positions round it
            2.4.) Nothing is masked over a cache: for that the compiler lays
            every stacked cache out anew and back.

            On the chip (``rows_path`` "kernel", where the kernel ``takes``
            the table: rows across whole tiles of lanes) one Pallas kernel a
            layer writes every active slot's rows by DMA instead
            (``ops/row_write.py``): the loop's turns, and not its bytes, cost
            2.75 ms of a 5.54 ms round of sixteen gpt2-large members on a
            v5e (PERF.md section 5). Elsewhere the loop stands: the CPU's
            compiler has no lanes, and a kernel a layer in interpret mode
            would slow every round there."""
            if self.rows_path == "kernel":
                return row_write.write_table_rows(
                    caches, rows, pos, active, interpret=not ops._on_tpu())
            return write_rows_in_turns(caches, rows, pos, active)

        def embed(params, token, pos):
            with jax.named_scope("embed"):
                return params["embed"][token] + params["pos"][pos]  # [D]

        def qkv_rows(layer, x):
            """The query [H, Dh] and the new key and value rows, each
            [H, 1, Dh], of one token."""
            with jax.named_scope("attn_qkv"):
                h = norm(x)
                qkv = h @ layer["qkv"]  # [3D]
                q, k_new, v_new = jnp.split(qkv, 3)
                return (q.reshape(H, Dh), k_new.reshape(H, 1, Dh),
                        v_new.reshape(H, 1, Dh))

        def as_rows(new, cache):
            """A token's new rows [.., H, 1, Dh] laid as ``cache`` [.., H/P,
            M, P x Dh] lays a position: heads ``P n`` to ``P n + P - 1`` lie
            side by side in ``qkv``'s output, so this is a reshape."""
            return new.reshape(new.shape[:-3] + (cache.shape[-3], 1,
                                                 cache.shape[-1]))

        # How a product reads a cache that holds ``P`` heads a row
        # (``_fresh_table``, ``heads_a_row``): the query of each head takes
        # its own Dh lanes of a [H/P, P, P x Dh] operand, zeros elsewhere,
        # and one product contracts the whole row; the weighing gives every
        # head the whole row, and each keeps its own lanes. The bytes read
        # are the cache's, where it lies; the zeros add exact zeros. Such a
        # product is the matrix unit's, which takes float32 in bfloat16
        # passes at the default precision: on a v5e it read 1e-3 to 2e-3 off
        # a head's own products of a head a row, which are float32 (PERF.md
        # section 6). So it is at ``HIGHEST``: their float32 math. At one
        # head a row (P of 1) the products are the plain ones at
        # ``precision``. ``at`` is the einsum letters of the dimensions
        # before the heads.
        highest = lax.Precision.HIGHEST

        def scored(at, q32, k32, precision=None):
            """``q32`` [.., H, Dh] against ``k32`` [.., H/P, m, P x Dh]:
            [.., H, m]."""
            if k32.shape[-1] == Dh:
                return jnp.einsum(f"{at}hd,{at}hmd->{at}hm", q32, k32,
                                  precision=precision)
            rows, P, lead = k32.shape[-3], k32.shape[-1] // Dh, q32.shape[:-2]
            own = np.eye(P, dtype=np.float32)[:, :, None]
            spread = (q32.reshape(lead + (rows, P, 1, Dh)) * own).reshape(
                lead + (rows, P, P * Dh))
            return jnp.einsum(f"{at}hjc,{at}hmc->{at}hjm", spread, k32,
                              precision=highest).reshape(
                                  lead + (H, k32.shape[-2]))

        def weighed(at, probs, v32, precision=None):
            """``probs`` [.., H, m] over ``v32`` [.., H/P, m, P x Dh]:
            [.., H, Dh]."""
            if v32.shape[-1] == Dh:
                return jnp.einsum(f"{at}hm,{at}hmd->{at}hd", probs, v32,
                                  precision=precision)
            rows, P, lead = v32.shape[-3], v32.shape[-1] // Dh, probs.shape[:-2]
            whole = jnp.einsum(f"{at}hjm,{at}hmc->{at}hjc", probs.reshape(
                lead + (rows, P, probs.shape[-1])), v32, precision=highest)
            # each head keeps its own lanes by a select and a sum over the
            # heads, not by slices: the chip's compiler gave the second
            # head of a row the first one's lanes where they were sliced,
            # and served tokens 3.5 to 5 under the reference's best logit
            # (PERF.md section 6)
            own = np.arange(P * Dh) // Dh == np.arange(P)[:, None]
            return jnp.sum(jnp.where(own, whole, 0.0), axis=-2).reshape(
                lead + (H, Dh))

        def attention(q, k, v, pos, *, live):
            """The attention of one token over a sequence's cache ``k``,
            ``v`` with the row at ``pos`` written: [H, Dh]."""
            with jax.named_scope("attention"):
                if self._attention_impl == "pallas":
                    from ..ops.decode_attention import decode_attention

                    return decode_attention(
                        q[None], k[None], v[None],
                        jnp.asarray(pos, jnp.int32).reshape(1),
                    )[0]  # bf16 (kernel accumulates fp32)
                # position-based mask: only slots <= pos attend, and they
                # lie in the prefix (the row just written at ``pos`` among
                # them)
                scores = scored(
                    "", q.astype(jnp.float32),
                    k[:, :live].astype(jnp.float32)) * (Dh ** -0.5)
                mask = jnp.arange(live) <= pos
                scores = jnp.where(mask[None, :], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                return weighed(
                    "", probs, v[:, :live].astype(jnp.float32))

        def read_table(q, k, v, pos):
            """The attention of every slot of a table [slots, H/P, M, P x
            Dh] over its whole cache, the top rung: ``attention`` under
            ``vmap`` makes one product a table, and beside the rows'
            ``while`` the chip's compiler then stages every table through
            fast memory and back each round: 6.4 of a 12.3 ms round of
            sixteen at 1,024 positions (PERF.md section 6). Here each
            product is a ``while`` of two turns, half the positions of every
            slot a turn, each a slice of the table fused into the product:
            the table is read where it lies, once. The same float32
            products, mask and softmax: the products are at ``HIGHEST``
            precision, since at the default the chip rounds their float32
            operands to bfloat16."""
            slots = q.shape[0]
            with jax.named_scope("attention"):
                half = M // 2

                def rows(cache, n):
                    return lax.dynamic_slice_in_dim(
                        cache, n * half, half, axis=2).astype(jnp.float32)

                q32 = q.astype(jnp.float32)

                def score(n, scores):
                    return lax.dynamic_update_slice_in_dim(
                        scores, scored("s", q32, rows(k, n), highest),
                        n * half, axis=2)

                scores = lax.fori_loop(
                    0, 2, score,
                    jnp.zeros((slots, H, M), jnp.float32)) * (Dh ** -0.5)
                mask = jnp.arange(M)[None, :] <= pos[:, None]
                scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)

                def weigh(n, attn):
                    return attn + weighed(
                        "s",
                        lax.dynamic_slice_in_dim(probs, n * half, half, axis=2),
                        rows(v, n), highest)

                return lax.fori_loop(
                    0, 2, weigh, jnp.zeros((slots, H, Dh), jnp.float32))

        def rest_of_layer(layer, x, attn):
            """The layer after its attention ``attn`` [H, Dh]."""
            with jax.named_scope("attn_proj"):
                x = x + (attn.reshape(D).astype(jnp.bfloat16) @ layer["proj"])
            with jax.named_scope("mlp"):
                h2 = norm(x)
                return x + (jax.nn.gelu(h2 @ layer["mlp_in"])
                            @ layer["mlp_out"])

        def unembed(params, x):
            with jax.named_scope("unembed"):
                return (norm(x) @ params["unembed"]).astype(jnp.float32)

        def one_layer(layer, cache, x, pos, *, live):
            """One layer of a single sequence's step: ``(x, cache)`` with
            the row at ``pos`` written."""
            q, k_new, v_new = qkv_rows(layer, x)
            with jax.named_scope("cache_update"):
                k, v = (lax.dynamic_update_slice(held, as_rows(new, held),
                                                 (0, pos, 0))
                        for held, new in ((cache["k"], k_new),
                                          (cache["v"], v_new)))
            x = rest_of_layer(layer, x, attention(q, k, v, pos, live=live))
            return x, {"k": k, "v": v}

        def step(params, caches, token, pos, *, live=M):
            """One decode step. caches: [L] dicts of k/v [H, M, Dh].

            ``live`` is a compile-time length, a rung of the ladder that
            covers ``pos``: the attention reads that prefix of the cache and
            no reserved position beyond it. At ``M`` it is the whole cache.
            The Pallas kernel takes no notice of it.

            The step owns ``caches``: the jitted programs donate them, so
            the returned caches are the same buffers with row ``pos``
            written and the caller's handle on the old ones is dead.

            The named scopes are compile-time metadata: the device
            operations of a trace carry them, so that device time reads by
            part of the step whatever the compiler names its fusions."""
            x = embed(params, token, pos)
            new_caches = []
            for layer, cache in zip(params["layers"], caches):
                x, cache = one_layer(layer, cache, x, pos, live=live)
                new_caches.append(cache)
            return unembed(params, x), new_caches

        def over_slots(part, in_axes):
            """``vmap`` of one of the step's parts, as a jitted call: a
            scope named inside a call keeps its name under ``vmap`` (named
            in the batched function itself it would read ``vmap(mlp)``, and
            a trace is read by the plain names)."""
            return jax.vmap(jax.jit(part), in_axes)

        def slot_turns(q, k, v, pos, turns, *, live):
            """The attention of a round over the table's slots
            ``slots_a_turn`` at a time for ``turns`` turns, the occupied ones
            and no slot's cache beyond, a turn's slots and the prefix of
            their positions one slice of the table."""
            slots = q.shape[0]
            a_turn = slots_a_turn(slots)
            attend = over_slots(functools.partial(attention, live=live), 0)

            def turn(n, attn):
                at = n * a_turn
                those = functools.partial(
                    lax.dynamic_slice_in_dim, start_index=at,
                    slice_size=a_turn)
                prefix = lambda cache: lax.dynamic_slice(
                    cache, (at, 0, 0, 0),
                    (a_turn, cache.shape[1], live, cache.shape[3]))
                return lax.dynamic_update_slice_in_dim(
                    attn, attend(those(q), prefix(k), prefix(v), those(pos)),
                    at, 0)

            with jax.named_scope("attention"):  # the loop's own operations
                return lax.fori_loop(
                    0, turns, turn, jnp.zeros((slots, H, Dh), jnp.float32))

        def read_round(q, k, v, pos, turns, *, live):
            """The attention of a round over a table, the one rule for how a
            table is read: [slots, H, Dh]. Where a round reads every slot
            (``reads_every_slot``) it reads each slot's prefix where it
            lies, ``attention`` over the slots, and at the top rung the
            whole table in ``read_table``'s two turns (the Pallas kernel
            takes every slot's whole cache at its one rung): for sixteen
            gpt2-large members that read 1.56 ms of a round of 36 layers on
            a v5e and the turns 2.34 (laid a head a row, the compiler set
            each turn's slice aside and laid it out anew: 3.0 ms; PERF.md
            section 6). Where a head fills a tile's lanes it reads the
            occupied slots in ``turns`` (``slot_turns``): a round costs the
            caches of its live streams, in width as the rung makes it in
            length. ``slots_read`` counts what it read."""
            if not self.reads_every_slot():
                return slot_turns(q, k, v, pos, turns, live=live)
            if live == M and self._attention_impl == "einsum":
                return read_table(q, k, v, pos)
            return over_slots(functools.partial(attention, live=live), 0)(
                q, k, v, pos)

        def round_layer(layer, cache, x, pos, active, turns, *, live):
            """One layer of a round over a table: ``vmap`` of the step's own
            parts over the slots, round the one row write that takes the
            whole table where it lies and the one read (``read_round``).
            The products with the weights take every slot (a slot more costs
            them nothing: they read the weights)."""
            q, k_new, v_new = over_slots(qkv_rows, (None, 0))(layer, x)
            with jax.named_scope("cache_update"):
                k, v = write_table_rows(
                    (cache["k"], cache["v"]),
                    (as_rows(k_new, cache["k"]), as_rows(v_new, cache["v"])),
                    pos, active)
            attn = read_round(q, k, v, pos, turns, live=live)
            x = over_slots(rest_of_layer, (None, 0, 0))(layer, x, attn)
            return x, {"k": k, "v": v}

        # A jitted call: every layer has the same shapes, so a program traces
        # and lowers the layer once and not once a layer, which was most of
        # what a program costs a warm set-up (1.4 s of Python at 36 layers,
        # 0.15 s so). The chip's compiler inlines the calls. A single
        # sequence's step stays one flat trace: the CPU's compiler rounds a
        # bfloat16 carried across a call that it keeps in float32 within one
        # computation, and decoder_tp.py's step is held bit-equal to it there.
        a_round_layer = jax.jit(round_layer, static_argnames="live")

        def table_step(params, caches, token, pos, active, *, live):
            """One step of every slot of a table. caches: [L] dicts of k/v
            as ``_fresh_table`` lays them. ``token``, ``pos`` int32 [slots];
            ``active`` bool [slots]: whether the slot's sequence has a token
            in the step. An inactive slot (a full one, a freed one) has no
            row written and its logits go unread. Returns the logits,
            float32 [slots, V], and the caches."""
            slots = active.shape[0]
            occupied = jnp.max(jnp.where(active, jnp.arange(slots) + 1, 0))
            turns = -(-occupied // slots_a_turn(slots))
            x = over_slots(embed, (None, 0, 0))(params, token, pos)
            new_caches = []
            for layer, cache in zip(params["layers"], caches):
                x, cache = a_round_layer(
                    layer, cache, x, pos, active, turns, live=live)
                new_caches.append(cache)
            return over_slots(unembed, (None, 0))(params, x), new_caches

        def round_program():
            # traced as ``jit_step``: a round is the step of a model whose
            # streams share it, and a trace is read by that name
            def step(params, caches, fed, ctl, *, live):
                """One round over a table of slots, caches donated. ``fed``
                int32 [slots]: the tokens the round before chose, still on
                the device. ``ctl`` int32 [3, slots], the host's word a
                slot: a token of its own (a prompt's) or -1 for the fed one;
                the position; whether a stream sits there. Returns the
                greedy choice of every slot, int32 [slots], and the
                caches."""
                given, pos, active = ctl[0], ctl[1], ctl[2] > 0
                token = jnp.where(given >= 0, given, fed)
                logits, caches = table_step(params, caches, token, pos,
                                            active, live=live)
                with jax.named_scope("greedy_argmax"):
                    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            caches)

            return jax.jit(step, donate_argnums=1, static_argnames="live")

        def batched_step(params, caches, tokens, pos, active, *, live=M):
            """The slot batcher's round (decoder_batched.py), caches
            donated: ``table_step``, the logits of every slot."""
            return table_step(params, caches, tokens, pos, active, live=live)

        self._params = params
        self.rows_path = ("kernel" if ops._on_tpu() and row_write.takes(
            self._table_shape(1)) else "loop")
        self._step_fn = jax.jit(step, donate_argnums=1,
                                static_argnames="live")
        self._table_fn = jax.jit(batched_step, donate_argnums=1,
                                 static_argnames="live")
        if self._attention_impl == "einsum":
            self._rungs = self.ladder()
            self._round_fn = round_program()

    def _ensure_built(self):
        with self._lock:
            if self._step_fn is None:
                self._build()

    def _step_at(self, caches, token, pos, live: int):
        """The jitted step at one rung. The whole length is the step's own
        default, and all that a subclass's step knows."""
        if live == self.MAX_LEN:
            return self._step_fn(self._params, caches, token, pos)
        return self._step_fn(self._params, caches, token, pos, live=live)

    def _ensure_warm(self) -> None:
        """Every rung's program compiled, by one real step a rung on a
        scratch cache, before the first step of a sequence is dispatched:
        a session that crosses a rung in the middle of serving finds its
        program there. A ladder of one rung has nothing to build ahead: its
        one program is compiled by its first step. Whoever fails here fails
        alone; the next caller tries again. (Not on a thread beside the
        frontend's start: what a second program costs a warm set-up is its
        trace, which holds the interpreter.)"""
        if self._warm:
            return
        with self._warm_lock:
            if self._warm:
                return
            if len(self._rungs) > 1:
                caches = self._fresh_cache()
                for live in self._rungs:
                    _, caches = self._step_at(caches, 0, 0, live)
            self._warm = True

    def reads_every_slot(self) -> bool:
        """Whether a round's attention reads every slot of its table
        (``read_round``): where heads are narrower than the lanes, and
        through the Pallas kernel; where a head fills them it reads the
        occupied slots in turns."""
        return (self._attention_impl == "pallas"
                or self.D_MODEL // self.HEADS < LANES)

    def slots_read(self, slots: int, occupied: int) -> int:
        """The slots whose caches a round's attention reads over a table of
        ``slots`` whose highest occupied slot is ``occupied - 1``
        (``reads_every_slot``): every slot, or the occupied ones in whole
        turns."""
        if self.reads_every_slot():
            return slots
        return in_whole_turns(slots, occupied)

    def count_rows_written(self, count: RungCount, members: int) -> None:
        """A round's key and value rows of ``members`` streams, a pair a
        layer, by how ``write_table_rows`` wrote them (``rows_path``);
        nothing where this decoder's programs never call it."""
        if self.rows_path is not None:
            count.add_written(self.rows_path, 2 * self.LAYERS * members)

    def count_positions(self, count: RungCount, positions, decoding: bool) -> None:
        """What the tokens at ``positions`` (a prompt's, or decode steps')
        read and wrote beside their steps, counted from the positions alone:
        nothing, for this decoder; one whose state is of more kinds than a
        row a position counts here (``RungCount.add_rows``)."""

    def decode_step(self, caches, token: int, pos: int,
                    count: Optional[RungCount] = None):
        """One token of one sequence, at the shortest rung that covers it;
        ``(logits, caches)`` as the jitted step gives them. Every model that
        steps a single sequence through this decoder steps it here, and
        hands in its own count of steps by rung."""
        self._ensure_warm()
        live = self.rung_for(pos + 1)
        (self.steps_by_rung if count is None else count).add(live)
        return self._step_at(caches, token, pos, live)

    def prefill(self, caches, tokens, pos: int,
                count: Optional[RungCount] = None):
        """A prompt's ``tokens`` from ``pos`` on through the cache:
        ``(logits, caches)`` after the last. Here it is the compiled step
        over the prompt, a token a dispatch (the same executables the decode
        loop uses: nothing new compiles per prompt length), each waited for:
        one step of a stream in the device's queue at a time, in prefill as
        in decode, since a prompt enqueued whole holds every other stream's
        next token behind it. The loop is the one ``generate.py`` held until
        PR 32, with nothing added inside it; the prompt is counted once,
        after it. A decoder with a prefill program of its own overrides
        this."""
        count = self.steps_by_rung if count is None else count
        logits = None
        for t in tokens:
            logits, caches = self.decode_step(caches, int(t), pos, count)
            pos += 1
            logits.block_until_ready()
        count.add_prefill(len(tokens), chunks=len(tokens))
        return logits, caches

    def _advance(self, caches, tokens, pos: int):
        """A request's ``tokens`` through the cache on the sequence API:
        the compiled step a token at a time, the same executables for a
        prompt and a continuation (static shapes; the cache carries the
        history), enqueued without waiting. A decoder with a prefill program
        overrides this for prompts."""
        logits = None
        for t in tokens:
            logits, caches = self.decode_step(caches, int(t), pos)
            pos += 1
        return logits, caches

    def _fresh_cache(self):
        import jax.numpy as jnp

        Dh = self.D_MODEL // self.HEADS
        return [
            {
                "k": jnp.zeros((self.HEADS, self.MAX_LEN, Dh), jnp.bfloat16),
                "v": jnp.zeros((self.HEADS, self.MAX_LEN, Dh), jnp.bfloat16),
            }
            for _ in range(self.LAYERS)
        ]

    def _table_shape(self, slots: int) -> Tuple[int, int, int, int]:
        """A layer's k (or v) of ``_fresh_table(slots)``."""
        Dh = self.D_MODEL // self.HEADS
        P = (heads_a_row(self.HEADS, Dh) if self._attention_impl == "einsum"
             else 1)
        return (slots, self.HEADS // P, self.MAX_LEN, P * Dh)

    def _fresh_table(self, slots: int):
        """``slots`` caches, stacked: [slots, heads / P, max_len, P x
        head_dim] a layer, zeros, a position's row of a slot ``P =
        heads_a_row(heads, head_dim)`` heads side by side, so that it fills
        the chip's lanes (``write_table_rows``). The Pallas kernel takes a
        head a row."""
        import jax.numpy as jnp

        shape = self._table_shape(slots)
        return [{"k": jnp.zeros(shape, jnp.bfloat16),
                 "v": jnp.zeros(shape, jnp.bfloat16)}
                for _ in range(self.LAYERS)]

    # -- serving -------------------------------------------------------------
    def execute(self, inputs: Dict[str, np.ndarray], parameters: Dict[str, Any]):
        self._ensure_built()
        seq_id = parameters.get("sequence_id", 0)
        start = parameters.get("sequence_start", False)
        end = parameters.get("sequence_end", False)
        if not seq_id:
            raise ValueError("decoder_lm requires a sequence_id")

        tokens = np.asarray(inputs["TOKENS"]).reshape(-1).astype(np.int64)
        if np.any(tokens < 0) or np.any(tokens >= self.VOCAB):
            raise ValueError(f"tokens out of range [0, {self.VOCAB})")

        with self._lock:
            seq_lock = self._seq_locks.setdefault(seq_id, threading.Lock())

        # the whole read-compute-write is serialized PER SEQUENCE (other
        # sequences decode concurrently); without this, two requests on one
        # sequence_id both read pos=P and the later writer silently drops
        # the earlier token's KV update
        with seq_lock:
            with self._lock:
                if start:
                    state = {"caches": self._fresh_cache(), "pos": 0}
                else:
                    state = self._sequences.get(seq_id)
                    if state is None:
                        raise ValueError(
                            f"sequence {seq_id} has no live state "
                            "(missing sequence_start?)")
                    if len(tokens) != 1:
                        raise ValueError(
                            "continuation requests carry exactly one token")
                if state["pos"] + len(tokens) > self.MAX_LEN:
                    raise ValueError(
                        f"sequence longer than max_len {self.MAX_LEN}")

            caches, pos = state["caches"], state["pos"]
            try:
                logits, caches = self._advance(caches, tokens, pos)
                pos += len(tokens)
            except Exception:
                # the step owned the caches it was given: after a failure
                # the sequence has no state, and says so to its next request
                with self._lock:
                    self._sequences.pop(seq_id, None)
                    self._seq_locks.pop(seq_id, None)
                raise

            with self._lock:
                if end:
                    self._sequences.pop(seq_id, None)
                    self._seq_locks.pop(seq_id, None)
                else:
                    self._sequences[seq_id] = {"caches": caches, "pos": pos}

        logits_np = np.asarray(logits, dtype=np.float32).reshape(1, self.VOCAB)
        return {
            "LOGITS": logits_np,
            "NEXT_TOKEN": np.array([[int(logits_np.argmax())]], dtype=np.int32),
        }

    def live_sequences(self) -> int:
        with self._lock:
            return len(self._sequences)

"""The window-and-summaries family's arithmetic: sizes, bytes and FLOPs from
shapes alone.

The yardstick's own count for a decoder whose attention reads an exact
window of ``window_size`` positions beside one summary row a
``chunk_size``-position chunk of every earlier window, with a dense gated
MLP and ``num_pred_heads`` output heads
(``client_tpu/models/window_summary_decoder.py`` serves it; nothing here
reads that program, and no jax: the users' process loads this module). A
configuration of the family carries those keys beside the usual ones and
``reserved_positions``, the positions a sequence may reach here. Weights and
state are bfloat16, logits float32.

``benchmark/family.py`` has the contract: ``vocab``, ``max_len``, ``work``,
``step_least``, ``total_params``, ``init_scale``, ``fixture``; ``step_parts``
is what the family's own reader (``layer_metrics/eva_attention_roofline.py``)
takes.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

WEIGHT_BYTES = 2  # bfloat16
STATE_BYTES = 2
LOGIT_BYTES = 4  # float32


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "mlp_width": int(config["intermediate_size"]),
        "window": int(config["window_size"]), "chunk": int(config["chunk_size"]),
        "pred_heads": int(config.get("num_pred_heads", 1)),
        "max_len": int(config.get("reserved_positions",
                                  config["max_position_embeddings"])),
    }


def vocab(config: Dict[str, Any]) -> int:
    return sizes(config)["vocab"]


def max_len(config: Dict[str, Any]) -> int:
    """The positions a sequence may reach: what a slot reserves
    (``reserved_positions``; the published context where the file has none)."""
    return sizes(config)["max_len"]


def matrix_params(config: Dict[str, Any]) -> int:
    """A layer's seven matrices: q, k, v, the output, and the gated MLP's
    three."""
    s = sizes(config)
    return 4 * s["d"] * s["d"] + 3 * s["d"] * s["mlp_width"]


def layer_params(config: Dict[str, Any]) -> int:
    """A layer: its matrices, its two gains, and a head's two chunk vectors
    (``mu``, ``phi``: ``heads * head_dim = d`` each)."""
    return matrix_params(config) + 4 * sizes(config)["d"]


def head_params(config: Dict[str, Any]) -> int:
    """The output matrix: every prediction head's."""
    s = sizes(config)
    return s["d"] * s["pred_heads"] * s["vocab"]


def total_params(config: Dict[str, Any]) -> int:
    """As the served code lays them out, of what is held here: every layer
    kept, the byte table, the final norm's gain and the heads' matrix."""
    s = sizes(config)
    return (s["layers"] * layer_params(config) + s["vocab"] * s["d"] + s["d"]
            + head_params(config))


def row_bytes(config: Dict[str, Any]) -> int:
    """One row of state over all layers: a key and a value, of the ring or of
    the summary table alike."""
    s = sizes(config)
    return s["layers"] * 2 * s["d"] * STATE_BYTES


def slot_bytes(config: Dict[str, Any]) -> int:
    """What a slot reserves: the ring and one summary row a chunk."""
    s = sizes(config)
    return row_bytes(config) * (s["window"] + s["max_len"] // s["chunk"])


def rows_attended(config: Dict[str, Any], position: int):
    """``(window rows, summary rows)`` the token at 0-based ``position``
    attends to: its own window up to itself, and every chunk of every
    earlier window."""
    s = sizes(config)
    return (position % s["window"] + 1,
            (s["window"] // s["chunk"]) * (position // s["window"]))


def token_flops(config: Dict[str, Any], position: int) -> int:
    """FLOPs the model needs for the token at ``position``, the heads apart:
    two for each matrix parameter, and a layer's scores and weighted sum over
    the rows it attends to."""
    s = sizes(config)
    rows = sum(rows_attended(config, position))
    return s["layers"] * (2 * matrix_params(config) + 4 * s["d"] * rows)


def work(config: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """Totals over the 0-based positions of the tokens processed in the
    window: beside ``tokens_processed``, ``flops`` and ``reach``, the window
    rows and the summary rows those positions attend to, counted exactly. The
    heads' FLOPs are counted for no token (a prompt's tokens need none), so
    ``flops`` is a little under what was needed, never over."""
    tokens = flops = reach = window_rows = summary_rows = 0
    for p in positions:
        tokens += 1
        flops += token_flops(config, p)
        reach += p + 1
        exact, before = rows_attended(config, p)
        window_rows += exact
        summary_rows += before
    return {"tokens_processed": tokens, "flops": flops, "reach": reach,
            "window_rows": window_rows, "summary_rows": summary_rows}


def step_parts(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes of a mean round of ``width`` members, by part: ``weights``
    (every layer's matrices, gains and chunk vectors, once a round whatever
    its width), ``attention`` (a member the mean ring rows and summary rows
    the window's positions attend to: prompt positions among them, which lie
    before a decode step's own, so the summaries are counted a little low,
    never high), ``head`` (the heads' matrix once, a member its logits row)
    and ``rows`` (a member its byte's table row and the key and value row it
    writes a layer)."""
    s = sizes(config)
    tokens = max(work["tokens_processed"], 1)
    attended = (work["window_rows"] + work["summary_rows"]) / tokens
    return {
        "weights": WEIGHT_BYTES * s["layers"] * layer_params(config),
        "attention": width * row_bytes(config) * attended,
        "head": (WEIGHT_BYTES * (head_params(config) + s["d"])
                 + width * LOGIT_BYTES * s["pred_heads"] * s["vocab"]),
        "rows": width * (WEIGHT_BYTES * s["d"] + row_bytes(config)),
    }


def step_least(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes and FLOPs of a mean round of ``width`` members: every
    matrix once a round, a member its mean rows of state, one table row and
    its logits row; the same count whatever implements the round."""
    tokens = max(work["tokens_processed"], 1)
    flops = work["flops"] / tokens + 2 * head_params(config)
    return {"bytes": sum(step_parts(config, work, width).values()),
            "flops": width * flops}


def init_scale(path, leaf):
    """The family's one rule (the program's seeded constructor takes it too):
    gains 0 +- 0.1 about the unit offset (so that an offset left out is
    seen), the heads' matrix 0.02, every other matrix by its first axis, the
    chunk vectors 1 (``s k . mu`` is then of order 1 and a chunk's softmax is
    far from flat), and the table 1: a residual stream of the size of a
    layer's output, as a trained model's is (PERF.md section 6, PR 32)."""
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm"):
        return (0.0, 0.1)
    if name in ("mu", "phi", "embed"):
        return 1.0
    if name == "unembed":
        return 0.02
    return leaf.shape[0] ** -0.5


def fixture(config: Dict[str, Any]):
    """The family at fixture size and the limit its cells are held to on the
    CPU: 2 layers, 4 heads of 16, a window of 8 positions, chunks of 2 and
    prefill chunks of 4, so that the fixture's sessions (prompts and outputs
    of 2 to 12) cross a window, finish chunks and end some mid-chunk, and 64
    positions give three rungs (0, 16, 32). Served in bfloat16, as the
    configuration is: there is no discrete choice for rounding to flip, and
    at this width the logits are small (deviation 0.16). Over 16 seeds on the
    CPU the served gap read at most 0.0006 (0 on ten of them) and the fp8
    control's 0.0074 to 0.062."""
    tiny = dict(
        config, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, intermediate_size=128, window_size=8, chunk_size=2,
        prefill_chunk=4, reserved_positions=64, max_position_embeddings=64,
        max_seq_length=64, reduced=[], source="fixture for the CPU tests")
    for key in ("published", "deployment"):
        tiny.pop(key, None)
    return tiny, {"served_gap_max": 0.003}

"""The window-and-full, routed family's arithmetic: sizes, bytes and FLOPs
from shapes alone.

The yardstick's own count for a decoder whose attention layers either keep a
sliding window (``sliding_window_layout`` 1: rotary, a ring of
``sliding_window_size`` rows) or attend to the whole prefix (0: no rotary),
every layer's tokens routed by a softmax router over ``h1`` to ReLU-gated
experts (``client_tpu/models/window_routed_decoder.py`` serves it; nothing
here reads that program, and no jax: the users' process loads this module).
A configuration of the family carries ``reserved_positions``, the positions
a sequence may reach here. Weights and cache rows are bfloat16, logits
float32.

``benchmark/family.py`` has the contract: ``vocab``, ``max_len``, ``work``,
``step_least``, ``total_params``, ``init_scale``, ``fixture``;
``step_parts``, ``routed_layers``, ``experts_bytes`` and ``row_bytes`` are
what the family's own readers take (``layer_metrics/round_experts_roofline.py``,
``experts_reached_mean.py``, ``sliding_attention_roofline.py``,
``full_attention_roofline.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

WEIGHT_BYTES = 2  # bfloat16
ROW_BYTES = 2  # a key or value lane
LOGIT_BYTES = 4  # float32


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    sliding = [bool(f) for f in config["sliding_window_layout"]]
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": len(sliding), "windows": sum(sliding),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert_width": int(config["moe_ffn_hidden_size"]),
        "experts": int(config["moe_num_primary_experts"]),
        "experts_per_token": int(config["moe_num_active_primary_experts"]),
        "window": int(config["sliding_window_size"]),
        "max_len": int(config.get("reserved_positions",
                                  config["max_position_embeddings"])),
    }


def vocab(config: Dict[str, Any]) -> int:
    return sizes(config)["vocab"]


def max_len(config: Dict[str, Any]) -> int:
    """The positions a sequence may reach: what a slot reserves
    (``reserved_positions``; the published context where the file has none)."""
    return sizes(config)["max_len"]


def attention_params(config: Dict[str, Any]) -> int:
    """A layer's four matrices: q, k, v and the output."""
    s = sizes(config)
    return s["d"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])


def router_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["d"] * s["experts"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    s = sizes(config)
    return 3 * s["d"] * s["expert_width"]


def routed_layers(config: Dict[str, Any]) -> int:
    """Every layer is routed."""
    return sizes(config)["layers"]


def layer_params(config: Dict[str, Any], experts: int) -> int:
    """A layer with ``experts`` of its experts: attention, router, experts
    and two gains."""
    s = sizes(config)
    return (attention_params(config) + router_params(config)
            + experts * expert_params(config) + 2 * s["d"])


def head_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["d"] * s["vocab"]


def total_params(config: Dict[str, Any]) -> int:
    """As the served code lays them out, of what is held here: every layer
    kept with all its experts, the token table, the final norm's gain and an
    untied head."""
    s = sizes(config)
    return (s["layers"] * layer_params(config, s["experts"])
            + 2 * head_params(config) + s["d"])


def row_bytes(config: Dict[str, Any]) -> int:
    """One position of one layer: a key and a value row."""
    s = sizes(config)
    return 2 * s["kv_heads"] * s["head_dim"] * ROW_BYTES


def slot_bytes(config: Dict[str, Any]) -> int:
    """A slot of the table: a full layer's rows at every reserved position,
    a window layer's ring of ``window`` rows."""
    s = sizes(config)
    return row_bytes(config) * ((s["layers"] - s["windows"]) * s["max_len"]
                                + s["windows"] * s["window"])


def rows_attended(config: Dict[str, Any], position: int):
    """The rows the token at 0-based ``position`` attends to in a window
    layer and in a full layer."""
    return min(position + 1, sizes(config)["window"]), position + 1


def token_flops(config: Dict[str, Any], position: int) -> int:
    """FLOPs the model needs for the token at 0-based ``position``, the head
    apart: two for each matrix parameter it is multiplied by (the router, and
    the experts it reaches, not those held), and each layer's scores and
    weighted sum over the rows it attends to."""
    s = sizes(config)
    ring, prefix = rows_attended(config, position)
    attended = s["windows"] * ring + (s["layers"] - s["windows"]) * prefix
    return (s["layers"] * 2 * (attention_params(config) + router_params(config)
                               + s["experts_per_token"] * expert_params(config))
            + 4 * s["heads"] * s["head_dim"] * attended)


def work(config: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """Totals over the 0-based positions of the tokens processed in the
    window: beside ``tokens_processed``, ``flops`` and ``reach``, the rows a
    window layer and a full layer attend to (``ring_rows``,
    ``prefix_rows``), counted exactly. The head's FLOPs are counted for no
    token (a prompt's tokens need none), so ``flops`` is a little under what
    was needed, never over."""
    tokens = flops = ring_rows = prefix_rows = 0
    for p in positions:
        tokens += 1
        flops += token_flops(config, p)
        ring, prefix = rows_attended(config, p)
        ring_rows += ring
        prefix_rows += prefix
    return {"tokens_processed": tokens, "flops": flops, "reach": prefix_rows,
            "ring_rows": ring_rows, "prefix_rows": prefix_rows}


def experts_bytes(config: Dict[str, Any], reached: float) -> float:
    """Least bytes of the routed layers' part of a dispatch whose grouped
    products read ``reached`` experts, summed over the layers: every router,
    and each reached expert once."""
    return WEIGHT_BYTES * (routed_layers(config) * router_params(config)
                           + reached * expert_params(config))


def attention_bytes(config: Dict[str, Any], rows: float) -> float:
    """Least bytes of ``rows`` key and value rows read, one layer's position
    each."""
    return rows * row_bytes(config)


def step_parts(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes of a mean round of ``width`` members, by part: ``weights``
    (every matrix and gain but the experts', the routers', the table's and
    the head's, once a round whatever its width), ``experts`` (the routers,
    and a routed layer ``experts_per_token`` experts: one token's, which a
    round reads whatever its routing; how many more it reads the routing
    decides and the positions cannot say, so this part, and ``step_least``,
    read low by construction), ``attention`` (a member the ring rows and
    prefix rows of the window's mean position: prompt positions among them,
    which lie before a decode step's own, so the rows are counted low, never
    high), ``head`` (the head's matrix once, a member its logits row) and
    ``rows`` (a member its table row and the key and value row it writes a
    layer)."""
    s = sizes(config)
    tokens = max(work["tokens_processed"], 1)
    attended = (s["windows"] * work["ring_rows"]
                + (s["layers"] - s["windows"]) * work["prefix_rows"]) / tokens
    return {
        "weights": WEIGHT_BYTES * (s["layers"] * (attention_params(config) + 2 * s["d"])
                                   + s["d"]),
        "experts": experts_bytes(config, routed_layers(config) * s["experts_per_token"]),
        "attention": width * attention_bytes(config, attended),
        "head": (WEIGHT_BYTES * head_params(config)
                 + width * LOGIT_BYTES * s["vocab"]),
        "rows": width * (WEIGHT_BYTES * s["d"] + s["layers"] * row_bytes(config)),
    }


def step_least(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes and FLOPs of a mean round of ``width`` members: the same
    count whatever implements the round, and whatever its routing."""
    tokens = max(work["tokens_processed"], 1)
    flops = work["flops"] / tokens + 2 * head_params(config)
    return {"bytes": sum(step_parts(config, work, width).values()),
            "flops": width * flops}


# the residual layers of the published model: 52 layers, each writing to the
# residual stream twice (its attention's output, its experts' down projection)
RESIDUAL_LAYERS = 104
RESIDUAL_OUTPUTS = ("wo", "experts_down")


def init_scale(path, leaf):
    """The family's one rule (the program's seeded constructor takes it too):
    gains 1 +- 0.1 (so that a norm left out is seen), the head 0.02, the
    table 1.0, stacked experts ``[experts, fan_in, fan_out]`` by their own
    fan-in and every other matrix by its first axis; the two projections that
    write to the residual stream (``RESIDUAL_OUTPUTS``) besides by ``(2 * 52)
    ** -0.5``, as GPT-2 scales its residual layers by one over the root of
    their number (Radford et al. 2019, section 2.3), the published model's
    104, and as ``gated_conv_arithmetic.init_scale`` does for its family. So
    a layer adds to the stream a part that is small beside it, as in a
    trained model of that depth, and an expert chosen otherwise at a near tie
    (the sixth of six, the least weighted) moves the stream, and every later
    router's choice, by a part of that part: without the factor a layer adds
    as much as the table gives, and the gated-convolution family's
    bfloat16-against-float32 reading overlapped its float8 control's (TPU
    v5e runs, PERF.md section 6)."""
    name = path[-1]
    if name in ("ln1", "ln2", "final_norm"):
        return (1.0, 0.1)
    if name == "embed":
        return 1.0
    if name == "unembed":
        return 0.02
    out = RESIDUAL_LAYERS ** -0.5 if name in RESIDUAL_OUTPUTS else 1.0
    if name.startswith("experts_"):
        return out * leaf.shape[1] ** -0.5
    return out * leaf.shape[0] ** -0.5


def fixture(config: Dict[str, Any]):
    """The family at fixture size and the limits its cells are held to on the
    CPU: two periods of a full layer and three window layers, a window of 16
    positions, prompts taken 8 positions a dispatch, 8 experts of which a
    token takes 2, so that the fixture's sessions (4 to 24 positions) reach
    past the window and wrap its ring. It is served in float32 (``dtype``):
    with 2 experts of 8 a tie that bfloat16 decides otherwise than float32
    moves a logit by tenths, so at this size it is the mathematics that is
    held, to float32's rounding."""
    tiny = dict(
        config, hidden_size=64, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2,
        sliding_window_size=16, vocab_size=300, reserved_positions=64,
        max_position_embeddings=64, prefill_chunk=8, dtype="float32",
        reduced=[], source="fixture for the CPU tests")
    for key in ("published", "deployment"):
        tiny.pop(key, None)
    return tiny, {"served_gap_max": 1e-4, "near_tie_share": 0.3}

"""The gated-convolution family's arithmetic: sizes, bytes and FLOPs from
shapes alone.

The yardstick's own count for a decoder whose layers are gated short
convolutions or grouped-query attention (``layer_types``), with a dense
SwiGLU in the first ``num_dense_layers`` and sigmoid-routed SwiGLU experts in
every later one (``client_tpu/models/gated_conv_decoder.py`` serves it;
nothing here reads that program, and no jax: the users' process loads this
module). A configuration of the family carries ``reserved_positions``, the
positions a sequence may reach here. Weights, cache rows and conv state are
bfloat16, logits float32.

``benchmark/family.py`` has the contract: ``vocab``, ``max_len``, ``work``,
``step_least``, ``total_params``, ``init_scale``, ``fixture``;
``step_parts``, ``routed_layers`` and ``experts_bytes`` are what the family's
own readers (``layer_metrics/round_experts_roofline.py``,
``experts_reached_mean.py``) take.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

WEIGHT_BYTES = 2  # bfloat16
STATE_BYTES = 2
LOGIT_BYTES = 4  # float32
CONV_TAPS = 3  # conv_L_cache: the state holds the two before a token


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    d, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    kinds = list(config["layer_types"])
    return {
        "vocab": int(config["vocab_size"]), "d": d, "layers": len(kinds),
        "kinds": kinds, "dense": int(config.get("num_dense_layers", 0)),
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // heads, "mlp_width": int(config["intermediate_size"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "max_len": int(config.get("reserved_positions",
                                  config["max_position_embeddings"])),
    }


def vocab(config: Dict[str, Any]) -> int:
    return sizes(config)["vocab"]


def max_len(config: Dict[str, Any]) -> int:
    """The positions a sequence may reach: what a slot reserves
    (``reserved_positions``; the published context where the file has none)."""
    return sizes(config)["max_len"]


def conv_params(config: Dict[str, Any]) -> int:
    """A conv layer's matrices and taps: ``[B, C, u]``'s projection, the
    output's, and three taps a channel."""
    d = sizes(config)["d"]
    return 3 * d * d + d * d + CONV_TAPS * d


def attention_params(config: Dict[str, Any]) -> int:
    """An attention layer's four matrices: q, k, v and the output."""
    s = sizes(config)
    return s["d"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])


def dense_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return 3 * s["d"] * s["mlp_width"]


def router_params(config: Dict[str, Any]) -> int:
    """The router's matrix and the experts' bias."""
    s = sizes(config)
    return s["d"] * s["experts"] + s["experts"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    s = sizes(config)
    return 3 * s["d"] * s["expert_width"]


def routed_layers(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["layers"] - s["dense"]


def mixer_params(config: Dict[str, Any], i: int) -> int:
    """Layer ``i``'s conv or attention part, with its norm's gain (and an
    attention layer's two gains over a head)."""
    s = sizes(config)
    if s["kinds"][i] == "conv":
        return conv_params(config) + s["d"]
    return attention_params(config) + s["d"] + 2 * s["head_dim"]


def ffn_params(config: Dict[str, Any], i: int, experts: int) -> int:
    """Layer ``i``'s feed-forward part with ``experts`` of its experts, and
    its norm's gain."""
    s = sizes(config)
    if i < s["dense"]:
        return dense_params(config) + s["d"]
    return router_params(config) + experts * expert_params(config) + s["d"]


def head_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["d"] * s["vocab"]


def total_params(config: Dict[str, Any]) -> int:
    """As the served code lays them out, of what is held here: every layer
    kept with all its experts, the token table, the final norm's gain and an
    untied head."""
    s = sizes(config)
    return (sum(mixer_params(config, i) + ffn_params(config, i, s["experts"])
                for i in range(s["layers"]))
            + 2 * head_params(config) + s["d"])


def cache_row_bytes(config: Dict[str, Any]) -> int:
    """One position over the attention layers: a key and a value row."""
    s = sizes(config)
    return (s["kinds"].count("full_attention") * 2 * s["kv_heads"] * s["head_dim"]
            * STATE_BYTES)


def conv_state_bytes(config: Dict[str, Any]) -> int:
    """A slot's conv state over the conv layers: two rows of ``d``."""
    s = sizes(config)
    return s["kinds"].count("conv") * (CONV_TAPS - 1) * s["d"] * STATE_BYTES


def token_flops(config: Dict[str, Any], position: int) -> int:
    """FLOPs the model needs for the token at 0-based ``position``, the head
    apart: two for each matrix parameter it is multiplied by (the experts it
    reaches, not those held), an attention layer's scores and weighted sum
    over the ``position + 1`` positions, and a conv layer's taps and gate."""
    s = sizes(config)
    flops = 0
    for i, kind in enumerate(s["kinds"]):
        if kind == "conv":
            flops += 2 * (conv_params(config) - CONV_TAPS * s["d"]) + (
                2 * CONV_TAPS + 2) * s["d"]
        else:
            flops += (2 * attention_params(config)
                      + 4 * s["heads"] * s["head_dim"] * (position + 1))
        flops += 2 * (dense_params(config) if i < s["dense"] else (
            s["d"] * s["experts"] + s["experts_per_token"] * expert_params(config)))
    return flops


def work(config: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """Totals over the 0-based positions of the tokens processed in the
    window. The head's FLOPs are counted for no token (a prompt's tokens need
    none), so ``flops`` is a little under what was needed, never over."""
    tokens = flops = reach = 0
    for p in positions:
        tokens += 1
        flops += token_flops(config, p)
        reach += p + 1
    return {"tokens_processed": tokens, "flops": flops, "reach": reach}


def experts_bytes(config: Dict[str, Any], reached: float) -> float:
    """Least bytes of the routed layers' part of a dispatch whose grouped
    products read ``reached`` experts, summed over the layers: every router
    and bias, and each reached expert once."""
    return WEIGHT_BYTES * (routed_layers(config) * router_params(config)
                           + reached * expert_params(config))


def step_parts(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes of a mean round of ``width`` members, by part:
    ``weights`` (every matrix, gain and tap but the experts', once a round
    whatever its width), ``experts`` (the routers, and a routed layer
    ``experts_per_token`` experts: one token's, which a round reads whatever
    its routing; how many more it reads the routing decides and the positions
    cannot say, so this part, and ``step_least``, read low by construction),
    ``attention`` (a member the key and value rows over its mean reach),
    ``state`` (a member its conv state read and written), ``head`` (the head's
    matrix once, a member its logits row) and ``rows`` (a member its table row
    and the key and value row it writes)."""
    s = sizes(config)
    tokens = max(work["tokens_processed"], 1)
    reach = work["reach"] / tokens
    kept = (sum(mixer_params(config, i) for i in range(s["layers"]))
            + sum(ffn_params(config, i, 0) for i in range(s["dense"]))
            + routed_layers(config) * s["d"] + s["d"])
    return {
        "weights": WEIGHT_BYTES * kept,
        "experts": experts_bytes(config, routed_layers(config) * s["experts_per_token"]),
        "attention": width * cache_row_bytes(config) * reach,
        "state": width * 2 * conv_state_bytes(config),
        "head": (WEIGHT_BYTES * head_params(config)
                 + width * LOGIT_BYTES * s["vocab"]),
        "rows": width * (WEIGHT_BYTES * s["d"] + cache_row_bytes(config)),
    }


def step_least(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes and FLOPs of a mean round of ``width`` members: the same
    count whatever implements the round, and whatever its routing."""
    tokens = max(work["tokens_processed"], 1)
    flops = work["flops"] / tokens + 2 * head_params(config)
    return {"bytes": sum(step_parts(config, work, width).values()),
            "flops": width * flops}


# the residual layers of the published model: 40 layers, each writing to the
# residual stream twice (its conv or attention part, its feed-forward part)
RESIDUAL_LAYERS = 80
RESIDUAL_OUTPUTS = ("conv_out", "wo", "mlp_down", "experts_down")


def init_scale(path, leaf):
    """The family's one rule (the program's seeded constructor takes it too):
    gains 1 +- 0.1 (so that a norm left out is seen), the head 0.02, the
    experts' bias 0 +- 0.05 (a quarter of the deviation of a sigmoid score
    here, so that it moves choices and a program that weighs by it, or leaves
    it out, is seen), the convolution's three taps by their count, stacked
    experts ``[experts, fan_in, fan_out]`` by their own fan-in, every other
    matrix by its first axis, and the table 1.0; the projections that write
    to the residual stream (``RESIDUAL_OUTPUTS``) besides by ``(2 * 40) **
    -0.5``, as GPT-2 scales its residual layers by one over the root of
    their number (Radford et al. 2019, section 2.3), the published model's
    80. So a layer adds to the stream a part that is small beside the
    stream, as in a trained model of that depth. Without that factor a
    layer adds as much as the table gives, and an expert chosen otherwise at
    a near tie (sigmoid scores, four of 64, each weighted a quarter) moves
    the stream so far that bfloat16 against float32 read 0.59–1.24 in
    ``served_gap_max`` and the float8 control 1.67–1.94 (TPU v5e runs,
    PERF.md section 6); with it, 0.0017–0.0046 against 0.158–0.180."""
    name = path[-1]
    if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
        return (1.0, 0.1)
    if name == "expert_bias":
        return (0.0, 0.05)
    if name == "embed":
        return 1.0
    if name == "unembed":
        return 0.02
    if name == "conv_w":
        return leaf.shape[0] ** -0.5
    out = RESIDUAL_LAYERS ** -0.5 if name in RESIDUAL_OUTPUTS else 1.0
    if name.startswith("experts_"):
        return out * leaf.shape[1] ** -0.5
    return out * leaf.shape[0] ** -0.5


def fixture(config: Dict[str, Any]):
    """The family at fixture size and the limits its cells are held to on the
    CPU: a dense conv layer, then a routed attention layer, a routed conv
    layer and a routed attention layer, 8 experts of which a token takes 2,
    prompts taken 4 positions a dispatch, so that the fixture's prompts (2 to
    12 tokens) end 0 to 3 past a chunk's end and the conv state crosses
    chunks and joins the rounds. It is served in float32 (``dtype``): with 2
    experts of 8 a tie that bfloat16 decides otherwise than float32 moves a
    logit by tenths, so at this size it is the mathematics that is held, to
    float32's rounding (3e-7 in a logit: a served gap is 0 but at an exact
    tie). Over 16 seeds on the CPU the served gap read 0.0 (the same choice at
    every position); the fp8 control's 0.0024 to 0.026 on 15 of them and 0.0
    on one, whose float8 pass chose the float32 pass's token at every
    position (at this size the residual outputs' scale leaves the float8
    rounding little to move); at most 0.089 of the served positions were set
    aside as near ties of the router (a distance of 0.002)."""
    tiny = dict(
        config, hidden_size=64, num_hidden_layers=4,
        layer_types=["conv", "full_attention", "conv", "full_attention"],
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, vocab_size=300, reserved_positions=64,
        max_position_embeddings=64, prefill_chunk=4, dtype="float32",
        reduced=[], source="fixture for the CPU tests")
    for key in ("published", "deployment"):
        tiny.pop(key, None)
    return tiny, {"served_gap_max": 1e-4, "near_tie_share": 0.3}

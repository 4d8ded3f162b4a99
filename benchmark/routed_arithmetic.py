"""The routed family's arithmetic: sizes, bytes and FLOPs from shapes alone.

The yardstick's own count for a decoder of routed experts and grouped-query
attention over the positions a learned indexer picks
(``client_tpu/models/routed_decoder.py`` serves it; nothing here reads that
program, and no jax: the users' process loads this module). A configuration
of the family carries the keys of a Qwen3-MoE style ``config.json`` with an
``sa_config``, and ``reserved_positions``, the positions a sequence may reach
here (the published ``max_position_embeddings`` is the model's, and more than
a chip reserves). Weights and caches are bfloat16, logits float32.

``benchmark/family.py`` has the contract: ``vocab``, ``max_len``, ``work``,
``step_least``, ``total_params``, ``init_scale``, ``fixture``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

WEIGHT_BYTES = 2  # bfloat16
CACHE_BYTES = 2
LOGIT_BYTES = 4  # float32


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    sa = config["sa_config"]
    return {
        "vocab": int(config["vocab_size"]), "d": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]), "topk": int(sa["topk"]),
        "chunk": int(sa["q_chunk_size"]),
        "max_len": int(config.get("reserved_positions",
                                  config["max_position_embeddings"])),
    }


def vocab(config: Dict[str, Any]) -> int:
    return sizes(config)["vocab"]


def max_len(config: Dict[str, Any]) -> int:
    """The positions a sequence may reach: what the cache reserves
    (``reserved_positions``; the published context where the file has none)."""
    return sizes(config)["max_len"]


def attention_params(config: Dict[str, Any]) -> int:
    """A layer's four attention matrices: q, k, v and the output."""
    s = sizes(config)
    return s["d"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])


def indexer_params(config: Dict[str, Any]) -> int:
    """A layer's indexer: its queries, its one key, its heads' weights."""
    s = sizes(config)
    return s["d"] * (s["index_heads"] * s["index_dim"] + s["index_dim"]
                     + s["index_heads"])


def router_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["d"] * s["experts"]


def expert_params(config: Dict[str, Any]) -> int:
    """One expert: gate, up and down."""
    s = sizes(config)
    return 3 * s["d"] * s["expert_width"]


def norm_params(config: Dict[str, Any]) -> int:
    """A layer's gains: two RMS norms of the residual, one of each query and
    key head, and the indexer key's layer norm (gain and bias)."""
    s = sizes(config)
    return 2 * s["d"] + 2 * s["head_dim"] + 2 * s["index_dim"]


def layer_params(config: Dict[str, Any], experts: int) -> int:
    """A layer with ``experts`` of its experts."""
    return (attention_params(config) + indexer_params(config)
            + router_params(config) + experts * expert_params(config)
            + norm_params(config))


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


def cache_row_bytes(config: Dict[str, Any]) -> int:
    """One position over all layers: a key, a value, the indexer's key."""
    s = sizes(config)
    return s["layers"] * CACHE_BYTES * (2 * s["kv_heads"] * s["head_dim"]
                                        + s["index_dim"])


def token_flops(config: Dict[str, Any], position: int) -> int:
    """FLOPs the model needs for the token at 0-based ``position``, the head
    apart: two for each parameter it is multiplied by (the experts it
    reaches, not those held), and a layer's index scores over the
    ``position + 1`` positions it may attend to and attention over the
    ``min(position + 1, topk)`` it does attend to."""
    s = sizes(config)
    reach = position + 1
    matmul = 2 * s["layers"] * (
        attention_params(config) + indexer_params(config) + router_params(config)
        + s["experts_per_token"] * expert_params(config))
    scored = 2 * s["index_heads"] * s["index_dim"] * reach if reach > s["topk"] else 0
    attended = 4 * s["heads"] * s["head_dim"] * min(reach, s["topk"])
    return matmul + s["layers"] * (scored + attended)


def work(config: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """Totals over the 0-based positions of the tokens processed in the
    window. The head's FLOPs are counted for no token: a prompt's tokens need
    none, the positions do not say which token was an output's, and output
    tokens are a hundredth of those processed where prompts are long; so
    ``flops`` is a little under what was needed, never over."""
    tokens = flops = reach = 0
    for p in positions:
        tokens += 1
        flops += token_flops(config, p)
        reach += p + 1
    return {"tokens_processed": tokens, "flops": flops, "reach": reach}


def step_parts(config: Dict[str, Any], live: float) -> Dict[str, float]:
    """Least bytes of one sequence's decode step with ``live`` positions in
    its cache, by part, as the named scopes of the step divide it:
    ``experts`` (``moe_route`` + ``moe_experts``: the router and the experts
    the token reaches, not those held), ``sparse_attention`` (``indexer`` +
    ``select`` + ``sparse_attention``: the indexer's weights, past ``topk``
    its ``live`` keys, and the ``min(live, topk)`` key and value rows that
    are attended to), ``attention_weights`` (q, k, v, output), ``head`` (the
    head's matrix and the logits row) and ``rows`` (the token's table row and
    its three new cache rows)."""
    s = sizes(config)
    layers = s["layers"]
    kv_row = 2 * s["kv_heads"] * s["head_dim"] * CACHE_BYTES
    index_row = s["index_dim"] * CACHE_BYTES
    return {
        "experts": layers * WEIGHT_BYTES * (
            router_params(config) + s["experts_per_token"] * expert_params(config)),
        "sparse_attention": layers * (
            WEIGHT_BYTES * indexer_params(config)
            + (index_row * live if live > s["topk"] else 0)
            + kv_row * min(live, s["topk"])),
        "attention_weights": layers * WEIGHT_BYTES * attention_params(config),
        "head": WEIGHT_BYTES * head_params(config) + LOGIT_BYTES * s["vocab"],
        "rows": WEIGHT_BYTES * s["d"] + layers * (kv_row + index_row),
    }


def step_least(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes and FLOPs of a mean step of ``width`` sequences, each on
    its own (this family has no batched step: nothing is shared between the
    sequences of a step, so the count is ``width`` times one sequence's).
    ``live`` is the mean reach of the tokens processed in the window, prompt
    tokens among them, which is under a decode step's own (a step comes after
    its prompt): the indexer's keys are counted a little low, never high. The
    head is counted once a step, in bytes and in FLOPs."""
    tokens = max(work["tokens_processed"], 1)
    live = work["reach"] / tokens
    parts = step_parts(config, live)
    flops = work["flops"] / tokens + 2 * head_params(config)
    return {"bytes": width * sum(parts.values()), "flops": width * flops}


def init_scale(path, leaf):
    """The family's one rule (the program's seeded constructor takes it too):
    gains about 1 and the one bias about 0 (deviation 0.1, so that a norm
    left out is seen), the head 0.02, stacked experts ``[experts, fan_in,
    fan_out]`` by their own fan-in, every other matrix by its first axis, and
    **the table 1.0**: a residual stream of the size of a layer's output, as a
    trained model's is. At 0.02 the stream entering layer 0 is smaller than
    one attention output (0.04), so the router reads mostly what the
    attention gave, and that depends on which rows the indexer kept: this
    family's reference against itself in bfloat16 then parts by 0.056
    (deviation) in layer 0's router logits where rows are chosen, against
    0.006 at 1.0, 62% of such positions take another expert in layer 1 and
    logits move by up to 2.5 (CPU, published widths, 2 layers; PERF.md
    section 6, PR 32). That reads the scale of the table, not the program."""
    name = path[-1]
    if name in ("ln1", "ln2", "q_norm", "k_norm", "idx_k_norm", "final_norm"):
        return (1.0, 0.1)
    if name == "idx_k_bias":
        return 0.1
    if name == "embed":
        return 1.0
    if name == "unembed":
        return 0.02
    if name.startswith("experts_"):
        return leaf.shape[1] ** -0.5
    return leaf.shape[0] ** -0.5


def fixture(config: Dict[str, Any]):
    """The family at fixture size and the limits its cells are held to on the
    CPU: 3 layers, 8 experts of which a token takes 2, the indexer keeps 8
    positions and a prefill chunk is 4 tokens, so that the fixture's prompts
    (2 to 12 tokens) are longer than both and the end-to-end case selects
    and chunks. It is served in float32 (``dtype``): with 8 rows attended to
    and 2 experts of 8 a tie that bfloat16 decides otherwise than float32
    moves a logit by 0.1 (benchmark/routed_reference.py), so at this size it
    is the mathematics that is held, to float32's rounding. Over 16 seeds on
    the CPU the served gap read 0.0 (the same choice at every position) and the fp8 control's at
    least 0.094; at most 0.136 of the served positions were set aside as near
    ties of the router (a distance of 0.01)."""
    tiny = dict(
        config, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_local_experts=8,
        num_experts_per_tok=2, vocab_size=300, reserved_positions=64,
        dtype="float32",
        sa_config=dict(config["sa_config"], indexer_head_dim=8, indexer_num_heads=2,
                       q_chunk_size=4, kv_chunk_size=4, topk=8),
        reduced=[], source="fixture for the CPU tests")
    for key in ("published", "deployment"):
        tiny.pop(key, None)
    return tiny, {"served_gap_max": 0.001, "near_tie_share": 0.3}

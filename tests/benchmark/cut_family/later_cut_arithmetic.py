"""The arithmetic of a family that is not GPT-2's and whose configuration is
cut: a dense first layer, then layers of routed experts of which this chip
holds a share. ``num_experts`` is what is held here; the router keeps the
published width (``published["num_experts"]`` where the key is reduced)."""


def vocab(config):
    return config["vocab_size"]


def max_len(config):
    return config["max_position_embeddings"]


def routed(config):
    """The experts the router scores: the published count."""
    return config.get("published", {}).get("num_experts", config["num_experts"])


def _layer_params(config, experts):
    d = config["hidden_size"]
    dense = config["first_dense_layers"]
    moe = config["num_hidden_layers"] - dense
    return (config["num_hidden_layers"] * 4 * d * d
            + dense * 2 * d * config["intermediate_size"]
            + moe * (d * routed(config)
                     + experts * 2 * d * config["moe_intermediate_size"]))


def total_params(config):
    """Of what is held here: ``num_experts`` experts a routed layer."""
    d = config["hidden_size"]
    return (_layer_params(config, config["num_experts"])
            + (2 * config["vocab_size"] + config["max_position_embeddings"]) * d)


def work(config, positions):
    tokens = sum(1 for _ in positions)
    reached = _layer_params(config, config["num_experts_per_tok"])
    flops = 2.0 * tokens * (reached + config["hidden_size"] * config["vocab_size"])
    return {"tokens_processed": tokens, "flops": flops}


def step_least(config, work, width):
    reached = min(config["num_experts"], config["num_experts_per_tok"] * max(width, 1))
    weights = _layer_params(config, reached) + config["hidden_size"] * config["vocab_size"]
    return {"bytes": 2.0 * weights,
            "flops": work["flops"] / max(work["tokens_processed"], 1) * width}


def init_scale(path, leaf):
    # stacked experts [experts, fan_in, fan_out]: the default rule would read
    # the number of experts as the fan-in
    return leaf.shape[1] ** -0.5 if path[-1].startswith("experts_") else None


def fixture(config):
    """The family at fixture size, cut as the real one is (4 of 8 experts),
    and the limits its cells are held to on the CPU. Over 45 seeds there the
    served gap read at most 0.0012 and the fp8 control's at least 0.0059
    (logits of 0.1 in size), and at most 0.167 of the served positions were
    set aside as near ties."""
    tiny = dict(config, hidden_size=32, num_hidden_layers=2, first_dense_layers=1,
                num_attention_heads=2, intermediate_size=64,
                moe_intermediate_size=32, num_experts=4, num_experts_per_tok=2,
                max_position_embeddings=64, vocab_size=300,
                reduced=["num_experts"], published={"num_experts": 8},
                source="fixture for the CPU tests")
    return tiny, {"served_gap_max": 0.003, "near_tie_share": 0.3}

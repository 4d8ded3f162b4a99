"""The GPT-2 family's arithmetic: sizes, bytes and FLOPs from shapes alone.

The yardstick's own arithmetic: nothing here reads the program. A
configuration file of this family carries the published keys of a
GPT-2-style ``config.json`` (``n_layer``, ``n_embd``, ``n_head``,
``n_positions``, ``vocab_size``, ``n_inner``); weights and cache are bfloat16
(2 bytes) and logits float32, as ``dtype`` in the file states.

It is the module a configuration gets where it names no ``"arithmetic"``
(``benchmark/family.py`` has the contract: ``vocab``, ``max_len``, ``work``,
``step_least``, ``total_params``); a model of another family brings its own
as a new file. The harness reaches this one only through that resolver; the
GPT-2 builders use ``sizes`` directly.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

WEIGHT_BYTES = 2  # bfloat16
CACHE_BYTES = 2  # bfloat16
LOGIT_BYTES = 4  # float32


def sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """The five sizes the decoder runs at, checked against what it can run."""
    d, heads = int(config["n_embd"]), int(config["n_head"])
    inner = config.get("n_inner") or 4 * d
    if int(inner) != 4 * d:
        raise ValueError(f"n_inner {inner}: the served decoder's MLP is 4 x n_embd")
    if d % heads:
        raise ValueError(f"n_embd {d} is not a multiple of n_head {heads}")
    return {"vocab": int(config["vocab_size"]), "d_model": d, "heads": heads,
            "layers": int(config["n_layer"]), "max_len": int(config["n_positions"])}


def vocab(config: Dict[str, Any]) -> int:
    """The ids traffic draws from."""
    return sizes(config)["vocab"]


def max_len(config: Dict[str, Any]) -> int:
    """The positions a sequence may reach: the cache's length."""
    return sizes(config)["max_len"]


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters every token is multiplied by: four matrices a layer
    (qkv 3d^2, proj d^2, mlp 4d^2 + 4d^2) and the untied output head."""
    s = sizes(config)
    return 12 * s["d_model"] ** 2 * s["layers"] + s["d_model"] * s["vocab"]


def total_params(config: Dict[str, Any]) -> int:
    """As the served code lays them out: token and position tables, the
    layers, and an output head that is not tied to the token table."""
    s = sizes(config)
    return matmul_params(config) + (s["vocab"] + s["max_len"]) * s["d_model"]


def cache_row_bytes(config: Dict[str, Any]) -> int:
    """One position's keys and values over all layers."""
    s = sizes(config)
    return 2 * s["layers"] * s["d_model"] * CACHE_BYTES


def cache_bytes_per_sequence(config: Dict[str, Any]) -> int:
    return cache_row_bytes(config) * sizes(config)["max_len"]


def token_flops(config: Dict[str, Any], position: int) -> int:
    """FLOPs the model needs for the token at 0-based ``position``: two per
    matmul parameter, and scores plus the weighted sum over the
    ``position + 1`` positions it may attend to (2 * d each, per layer)."""
    s = sizes(config)
    return (2 * matmul_params(config)
            + 4 * s["d_model"] * s["layers"] * (position + 1))


def token_cache_bytes(config: Dict[str, Any], position: int) -> int:
    """Least cache traffic for the token at ``position``: read the rows of
    the ``position`` earlier tokens, write its own."""
    return cache_row_bytes(config) * (position + 1)


def step_weight_bytes(config: Dict[str, Any]) -> int:
    """Least weight traffic of one step, whatever its batch: every matmul
    weight once. (The token and position tables are gathered by row.)"""
    return matmul_params(config) * WEIGHT_BYTES


def step_row_bytes(config: Dict[str, Any]) -> int:
    """Per sequence in a step, besides the cache: one row of each table in,
    one row of logits out."""
    s = sizes(config)
    return 2 * s["d_model"] * WEIGHT_BYTES + s["vocab"] * LOGIT_BYTES


def work(config: Dict[str, Any], positions: Iterable[int]) -> Dict[str, float]:
    """Totals over the 0-based positions of the tokens processed: how many,
    the FLOPs they need, and their least cache and per-row traffic."""
    tokens = flops = cache = 0
    for p in positions:
        tokens += 1
        flops += token_flops(config, p)
        cache += token_cache_bytes(config, p)
    return {"tokens_processed": tokens, "flops": flops, "cache_bytes": cache,
            "row_bytes": tokens * step_row_bytes(config)}


def step_least(config: Dict[str, Any], work: Dict[str, float],
               width: float) -> Dict[str, float]:
    """Least bytes and FLOPs of a mean step of ``width`` sequences: every
    matmul weight once, and for each sequence the mean token's cache rows,
    table rows and logits row (``work`` is this module's, over the window).
    The same count whatever implements the step."""
    tokens = work["tokens_processed"]
    step_bytes = (step_weight_bytes(config)
                  + width * (work["cache_bytes"] + work["row_bytes"]) / tokens)
    step_flops = width * work["flops"] / tokens
    return {"bytes": step_bytes, "flops": step_flops}

"""The bytes and FLOPs functions against numbers worked by hand from the
published sizes of both configurations."""

import json
import os

import pytest

from benchmark import family, shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# gpt2-large: d 1280, 36 layers, vocab 50257, 1024 positions
#   a layer's matrices 12 * 1280^2 = 19,660,800; x 36 = 707,788,800
#   output head 1280 * 50257 = 64,328,960     -> matmul 772,117,760
#   tables (50257 + 1024) * 1280 = 65,639,680 -> total 837,757,440
#   a cache row 2 (k, v) * 36 * 1280 * 2 B = 184,320 B; x 1024 = 188,743,680 B
# cerebras-gpt-1.3b: d 2048, 24 layers, vocab 50257, 2048 positions
#   12 * 2048^2 = 50,331,648; x 24 = 1,207,959,552; head 102,926,336
#   matmul 1,310,885,888; tables (50257 + 2048) * 2048 = 107,120,640
#   total 1,418,006,528; row 2 * 24 * 2048 * 2 = 196,608 B; x 2048 = 402,653,184 B
HAND = {
    "gpt2-large": dict(matmul=772_117_760, total=837_757_440, row=184_320,
                       cache=188_743_680, layers=36, d=1280, vocab=50257),
    "cerebras-gpt-1.3b": dict(matmul=1_310_885_888, total=1_418_006_528,
                              row=196_608, cache=402_653_184, layers=24,
                              d=2048, vocab=50257),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameter_counts(name):
    assert shapes.matmul_params(config(name)) == HAND[name]["matmul"]
    assert shapes.total_params(config(name)) == HAND[name]["total"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_cache_bytes(name):
    assert shapes.cache_row_bytes(config(name)) == HAND[name]["row"]
    assert shapes.cache_bytes_per_sequence(config(name)) == HAND[name]["cache"]


@pytest.mark.parametrize("name", sorted(HAND))
@pytest.mark.parametrize("position", [0, 31, 1023])
def test_token_flops_and_cache_traffic(name, position):
    h = HAND[name]
    attention = 4 * h["d"] * h["layers"] * (position + 1)
    assert shapes.token_flops(config(name), position) == 2 * h["matmul"] + attention
    assert shapes.token_cache_bytes(config(name), position) == h["row"] * (position + 1)


@pytest.mark.parametrize("name", sorted(HAND))
def test_step_bytes(name):
    h = HAND[name]
    assert shapes.step_weight_bytes(config(name)) == 2 * h["matmul"]
    assert shapes.step_row_bytes(config(name)) == 2 * h["d"] * 2 + h["vocab"] * 4


@pytest.mark.parametrize("name", sorted(HAND))
def test_work_adds_up_over_positions(name):
    c = config(name)
    total = shapes.work(c, [0, 1, 2, 10])
    assert total["tokens_processed"] == 4
    assert total["flops"] == sum(shapes.token_flops(c, p) for p in (0, 1, 2, 10))
    assert total["cache_bytes"] == HAND[name]["row"] * (1 + 2 + 3 + 11)
    assert total["row_bytes"] == 4 * shapes.step_row_bytes(c)


@pytest.mark.parametrize("name, positions", [("gpt2-large", 1024),
                                             ("cerebras-gpt-1.3b", 2048)])
def test_what_the_harness_asks_of_the_family(name, positions):
    c = config(name)
    assert family.arithmetic(c) is shapes  # no "arithmetic" key: the GPT-2 family's
    assert shapes.vocab(c) == HAND[name]["vocab"] and shapes.max_len(c) == positions


@pytest.mark.parametrize("name", sorted(HAND))
@pytest.mark.parametrize("width", [1.0, 2.5])
def test_step_least_is_the_weights_once_and_the_mean_tokens_rows(name, width):
    c, h = config(name), HAND[name]
    total = shapes.work(c, [0, 1, 2, 10])
    least = shapes.step_least(c, total, width)
    # the four tokens' cache rows: 1 + 2 + 3 + 11 = 17; a row of each table
    # in and a row of logits out for each
    rows = h["row"] * 17 + 4 * (2 * h["d"] * 2 + h["vocab"] * 4)
    assert least["bytes"] == 2 * h["matmul"] + width * rows / 4
    assert least["flops"] == width * total["flops"] / 4
    assert set(least) == {"bytes", "flops"}


@pytest.mark.parametrize("broken, message", [
    ({"n_inner": 1000}, "4 x n_embd"),
    ({"n_head": 7}, "multiple"),
])
def test_sizes_the_decoder_cannot_run_are_refused(broken, message):
    with pytest.raises(ValueError, match=message):
        shapes.sizes(dict(config("gpt2-large"), **broken))

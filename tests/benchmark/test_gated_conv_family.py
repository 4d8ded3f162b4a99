"""The gated-convolution family's files against numbers worked by hand from
the published sizes, its readers against facts made by hand, and its cell's
traffic against what the cell is there for."""

import json
import os

import pytest

from benchmark import family, run
from benchmark import gated_conv_arithmetic as arithmetic
from benchmark.sessions import SessionPlan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HOME = os.path.join(ROOT, "benchmark")
CELL = "lfm2-24b-a2b.sharegpt32"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
# the published config.json's keys, as the configuration file has to hold them
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": (["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9
                    + ["full_attention", "conv"]),
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def config():
    with open(os.path.join(HOME, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


# d 2048; 32 query and 8 key-value heads of 64; dense SwiGLU 11,776; 64
# experts of 1,536, 4 a token; vocabulary 65,536; layers published 1-9:
# [conv, attention, conv, conv, conv, attention, conv, conv, conv], the first dense
#   a conv layer 3*2048^2 + 2048^2 + 3*2048 = 16,783,360; with its gain 16,785,408
#   an attention layer 2048*64*(2*32 + 2*8) = 10,485,760; gains 2048 + 2*64 -> 10,487,936
#   the dense SwiGLU 3*2048*11776 = 72,351,744; with its gain 72,353,792
#   a router 2048*64 + 64 bias = 131,136; an expert 3*2048*1536 = 9,437,184
#   a routed layer's part 131,136 + 64*9,437,184 + 2048 = 604,112,960
#   7 conv + 2 attention + 1 dense + 8 routed = 5,043,731,200
#   table and head 2 * 65,536*2048 = 268,435,456; final gain 2,048
#   -> 5,312,168,704 held; the published 40 layers 23,977,879,168
#   a position's rows 2 layers * 2 (key, value) * 8*64 * 2 B = 4,096 B
#   a slot's conv state 7 layers * 2 rows * 2048 * 2 B = 57,344 B
HAND = dict(conv=16_783_360, attention=10_485_760, dense=72_351_744, router=131_136,
            expert=9_437_184, total=5_312_168_704, published=23_977_879_168,
            row=4_096, state=57_344)


def test_parameter_and_state_counts():
    c = config()
    assert arithmetic.conv_params(c) == HAND["conv"]
    assert arithmetic.attention_params(c) == HAND["attention"]
    assert arithmetic.dense_params(c) == HAND["dense"]
    assert arithmetic.router_params(c) == HAND["router"]
    assert arithmetic.expert_params(c) == HAND["expert"]
    assert arithmetic.total_params(c) == HAND["total"]
    assert arithmetic.total_params({**c, **c["published"]}) == HAND["published"]
    assert arithmetic.cache_row_bytes(c) == HAND["row"]
    assert arithmetic.conv_state_bytes(c) == HAND["state"]
    assert arithmetic.routed_layers(c) == 8 and arithmetic.max_len(c) == 2048
    assert arithmetic.vocab(c) == 65536 and family.arithmetic(c) is arithmetic
    # weights and thirty-two slots of 2,048 positions: 68% of one chip's 16 GB
    held = 2 * HAND["total"] + 32 * (2048 * HAND["row"] + HAND["state"])
    assert held == 10_894_607_872 and round(100 * held / 16e9) == 68


def test_work_step_parts_and_step_least():
    c = config()
    work = arithmetic.work(c, [0, 99, 999])
    assert work["tokens_processed"] == 3 and work["reach"] == 1 + 100 + 1000
    assert work["flops"] == sum(arithmetic.token_flops(c, p) for p in (0, 99, 999))
    # the attention's part of a token's FLOPs grows with its reach, 2 layers
    assert (arithmetic.token_flops(c, 999) - arithmetic.token_flops(c, 0)
            == 2 * 4 * 2048 * 999)
    parts = arithmetic.step_parts(c, work, 32.0)
    # a round reads one token's 4 experts a routed layer at least, and the routers
    assert parts["experts"] == 2 * (8 * HAND["router"] + 8 * 4 * HAND["expert"])
    assert parts["attention"] == 32 * HAND["row"] * 1101 / 3
    assert parts["state"] == 32 * 2 * HAND["state"]
    assert parts["head"] == 2 * 2048 * 65536 + 32 * 4 * 65536
    assert parts["rows"] == 32 * (2 * 2048 + HAND["row"])
    # the weights part is every parameter held but the experts and routers,
    # the table and the head
    assert parts["weights"] == 2 * (HAND["total"] - 8 * (HAND["router"] + 64 * HAND["expert"])
                                    - 2 * 65536 * 2048)
    least = arithmetic.step_least(c, work, 32.0)
    assert least["bytes"] == sum(parts.values())
    assert least["flops"] == 32 * (work["flops"] / 3 + 2 * 2048 * 65536)


def test_in_a_round_of_thirty_two_the_experts_read_are_most_of_the_bytes():
    """What the cell is there for: 32 members route 128 pairs a routed layer,
    which reach 55 of 64 experts if drawn at random; those experts are nine
    tenths of a round's least bytes, where ``step_least``'s one token's four
    are under half of a count that is a seventh of it: ``step_roofline`` reads
    low here by construction."""
    c = config()
    reached = 64 * (1 - (1 - 4 / 64) ** 32)
    assert 54 < reached < 57
    parts = arithmetic.step_parts(c, arithmetic.work(c, range(200, 700)), 32.0)
    read = {**parts, "experts": arithmetic.experts_bytes(c, 8 * reached)}
    assert 0.90 < read["experts"] / sum(read.values()) < 0.93
    assert 9.0e9 < sum(read.values()) < 9.3e9
    assert 0.40 < parts["experts"] / sum(parts.values()) < 0.50
    assert 0.14 < sum(parts.values()) / sum(read.values()) < 0.16


def test_the_file_holds_every_published_key_but_the_cut():
    stated = config()
    assert stated["source"] == SOURCE
    differs = sorted(key for key, value in PUBLISHED.items() if stated.get(key) != value)
    assert differs == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert sorted(stated["reduced"]) == differs
    assert stated["published"] == {key: PUBLISHED[key] for key in differs}
    # published layers 1 to 9: the second dense layer and two whole periods
    assert stated["layer_types"] == PUBLISHED["layer_types"][1:10]
    assert stated["num_hidden_layers"] - stated["num_dense_layers"] >= 4  # the floor
    assert set(stated["assumed"]) >= {
        "dtype", "recalled", "norm", "conv", "conv_state", "attention", "dense_ffn",
        "router", "experts", "head", "prefill_chunk", "init"}


def test_the_fixture_has_every_kind_of_layer_and_crosses_chunks():
    tiny, limits = arithmetic.fixture(config())
    assert tiny["layer_types"] == ["conv", "full_attention", "conv", "full_attention"]
    assert tiny["num_dense_layers"] == 1 and tiny["prefill_chunk"] == 4
    assert (tiny["num_experts"], tiny["num_experts_per_tok"]) == (8, 2)
    assert tiny["dtype"] == "float32" and "published" not in tiny
    assert tiny["arithmetic"] == "benchmark.gated_conv_arithmetic"
    assert tiny["reference"] == "benchmark.gated_conv_reference"
    assert set(limits) == {"served_gap_max", "near_tie_share"}


def _facts(scopes, registry=None):
    c = config()
    return {"config": c, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "work": arithmetic.work(c, [500] * 10), "registry": registry,
            "batch_histogram": {"32": 100},
            "trace": {"step_count": 100, "step_device_ms": 14.0, "scopes": scopes}}


REACHED = "client_tpu_server_experts_reached{program=round}"
ROUNDS = "client_tpu_server_experts_reached_rounds{program=round}"
# 100 rounds that read 56 experts in each of 8 routed layers
TALLY = {REACHED: 44800.0, ROUNDS: 100.0,
         "client_tpu_server_experts_reached{program=chunk}": 5000.0,
         "client_tpu_server_experts_reached_rounds{program=chunk}": 10.0}


def test_the_readers_read_the_tally_and_their_scopes():
    # 100 rounds: the routed layers 1.2 s, 12 ms a round; short_conv 2% of 1.4 s
    scopes = [["moe_experts", 1.15, 800, 1.0], ["moe_route", 0.05, 800, 1.0],
              ["short_conv", 0.028, 700, 1.0], ["attention", 0.172, 200, 1.0]]
    facts = _facts(scopes, TALLY)
    assert run.read_layer_metric(HOME, "experts_reached_mean", facts) == 56.0
    roofline = run.read_layer_metric(HOME, "round_experts_roofline", facts)
    least = 2 * (8 * HAND["router"] + 448 * HAND["expert"])
    assert roofline == pytest.approx(100 * least / 819e9 / 12e-3)
    assert 80 < roofline < 90
    assert run.read_layer_metric(HOME, "short_conv_share", facts) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", ["round_experts_roofline", "experts_reached_mean",
                                    "short_conv_share"])
def test_a_program_without_the_tally_or_the_scopes_gives_nothing(metric):
    """A parent that lacks the program's part, or another family's cell."""
    bare = _facts([["mlp", 0.1, 1, 1.0]], {"client_tpu_server_decode_steps{live=512}": 5.0})
    assert run.read_layer_metric(HOME, metric, bare) is None
    assert run.read_layer_metric(HOME, metric, {"config": config()}) is None
    no_rounds = _facts([["moe_experts", 0.1, 1, 1.0], ["short_conv", 0.1, 1, 1.0]],
                       {**TALLY, ROUNDS: 0.0})
    if metric != "short_conv_share":
        assert run.read_layer_metric(HOME, metric, no_rounds) is None


def test_every_sharegpt_session_fits_a_slot_and_prompts_are_a_few_chunks():
    cell = run.resolve_cell(ROOT, CELL)
    plan = SessionPlan(cell["traffic"], 65536, 7)
    assert plan.longest <= 2048 == arithmetic.max_len(cell["config"])
    assert cell["cell"]["users"] == 32 == cell["cell"]["args"]["slots"]
    assert cell["traffic"]["api"] == "stream"
    assert cell["cell"]["step_program"] == "jit_step"
    assert cell["cell"]["builder"] == "benchmark.gated_conv_builders:generate"
    assert plan.session(0)["prompt"].max() < 65536
    chunk = cell["config"]["prefill_chunk"]
    assert 2048 % chunk == 0
    chunks = -(-plan.prompts // chunk)
    assert chunks.min() == 1 and chunks.max() == -(-1024 // chunk)

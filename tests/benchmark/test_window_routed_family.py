"""The window-and-full, routed family's files against numbers worked by hand
from the published sizes, its readers against facts made by hand, and its
cell's traffic against what the cell is there for."""

import json
import os

import pytest

from benchmark import family, run
from benchmark import window_routed_arithmetic as arithmetic
from benchmark.sessions import SessionPlan

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HOME = os.path.join(ROOT, "benchmark")
CELL = "smallthinker-21ba3b-instruct.longctx16"
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/"
          "config.json")
# the published config.json's keys, as the configuration file has to hold them
LAYOUT = [0, 1, 1, 1] * 13
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": LAYOUT, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": LAYOUT,
    "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936}


def config():
    with open(os.path.join(HOME, "configs", "smallthinker-21ba3b-instruct.json")) as f:
        return json.load(f)


# d 2560; 28 query and 4 key-value heads of 128; 64 experts of 768, 6 a
# token; vocabulary 151,936; layers published 0-7: [full, window, window,
# window] twice, every one routed
#   attention 2560*128*(2*28 + 2*4) = 20,971,520; router 2560*64 = 163,840
#   an expert 3*2560*768 = 5,898,240; 64 of them 377,487,360
#   a layer 20,971,520 + 163,840 + 377,487,360 + 2*2560 = 398,627,840
#   8 layers 3,189,022,720; table and head 2 * 151,936*2560 = 777,912,320;
#   final gain 2,560 -> 3,966,937,600 held; the published 52 layers
#   21,506,562,560
#   a position's rows a layer 2 (key, value) * 4*128 * 2 B = 2,048 B; a slot
#   2 * 16,384 + 6 * 4,096 rows of it = 117,440,512 B (112 MiB)
HAND = dict(attention=20_971_520, router=163_840, expert=5_898_240, layer=398_627_840,
            total=3_966_937_600, published=21_506_562_560, row=2_048,
            slot=117_440_512)


def test_parameter_and_state_counts():
    c = config()
    assert arithmetic.attention_params(c) == HAND["attention"]
    assert arithmetic.router_params(c) == HAND["router"]
    assert arithmetic.expert_params(c) == HAND["expert"]
    assert arithmetic.layer_params(c, 64) == HAND["layer"]
    assert arithmetic.total_params(c) == HAND["total"]
    assert arithmetic.total_params({**c, **c["published"]}) == HAND["published"]
    assert arithmetic.row_bytes(c) == HAND["row"]
    assert arithmetic.slot_bytes(c) == HAND["slot"] == 112 * 2**20
    assert arithmetic.routed_layers(c) == 8 and arithmetic.max_len(c) == 16384
    assert arithmetic.vocab(c) == 151936 and family.arithmetic(c) is arithmetic
    # weights and sixteen slots: 61% of one chip's 16 GB
    held = 2 * HAND["total"] + 16 * HAND["slot"]
    assert held == 9_812_923_392 and round(100 * held / 16e9) == 61


def test_work_step_parts_and_step_least():
    c = config()
    work = arithmetic.work(c, [0, 4095, 9999])
    assert work["tokens_processed"] == 3
    assert work["ring_rows"] == 1 + 4096 + 4096
    assert work["prefix_rows"] == work["reach"] == 1 + 4096 + 10000
    assert work["flops"] == sum(arithmetic.token_flops(c, p) for p in (0, 4095, 9999))
    # the attention's part of a token's FLOPs: 6 window layers stop growing
    # at the window, 2 full layers do not
    assert (arithmetic.token_flops(c, 9999) - arithmetic.token_flops(c, 0)
            == 4 * 28 * 128 * (6 * 4095 + 2 * 9999))
    parts = arithmetic.step_parts(c, work, 16.0)
    # a round reads one token's 6 experts a routed layer at least, and the routers
    assert parts["experts"] == 2 * (8 * HAND["router"] + 8 * 6 * HAND["expert"])
    assert parts["attention"] == 16 * HAND["row"] * (6 * 8193 + 2 * 14097) / 3
    assert parts["head"] == 2 * 2560 * 151936 + 16 * 4 * 151936
    assert parts["rows"] == 16 * (2 * 2560 + 8 * HAND["row"])
    assert parts["weights"] == 2 * (8 * (HAND["attention"] + 2 * 2560) + 2560)
    least = arithmetic.step_least(c, work, 16.0)
    assert least["bytes"] == sum(parts.values())
    assert least["flops"] == 16 * (work["flops"] / 3 + 2 * 2560 * 151936)


def test_a_round_of_sixteen_reads_its_experts_beside_its_attention_rows():
    """What the cell is there for: 16 members route 96 pairs a layer, which
    reach 51 of 64 experts if drawn at random; at the 16,384 rung the full
    layers' prefixes and the rings are a quarter of a round's bytes, the
    experts three fifths; ``step_least``'s one token's six are a tenth of
    what is read, so ``step_roofline`` reads low here by construction."""
    c = config()
    reached = 64 * (1 - (1 - 6 / 64) ** 16)
    assert 50 < reached < 52
    experts = arithmetic.experts_bytes(c, 8 * reached)
    rings = arithmetic.attention_bytes(c, 16 * 6 * 4096)
    prefixes = arithmetic.attention_bytes(c, 16 * 2 * 16384)
    head = 2 * 2560 * 151936
    weights = 2 * 8 * HAND["attention"]
    read = experts + rings + prefixes + head + weights
    assert 7.7e9 < read < 7.9e9 and 9.3e-3 < read / 819e9 < 9.7e-3
    assert 0.23 < (rings + prefixes) / read < 0.25
    assert 0.60 < experts / read < 0.63
    least = arithmetic.step_parts(c, arithmetic.work(c, range(8000, 8200)), 16.0)
    assert least["experts"] / read < 0.1


def test_the_file_holds_every_published_key_but_the_cut():
    stated = config()
    assert stated["source"] == SOURCE
    differs = sorted(key for key, value in PUBLISHED.items() if stated.get(key) != value)
    assert differs == ["num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert sorted(stated["reduced"]) == differs
    assert stated["published"] == {key: PUBLISHED[key] for key in differs}
    # published layers 0 to 7: two whole periods, every expert, every head
    assert stated["sliding_window_layout"] == stated["rope_layout"] == LAYOUT[:8]
    assert stated["num_hidden_layers"] >= 4  # the floor
    assert stated["prefill_chunk"] == 512 and stated["reserved_positions"] == 16384
    assert set(stated["assumed"]) >= {
        "dtype", "recalled", "norm", "router", "attention", "window", "full", "experts",
        "head", "rotary", "prefill_chunk", "init"}


def test_the_fixture_has_every_kind_of_layer_and_wraps_its_ring():
    tiny, limits = arithmetic.fixture(config())
    assert tiny["sliding_window_layout"] == tiny["rope_layout"] == [0, 1, 1, 1] * 2
    assert tiny["sliding_window_size"] == 16 and tiny["prefill_chunk"] == 8
    assert (tiny["moe_num_primary_experts"], tiny["moe_num_active_primary_experts"]) == (
        8, 2)
    assert tiny["dtype"] == "float32" and "published" not in tiny
    assert tiny["arithmetic"] == "benchmark.window_routed_arithmetic"
    assert tiny["reference"] == "benchmark.window_routed_reference"
    assert set(limits) == {"served_gap_max", "near_tie_share"}


REACHED = "client_tpu_server_experts_reached{program=round}"
ROUNDS = "client_tpu_server_experts_reached_rounds{program=round}"
SLIDING = "client_tpu_server_attention_rows_read{kind=sliding}"
FULL = "client_tpu_server_attention_rows_read{kind=full}"
# 100 rounds of 16 members: 51 experts in each of 8 layers; each member past
# the window in the 6 window layers and at position 7,999 in the 2 full ones
TALLY = {REACHED: 40800.0, ROUNDS: 100.0, SLIDING: 100 * 16 * 6 * 4096.0,
         FULL: 100 * 16 * 2 * 8000.0}


def _facts(scopes, registry=None):
    c = config()
    return {"config": c, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "work": arithmetic.work(c, [8000] * 10), "registry": registry,
            "batch_histogram": {"16": 100},
            "trace": {"step_count": 100, "step_device_ms": 12.0, "scopes": scopes}}


def test_the_readers_read_the_counts_and_their_scopes():
    # 100 rounds: the window layers' attention 1.2 ms a round, the full
    # layers' 2.0, the routed layers 7.0
    scopes = [["moe_experts", 0.68, 800, 1.0], ["moe_route", 0.02, 800, 1.0],
              ["sliding_attention", 0.12, 600, 1.0], ["full_attention", 0.2, 200, 1.0]]
    facts = _facts(scopes, TALLY)
    assert run.read_layer_metric(HOME, "experts_reached_mean", facts) == 51.0
    roofline = run.read_layer_metric(HOME, "round_experts_roofline", facts)
    least = 2 * (8 * HAND["router"] + 408 * HAND["expert"])
    assert roofline == pytest.approx(100 * least / 819e9 / 7e-3)
    sliding = run.read_layer_metric(HOME, "sliding_attention_roofline", facts)
    assert sliding == pytest.approx(100 * 16 * 6 * 4096 * 2048 / 819e9 / 1.2e-3)
    assert 81 < sliding < 82
    full = run.read_layer_metric(HOME, "full_attention_roofline", facts)
    assert full == pytest.approx(100 * 16 * 2 * 8000 * 2048 / 819e9 / 2e-3)
    assert run.read_layer_metric(HOME, "sliding_rows_share", facts) == pytest.approx(
        100 * 6 * 4096 / (6 * 4096 + 2 * 8000))


@pytest.mark.parametrize("metric", ["sliding_attention_roofline",
                                    "full_attention_roofline", "sliding_rows_share"])
def test_a_program_without_the_counts_or_the_scopes_gives_nothing(metric):
    """A parent that lacks the program's part, or another family's cell."""
    bare = _facts([["attention", 0.1, 1, 1.0]],
                  {"client_tpu_server_decode_steps{live=512}": 5.0})
    assert run.read_layer_metric(HOME, metric, bare) is None
    assert run.read_layer_metric(HOME, metric, {"config": config()}) is None
    if metric != "sliding_rows_share":
        no_scope = _facts([["moe_experts", 0.1, 1, 1.0]], TALLY)
        assert run.read_layer_metric(HOME, metric, no_scope) is None
        no_rounds = dict(_facts([["sliding_attention", 0.1, 1, 1.0],
                                 ["full_attention", 0.1, 1, 1.0]], TALLY),
                         batch_histogram={})
        assert run.read_layer_metric(HOME, metric, no_rounds) is None


def test_every_longctx_session_passes_the_window_and_fits_a_slot():
    cell = run.resolve_cell(ROOT, CELL)
    plan = SessionPlan(cell["traffic"], 151936, 7)
    assert plan.longest == 15360 + 512 <= 16384 == arithmetic.max_len(cell["config"])
    assert cell["cell"]["users"] == 16 == cell["cell"]["args"]["slots"]
    assert cell["traffic"]["api"] == "stream" and cell["traffic"]["ramp_seconds"] == 5
    assert cell["cell"]["step_program"] == "jit_step"
    assert cell["cell"]["builder"] == "benchmark.window_routed_builders:generate"
    assert plan.session(0)["prompt"].max() < 151936
    chunk, window = cell["config"]["prefill_chunk"], cell["config"]["sliding_window_size"]
    assert window % chunk == 0 and 16384 % chunk == 0
    # every prompt passes the window by a chunk at least
    assert plan.prompts.min() >= window + chunk and plan.prompts.max() <= 15360

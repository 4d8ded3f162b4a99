"""The window-and-summaries family's files against numbers worked by hand from
the published sizes, its readers against facts made by hand, and its cells'
traffic against what the cells are there for."""

import json
import os

import numpy as np
import pytest

from benchmark import family, run
from benchmark import window_summary_arithmetic as arithmetic
from benchmark.sessions import SessionPlan, length_pool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HOME = os.path.join(ROOT, "benchmark")
CELL = "evabyte.bytedoc12"
LONG_PROMPTS = "gpt2-large.seq16-longprompt"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    with open(os.path.join(HOME, "configs", "evabyte.json")) as f:
        return json.load(f)


# d 4096; 32 heads of 128; MLP 11,008; vocabulary 320; 8 prediction heads; 8 layers
#   matrices 4 * 4096^2 + 3 * 4096 * 11008 = 67,108,864 + 135,266,304 = 202,375,168
#   gains 2 * 4096, chunk vectors 2 * 32 * 128 -> a layer 202,391,552; x 8 = 1,619,132,416
#   table 320 * 4096 = 1,310,720; final gain 4,096; heads 4096 * 2560 = 10,485,760
#   -> 1,630,932,992
#   a row of state 8 layers * 2 (key, value) * 4096 * 2 B = 131,072 B
#   a slot (2,048 ring rows + 32,768 / 16 summary rows) * 131,072 = 536,870,912 B
HAND = dict(matrices=202_375_168, layer=202_391_552, heads=10_485_760,
            total=1_630_932_992, row=131_072, slot=536_870_912)


def test_parameter_and_state_counts():
    c = config()
    assert arithmetic.matrix_params(c) == HAND["matrices"]
    assert arithmetic.layer_params(c) == HAND["layer"]
    assert arithmetic.head_params(c) == HAND["heads"]
    assert arithmetic.total_params(c) == HAND["total"]
    assert arithmetic.row_bytes(c) == HAND["row"]
    assert arithmetic.slot_bytes(c) == HAND["slot"]
    assert arithmetic.max_len(c) == 32768 and arithmetic.vocab(c) == 320
    assert family.arithmetic(c) is arithmetic
    # weights and sixteen slots: 74% of one chip's 16 GB
    held = 2 * HAND["total"] + 16 * HAND["slot"]
    assert held == 11_851_800_576 and round(100 * held / 16e9) == 74
    # every position's keys and values would be eight times a slot
    assert 32768 * HAND["row"] == 8 * HAND["slot"]


@pytest.mark.parametrize("position,exact,before", [
    (0, 1, 0), (2047, 2048, 0), (2048, 1, 128), (9000, 809, 512), (22015, 1536, 1280)])
def test_rows_attended_and_token_flops(position, exact, before):
    c = config()
    assert arithmetic.rows_attended(c, position) == (exact, before)
    assert arithmetic.token_flops(c, position) == 8 * (
        2 * HAND["matrices"] + 4 * 4096 * (exact + before))


def test_work_step_parts_and_step_least():
    c = config()
    work = arithmetic.work(c, [9, 2048, 9000])
    assert work["tokens_processed"] == 3 and work["reach"] == 10 + 2049 + 9001
    assert work["window_rows"] == 10 + 1 + 809 and work["summary_rows"] == 128 + 512
    assert work["flops"] == sum(arithmetic.token_flops(c, p) for p in (9, 2048, 9000))
    parts = arithmetic.step_parts(c, work, 12.0)
    assert parts["weights"] == 2 * 8 * HAND["layer"]
    assert parts["attention"] == 12 * HAND["row"] * (820 + 640) / 3
    assert parts["head"] == 2 * (HAND["heads"] + 4096) + 12 * 4 * 2560
    assert parts["rows"] == 12 * (2 * 4096 + HAND["row"])
    least = arithmetic.step_least(c, work, 12.0)
    assert least["bytes"] == sum(parts.values())
    assert least["flops"] == 12 * (work["flops"] / 3 + 2 * HAND["heads"])
    # the weights are read once whatever the width; the state a member
    wider = arithmetic.step_parts(c, work, 24.0)
    assert wider["weights"] == parts["weights"]
    assert wider["attention"] == 2 * parts["attention"]


def test_in_a_round_of_twelve_the_state_is_two_fifths_to_half_of_the_least_bytes():
    """What the cell is there for: a step of one stream is nine tenths
    weights, a round of twelve past the first windows is not (least bytes:
    the rows attended to, a mean 1,024 of the ring's 2,048 among them; the
    program reads the ring whole, so of what it moves the state is more)."""
    c = config()
    for position, low, high in ((4096, 0.36, 0.40), (20480, 0.50, 0.54)):
        # decode steps over a whole window from ``position`` on
        work = arithmetic.work(c, range(position, position + 2048))
        alone = arithmetic.step_parts(c, work, 1.0)
        assert alone["weights"] / sum(alone.values()) > 0.86
        round_of_12 = arithmetic.step_parts(c, work, 12.0)
        assert low < round_of_12["attention"] / sum(round_of_12.values()) < high


def test_the_file_holds_every_published_key_but_the_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    stated = config()
    assert stated["source"] == row["source_url"]
    differs = [key for key, value in row["config"].items() if stated.get(key) != value]
    assert differs == stated["reduced"] == ["num_hidden_layers"]
    assert stated["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert stated["num_hidden_layers"] >= 4  # the guide's floor
    assert set(stated["assumed"]) >= {
        "dtype", "norm", "projections", "rotary", "chunk_summary", "attention", "mlp",
        "heads", "prefill_chunk", "recalled", "init"}


def test_the_fixture_crosses_windows_and_finishes_chunks():
    """2 layers, a window of 8, chunks of 2, prompts taken 4 a dispatch: the
    fixture's sessions (2 to 12 of each) cross a window, end some mid-chunk,
    and read summaries."""
    tiny, limits = arithmetic.fixture(config())
    assert (tiny["num_hidden_layers"], tiny["window_size"], tiny["chunk_size"],
            tiny["prefill_chunk"]) == (2, 8, 2, 4)
    assert tiny["arithmetic"] == "benchmark.window_summary_arithmetic"
    assert tiny["reference"] == "benchmark.window_summary_reference"
    assert tiny["num_pred_heads"] == 8 and tiny["vocab_size"] == 320
    assert set(limits) == {"served_gap_max"}
    assert arithmetic.max_len(tiny) == 64 and "published" not in tiny
    import benchmark_fixture

    prompts = length_pool(benchmark_fixture.TINY_LENGTHS["prompt"], 8)
    outputs = length_pool(benchmark_fixture.TINY_LENGTHS["output"], 8)
    assert prompts.max() > 8 > prompts.min() and (prompts % 2).any()
    assert prompts.max() + outputs.max() > 16  # a third window


def _facts(scopes, registry=None, histogram=None):
    c = config()
    return {"config": c, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "work": arithmetic.work(c, [9000] * 10), "registry": registry,
            "batch_histogram": histogram or {"12": 100},
            "trace": {"step_count": 100, "step_device_ms": 12.0, "scopes": scopes}}


def test_the_two_device_readers_read_their_scopes():
    # 100 rounds; the two attentions 0.5 s -> 5 ms a round; summarise 1% of 1.2 s
    scopes = [["window_attention", 0.36, 800, 1.0], ["summary_attention", 0.14, 800, 1.0],
              ["mlp", 0.4, 800, 1.0], ["cache_update", 0.288, 9600, 1.0],
              ["summarise", 0.012, 2400, 1.0]]
    facts = _facts(scopes)
    parts = arithmetic.step_parts(config(), facts["work"], 12.0)
    roofline = run.read_layer_metric(HOME, "eva_attention_roofline", facts)
    assert roofline == pytest.approx(100 * parts["attention"] / 819e9 / 5e-3)
    assert 45 < roofline < 55
    assert run.read_layer_metric(HOME, "summarise_share", facts) == pytest.approx(1.0)
    # the chunk's one kernel is an attention scope too
    facts = _facts([["eva_attention", 0.25, 1, 1.0]])
    assert run.read_layer_metric(HOME, "eva_attention_roofline", facts) == pytest.approx(
        2 * roofline)


@pytest.mark.parametrize("metric", ["eva_attention_roofline", "summarise_share"])
def test_a_trace_without_the_scopes_gives_nothing(metric):
    """A parent that lacks the program's part, or another family's cell."""
    assert run.read_layer_metric(HOME, metric, _facts([["mlp", 0.1, 1, 1.0]])) is None
    assert run.read_layer_metric(HOME, metric, {"config": config()}) is None
    other = _facts([["window_attention", 0.1, 1, 1.0]])
    other["config"] = run.resolve_cell(ROOT, "gpt2-large.seq16")["config"]
    other["work"] = family.arithmetic(other["config"]).work(other["config"], [40] * 10)
    assert run.read_layer_metric(HOME, "eva_attention_roofline", other) is None


def test_the_counter_reads_the_registry():
    registry = {"client_tpu_server_window_rows_read": 3000.0,
                "client_tpu_server_summary_rows_read": 1000.0,
                "client_tpu_server_summaries_written": 50.0}
    assert run.read_layer_metric(
        HOME, "summary_rows_share", _facts([], registry)) == 25.0
    # a parent's registry has the steps and not the new series; a cell whose
    # decoder keeps no such state counts none
    old = _facts([], {"client_tpu_server_decode_steps{live=512}": 5.0})
    assert run.read_layer_metric(HOME, "summary_rows_share", old) is None
    none = _facts([], dict.fromkeys(registry, 0.0))
    assert run.read_layer_metric(HOME, "summary_rows_share", none) is None


def test_every_bytedoc_prompt_is_past_the_first_window():
    cell = run.resolve_cell(ROOT, CELL)
    plan = SessionPlan(cell["traffic"], 320, 7)
    assert plan.prompts.min() >= 2304 > cell["config"]["window_size"]
    assert plan.longest == 22016 <= arithmetic.max_len(cell["config"])
    assert 7_500 < plan.prompts.mean() < 8_800 and 450 < plan.outputs.mean() < 560
    assert cell["cell"]["users"] == 12 and cell["traffic"]["api"] == "stream"
    assert cell["cell"]["step_program"] == "jit_step"
    assert cell["cell"]["builder"] == "benchmark.window_summary_builders:generate"
    assert plan.session(0)["prompt"].max() < 320
    # a prompt is 5 to 40 dispatches of 512 positions
    chunks = -(-plan.prompts // cell["config"]["prefill_chunk"])
    assert chunks.min() == 5 and chunks.max() == 40


def test_the_long_prompt_cell_is_a_s_cell_under_other_lengths():
    cell = run.resolve_cell(ROOT, LONG_PROMPTS)
    control = run.resolve_cell(ROOT, "gpt2-large.seq16")
    assert cell["cell"] == control["cell"] and cell["config"] == control["config"]
    assert cell["traffic"]["api"] == control["traffic"]["api"] == "sequence"
    plan = SessionPlan(cell["traffic"], 50257, 7)
    assert plan.prompts.min() >= 512 and plan.prompts.max() <= 896
    assert plan.outputs.min() >= 16 and plan.outputs.max() <= 32
    assert 640 < plan.prompts.mean() < 720 and plan.longest <= 1024
    # every session passes the short rung of the decoder's ladder (256)
    assert plan.prompts.min() > 256


# the largest sound reading of twenty-two and the smallest of the fp8 control's six
# (my chip runs, PR 34: PERF.md section 4)
SOUND_MAX, CONTROL_MIN = 0.0264, 0.5607


def test_the_cell_s_limit_lies_between_its_readings():
    """``served_gap_max`` between the largest sound reading and the smallest
    of the fp8 control on the chip, with room on both sides."""
    limits = run.resolve_cell(ROOT, CELL)["cell"]["limits"]
    assert set(limits) == {"served_gap_max"}
    assert SOUND_MAX * 1.25 < limits["served_gap_max"] < CONTROL_MIN / 1.25

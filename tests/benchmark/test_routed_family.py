"""The routed family's files against numbers worked by hand from the published
sizes, its readers against facts made by hand, and its cells' traffic against
what the cells are there for."""

import json
import os

import numpy as np
import pytest

from benchmark import family, run
from benchmark import routed_arithmetic as arithmetic
from benchmark.sessions import SessionPlan, length_pool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HOME = os.path.join(ROOT, "benchmark")
CELL = "keye-vl-2.0-30b-a3b.longdoc4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    with open(os.path.join(HOME, "configs", "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


# d 2048; 32 query and 4 key-value heads of 128; indexer 16 heads of 64 and one
# key; 128 experts of width 768, 8 a token; vocabulary 151,936; 6 layers
#   attention 2048*4096 + 2*(2048*512) + 4096*2048 = 18,874,368
#   indexer 2048*1024 + 2048*64 + 2048*16 = 2,260,992; router 2048*128 = 262,144
#   an expert 3*2048*768 = 4,718,592; x 128 = 603,979,776
#   gains 2*2048 + 2*128 + 2*64 = 4,480 -> a layer 625,381,760; x 6 = 3,752,290,560
#   table and head 2 * 151,936*2048 = 622,329,856; final gain 2,048
#   -> 4,374,622,464
#   a cache row 6 * 2 B * (2*512 + 64) = 13,056 B; x 32,768 = 427,819,008 B a sequence
HAND = dict(attention=18_874_368, indexer=2_260_992, router=262_144,
            expert=4_718_592, layer=625_381_760, head=311_164_928,
            total=4_374_622_464, row=13_056)


def test_parameter_counts():
    c = config()
    assert arithmetic.attention_params(c) == HAND["attention"]
    assert arithmetic.indexer_params(c) == HAND["indexer"]
    assert arithmetic.router_params(c) == HAND["router"]
    assert arithmetic.expert_params(c) == HAND["expert"]
    assert arithmetic.layer_params(c, 128) == HAND["layer"]
    assert arithmetic.head_params(c) == HAND["head"]
    assert arithmetic.total_params(c) == HAND["total"]
    assert arithmetic.cache_row_bytes(c) == HAND["row"]
    assert arithmetic.max_len(c) == 32768 and arithmetic.vocab(c) == 151_936
    assert family.arithmetic(c) is arithmetic


@pytest.mark.parametrize("live,indexed,rows", [
    (1000, 0, 1000), (2048, 0, 2048), (2049, 2049, 2048), (16384, 16384, 2048)])
def test_step_parts(live, indexed, rows):
    """The experts a token reaches, not those held; the indexer's keys past
    ``topk`` alone; never more than ``topk`` key and value rows."""
    parts = arithmetic.step_parts(config(), live)
    assert parts["experts"] == 6 * 2 * (HAND["router"] + 8 * HAND["expert"])
    assert parts["sparse_attention"] == 6 * (
        2 * HAND["indexer"] + 128 * indexed + 2048 * rows)
    assert parts["attention_weights"] == 6 * 2 * HAND["attention"]
    assert parts["head"] == 2 * HAND["head"] + 4 * 151_936
    assert parts["rows"] == 2 * 2048 + 6 * (2048 + 128)


def test_a_step_at_16k_is_1_37_gb_and_the_head_is_45_percent_of_it():
    parts = arithmetic.step_parts(config(), 16384)
    total = sum(parts.values())
    assert round(total / 1e9, 2) == 1.37
    assert round(100 * parts["head"] / total) == 45


@pytest.mark.parametrize("position", [0, 2047, 2048, 20000])
def test_token_flops(position):
    reach = position + 1
    matmul = 2 * 6 * (HAND["attention"] + HAND["indexer"] + HAND["router"]
                      + 8 * HAND["expert"])
    scored = 2 * 16 * 64 * reach if reach > 2048 else 0
    attended = 4 * 32 * 128 * min(reach, 2048)
    assert arithmetic.token_flops(config(), position) == matmul + 6 * (scored + attended)


def test_work_and_step_least():
    c = config()
    work = arithmetic.work(c, [9, 19, 12287])
    assert work["tokens_processed"] == 3 and work["reach"] == 10 + 20 + 12288
    assert work["flops"] == sum(arithmetic.token_flops(c, p) for p in (9, 19, 12287))
    least = arithmetic.step_least(c, work, 1.0)
    live = work["reach"] / 3
    assert least["bytes"] == sum(arithmetic.step_parts(c, live).values())
    assert least["flops"] == work["flops"] / 3 + 2 * HAND["head"]
    assert arithmetic.step_least(c, work, 2.0)["bytes"] == 2 * least["bytes"]


def test_the_file_holds_every_published_key_but_the_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    stated = config()
    assert stated["source"] == row["source_url"]
    differs = [key for key, value in row["config"].items() if stated.get(key) != value]
    assert differs == stated["reduced"] == ["num_hidden_layers"]
    assert stated["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert stated["num_hidden_layers"] >= 4  # the guide's floor
    assert set(stated["assumed"]) >= {
        "dtype", "qk_norm", "rotary", "indexer_key_norm", "indexer_rotary",
        "indexer_weight_scale", "chunk_sizes", "router", "init"}


def test_the_fixture_selects_and_chunks():
    """3 layers, 8 experts top-2, topk 8 and chunk 4: shorter than the
    fixture's prompts, so the CPU end-to-end case selects and chunks."""
    tiny, limits = arithmetic.fixture(config())
    assert (tiny["num_hidden_layers"], tiny["num_experts"],
            tiny["num_experts_per_tok"]) == (3, 8, 2)
    assert tiny["sa_config"]["topk"] == 8 and tiny["sa_config"]["q_chunk_size"] == 4
    assert tiny["arithmetic"] == "benchmark.routed_arithmetic"
    assert tiny["reference"] == "benchmark.routed_reference"
    assert set(limits) == {"served_gap_max", "near_tie_share"}
    import benchmark_fixture

    prompts = length_pool(benchmark_fixture.TINY_LENGTHS["prompt"], 8)
    assert prompts.max() > 8 and prompts.max() > 4 and prompts.min() < 4


def _facts(scopes, registry=None):
    c = config()
    return {"config": c, "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
            "work": arithmetic.work(c, [16383] * 10), "registry": registry,
            "trace": {"step_count": 100, "step_device_ms": 3.0, "scopes": scopes}}


def test_the_two_rooflines_read_their_scopes():
    # 100 steps; moe_route + moe_experts 0.1 s -> 1 ms a step
    facts = _facts([["moe_experts", 0.09, 900, 100.0], ["moe_route", 0.01, 100, 100.0],
                    ["indexer", 0.02, 1, 1.0], ["select", 0.03, 1, 1.0],
                    ["sparse_attention", 0.05, 1, 1.0], ["unembed", 0.08, 1, 1.0]])
    parts = arithmetic.step_parts(config(), 16384)
    experts = run.read_layer_metric(HOME, "experts_roofline", facts)
    sparse = run.read_layer_metric(HOME, "sparse_attention_roofline", facts)
    assert experts == pytest.approx(100 * parts["experts"] / 819e9 / 1e-3)
    assert sparse == pytest.approx(100 * parts["sparse_attention"] / 819e9 / 1e-3)
    assert 50 < experts < 60 and 7 < sparse < 9


@pytest.mark.parametrize("metric", ["experts_roofline", "sparse_attention_roofline"])
def test_a_trace_without_the_scopes_gives_nothing(metric):
    """A parent that lacks the program's part, or another family's cell."""
    assert run.read_layer_metric(HOME, metric, _facts([["mlp", 0.1, 1, 1.0]])) is None
    other = _facts([["moe_experts", 0.1, 1, 1.0]])
    other["config"] = run.resolve_cell(ROOT, "gpt2-large.seq16")["config"]
    assert run.read_layer_metric(HOME, metric, other) is None


def test_the_two_counters_read_the_registry():
    registry = {"client_tpu_server_selecting_steps": 30.0,
                "client_tpu_server_decode_steps{live=8192}": 10.0,
                "client_tpu_server_decode_steps{live=32768}": 30.0,
                "client_tpu_server_prefill_tokens": 50_000.0,
                "client_tpu_server_prefill_ns": 10e9,
                "client_tpu_server_prefill_chunks": 100.0}
    facts = _facts([], registry)
    assert run.read_layer_metric(HOME, "selecting_step_share", facts) == 75.0
    assert run.read_layer_metric(HOME, "prefill_ms_per_ktoken", facts) == 200.0
    # a parent's registry has the steps and not the new series
    old = _facts([], {"client_tpu_server_decode_steps{live=512}": 5.0})
    assert run.read_layer_metric(HOME, "selecting_step_share", old) is None
    assert run.read_layer_metric(HOME, "prefill_ms_per_ktoken", old) is None


def test_every_longdoc_prompt_is_past_the_indexer_s_topk():
    cell = run.resolve_cell(ROOT, CELL)
    plan = SessionPlan(cell["traffic"], 151_936, 7)
    assert plan.prompts.min() >= 4096 > cell["config"]["sa_config"]["topk"]
    assert plan.longest <= arithmetic.max_len(cell["config"])
    assert 11_000 < plan.prompts.mean() < 13_500 and 150 < plan.outputs.mean() < 200
    assert cell["cell"]["users"] == 4
    assert cell["cell"]["step_program"] == "jit_routed_step"


def test_the_cell_s_limits_lie_between_their_readings():
    """``served_gap_max`` between the largest sound reading and the smallest
    of the fp8 control (PERF.md section 4: 0.222 over nine, 0.482 over five),
    with room on both sides; ``near_tie_share`` over the largest reading
    (0.616) and such that three positions in ten at least are compared, at
    the router distance the readings were taken at."""
    from benchmark import routed_reference

    limits = run.resolve_cell(ROOT, CELL)["cell"]["limits"]
    assert 0.222 * 1.25 < limits["served_gap_max"] < 0.482 / 1.25
    assert 0.616 < limits["near_tie_share"] <= 0.7
    assert routed_reference.NEAR == 0.01


def test_sharegpt_sessions_pass_the_first_rung():
    cell = run.resolve_cell(ROOT, "cerebras-gpt-1.3b.sharegpt6")
    plan = SessionPlan(cell["traffic"], 50257, 7)
    # over every pairing of a prompt and an output of the pool, the share of
    # the steps (a prompt's tokens are steps here) that reach past position 512
    steps = long = 0
    for prompt in plan.prompts:
        for output in plan.outputs:
            steps += prompt + output - 1
            long += max(0, prompt + output - 1 - 512)
    assert plan.longest <= 2048 and 0.18 < long / steps < 0.26  # 0.217
    control = run.resolve_cell(ROOT, "cerebras-gpt-1.3b.stream6")["cell"]
    assert {k: cell["cell"][k] for k in ("builder", "args", "users", "step_program")} \
        == {k: control[k] for k in ("builder", "args", "users", "step_program")}


@pytest.mark.parametrize("scale", [1.0, 1e-3, 37.0])
def test_the_control_s_rounding_is_float8_e4m3(scale):
    """The family's control rounds in float32 arithmetic; bit for bit what
    the GPT-2 family's does through the float8 type."""
    import jax.numpy as jnp

    from benchmark import reference as gpt2
    from benchmark import routed_reference

    rng = np.random.default_rng(3)
    x = jnp.asarray(np.concatenate([
        rng.standard_normal(50_000) * scale, rng.standard_normal(500) * scale * 1e-4,
        [0.0, -0.0, scale * 10]]).astype(np.float32))
    assert (np.asarray(gpt2._fp8(x)) == np.asarray(routed_reference._fp8(x))).all()

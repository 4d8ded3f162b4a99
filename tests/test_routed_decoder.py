"""The routed decoder against the family's plain reference, at a small size
on the CPU with seeded random weights: prefill by chunks and then decode
through the cache against the reference's full forward pass, on logits; the
indexer's selection; the expert layer's shares; the counts and spans the
serving path keeps. Counts, tokens and logits, no clock.

Two precisions. With float32 weights the program's every product is a
float32 one (the type of the weights is the type of the caches and
products), and it must agree with the reference to rounding of float32
sums in another order: ``EXACT``. That holds the mathematics: the chunks'
edges, the masks, the selection, the cache, the routing. With bfloat16
weights, as served, a router score or an index score that nearly ties is
decided otherwise than in float32 at some positions, and at this size (8
rows attended to, 2 experts of 8) one such position moves every later one;
so a position's widest logit difference reads 0.003 to 0.006 (bfloat16's
step, 2 ** -8, on logits of size 0.6) at most positions and 0.10 to 0.15 at
one or two of seven. The test holds the median position under ``ROUNDED`` /
30 and every position under ``ROUNDED``; a wrong mask reads 0.1 at the
median position and 0.6 at the widest.
"""

import numpy as np
import pytest

from benchmark import routed_arithmetic as arithmetic
from benchmark import routed_reference as reference
from client_tpu.models import routed_decoder
from client_tpu.models.decoder import TinyDecoderModel
from client_tpu.models.generate import TinyGenerateModel
from client_tpu.models.routed_decoder import RoutedDecoderModel
from client_tpu.server import ServerCore, timeline

EXACT = 2e-5
ROUNDED = 0.3

CONFIG = {
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "vocab_size": 300, "max_position_embeddings": 4096,
    "reserved_positions": 64,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 4,
                  "q_chunk_size": 4, "topk": 8}}


def configured(**changes):
    sa = dict(CONFIG["sa_config"], **changes.pop("sa_config", {}))
    return dict(CONFIG, sa_config=sa, **changes)


def built(config, seed=5, dtype="float32", **args):
    """The decoder with weights drawn from the seed, in ``dtype``."""
    import jax

    decoder = RoutedDecoderModel(config, seed=seed,
                                 init_scale=arithmetic.init_scale, **args)
    decoder._ensure_built()
    decoder._params = jax.tree_util.tree_map(
        lambda leaf: leaf.astype(dtype), decoder._params)
    return decoder


def served_logits(decoder, tokens, prompt):
    """Prefill of the first ``prompt`` tokens, then a step a token: the
    logits after each position from ``prompt - 1`` on."""
    logits, caches = decoder.prefill(decoder._fresh_cache(), tokens[:prompt], 0)
    rows = [np.asarray(logits)]
    for pos in range(prompt, len(tokens)):
        logits, caches = decoder.decode_step(caches, int(tokens[pos]), pos)
        rows.append(np.asarray(logits))
    return np.stack(rows)


def reference_logits(decoder, config, tokens, prompt):
    return np.asarray(reference.forward(
        decoder._params, config, list(tokens), np.arange(prompt - 1, len(tokens)))[0])


@pytest.fixture(scope="module")
def exact():
    return built(CONFIG)


@pytest.fixture(scope="module")
def rounded():
    return built(CONFIG, dtype="bfloat16")


TOKENS = np.random.default_rng(17).integers(0, 300, 24)


# prompts on both sides of a chunk's edge (4) and of topk (8), and outputs
# that cross both
@pytest.mark.parametrize("prompt", [1, 3, 4, 5, 7, 8, 9, 13, 16, 20])
def test_prefill_then_decode_is_the_reference_s_forward_pass(exact, prompt):
    tokens = TOKENS[:prompt + 6]
    got = served_logits(exact, tokens, prompt)
    want = reference_logits(exact, CONFIG, tokens, prompt)
    assert np.abs(want).max() > 0.2
    np.testing.assert_allclose(got, want, atol=EXACT, rtol=0)


@pytest.mark.parametrize("prompt", [3, 9, 16])
def test_the_served_precision_stays_within_rounding_of_it(rounded, prompt):
    tokens = TOKENS[:prompt + 6]
    got = served_logits(rounded, tokens, prompt)
    want = reference_logits(rounded, CONFIG, tokens, prompt)
    off = np.abs(got - want).max(axis=1)  # the widest logit of each position
    assert off.max() < ROUNDED and np.median(off) < ROUNDED / 30


def test_a_wrong_mask_is_not_within_rounding(exact):
    """What ``ROUNDED`` is held against: the reference keeping every causal
    position where the program keeps 8."""
    tokens = TOKENS[:22]
    got = served_logits(exact, tokens, 16)
    dense = configured(sa_config={"topk": 64})
    off = np.abs(got - reference_logits(exact, dense, tokens, 16)).max(axis=1)
    assert off.max() > ROUNDED and np.median(off) > ROUNDED / 3


def test_a_prefill_from_the_middle_keeps_the_rows_before_it(exact):
    """Two prefills, the second starting inside a chunk: the block's rows
    below its first token keep what the first prefill wrote."""
    tokens = TOKENS[:14]
    _, caches = exact.prefill(exact._fresh_cache(), tokens[:6], 0)
    logits, caches = exact.prefill(caches, tokens[6:], 6)
    want = reference_logits(exact, CONFIG, tokens, 14)
    np.testing.assert_allclose(np.asarray(logits)[None], want, atol=EXACT, rtol=0)


def test_a_step_whose_topk_covers_the_cache_is_dense_attention(exact):
    """``topk >= live``: the program has no selection, and a program that
    has one keeps every causal position while there are ``topk`` or fewer."""
    dense = RoutedDecoderModel(configured(sa_config={"topk": 64}), seed=None)
    dense._ensure_built()
    dense._params = exact._params
    lowered = lambda decoder: decoder._step_program.lower(
        decoder._params, decoder._fresh_cache(), decoder._tables, 0, 0).as_text()
    # the router's top_k a layer, and in the selecting program the indexer's
    assert lowered(dense).count("chlo.top_k") == 3
    assert lowered(exact).count("chlo.top_k") == 6
    tokens = TOKENS[:14]
    a, b = served_logits(dense, tokens, 3), served_logits(exact, tokens, 3)
    # positions 2..7 attend to all of 8 or fewer: the same rows in both
    np.testing.assert_allclose(a[:6], b[:6], atol=EXACT, rtol=0)
    assert np.abs(a[8:] - b[8:]).max() > 100 * EXACT


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_largest_mask_is_top_k_as_a_mask(seed):
    """Exactly ``k`` entries a row, the lower index first among equals."""
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((6, 40)).astype(np.float32)
    scores[0, 5:30] = 0.0          # a run of equal entries across the edge
    scores[1, :] = -np.inf         # nothing finite
    scores[2, 10:] = -np.inf       # fewer finite entries than k
    scores[3, ::2] = scores[3, 1]  # many equal to one value
    scores[4, 3] = -0.0
    scores[4, 4] = 0.0
    mask = np.asarray(routed_decoder.largest_mask(jnp.asarray(scores), 12))
    _, chosen = lax.top_k(jnp.asarray(scores), 12)
    want = np.zeros_like(mask)
    np.put_along_axis(want, np.asarray(chosen), True, axis=1)
    assert mask.sum(axis=1).tolist() == [12] * 6
    rows = [0, 1, 2, 3, 5]  # lax.top_k on the CPU orders -0.0 and 0.0 itself
    assert (mask[rows] == want[rows]).all()


@pytest.mark.parametrize("seed", [3, 4])
def test_the_chosen_set_is_the_reference_s_away_from_ties(seed):
    """The program's index scores and choice, in bfloat16, against the
    reference's in float32, for queries over 40 positions of which 8 are
    kept: the same set wherever the reference's 8th and 9th scores are not
    within bfloat16's rounding of each other."""
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(seed)
    queries, positions, heads, width, k = 24, 40, 2, 8, 8
    qi = rng.standard_normal((queries, heads, width)).astype(np.float32)
    ki = rng.standard_normal((positions, width)).astype(np.float32)
    wi = rng.standard_normal((queries, heads)).astype(np.float32)
    first = positions - queries
    want, scores = (np.asarray(a) for a in reference.chosen_positions(
        jnp.asarray(qi), jnp.asarray(wi), jnp.asarray(ki), first, topk=k))
    causal = np.arange(positions)[None, :] <= (first + np.arange(queries))[:, None]
    ranked = -np.sort(-np.where(causal, scores, -np.inf), axis=1)
    clear = ranked[:, k - 1] - ranked[:, k] > 0.05
    assert clear.sum() >= queries // 2
    got_scores = routed_decoder.index_scores(
        jnp.asarray(qi, jnp.bfloat16), jnp.asarray(wi), jnp.asarray(ki, jnp.bfloat16))
    got_scores = jnp.where(causal, got_scores, -jnp.inf)
    chunk = np.asarray(routed_decoder.largest_mask(got_scores, k)) & causal
    assert (chunk[clear] == want[clear]).all()
    assert want.sum(axis=1).tolist() == [k] * queries
    for row in np.flatnonzero(clear):  # the step's own way: one query, top_k
        one = routed_decoder.index_scores(
            jnp.asarray(qi[row:row + 1], jnp.bfloat16), jnp.asarray(wi[row:row + 1]),
            jnp.asarray(ki, jnp.bfloat16))
        _, chosen = lax.top_k(jnp.where(causal[row], one[0], -jnp.inf), k)
        assert set(np.asarray(chosen).tolist()) == set(np.flatnonzero(want[row]))


@pytest.mark.parametrize("dtype,limit", [("float32", EXACT), ("bfloat16", 0.05)])
def test_the_shares_of_the_expert_layer_add_up_to_the_whole(exact, dtype, limit):
    """``model-configs`` section 4: told "experts 0-3 of 8" and "4-7 of 8",
    the layer routes over all 8 and gives its own experts' part; the two
    parts add up to what the uncut reference layer gives."""
    import jax
    import jax.numpy as jnp

    s = exact.sizes
    layer = exact._params["layers"][1]
    x = jnp.asarray(np.random.default_rng(8).standard_normal((10, s.d_model)),
                    jnp.float32)
    whole, _ = reference.expert_layer(x, layer, CONFIG)
    want = np.asarray(whole - x)
    cast = jax.tree_util.tree_map(lambda leaf: leaf.astype(dtype), layer)
    h2 = routed_decoder.rms(x, cast["ln2"], s.eps)
    which, gates = routed_decoder.route(h2, cast["router"], s)
    h2 = h2.astype(dtype)
    parts = []
    for first in (0, 4):
        held = dict(cast, **{name: cast[name][first:first + 4] for name in (
            "experts_gate", "experts_up", "experts_down")})
        parts.append(np.asarray(routed_decoder.expert_layer(
            h2, which, gates, held, s, first)))
    assert min(np.abs(part).max() for part in parts) > 0.1
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=limit, rtol=0)
    # and the reference, given the same share, gives the same part
    share, _ = reference.expert_layer(x, held, CONFIG, first=4)
    np.testing.assert_allclose(parts[1], np.asarray(share - x), atol=limit, rtol=0)


def test_a_decoder_told_its_share_serves_it(exact):
    """The model built with ``experts=(4, 4)`` holds four experts a layer
    and serves the partial result, as the reference does when told so."""
    share = RoutedDecoderModel(CONFIG, seed=None, experts=(4, 4))
    share._ensure_built()
    assert share._params["layers"][0]["experts_up"].shape == (4, 64, 32)
    cut = lambda layer: dict(layer, **{name: layer[name][4:] for name in (
        "experts_gate", "experts_up", "experts_down")})
    share._params = dict(exact._params,
                         layers=[cut(layer) for layer in exact._params["layers"]])
    tokens = TOKENS[:12]
    got = served_logits(share, tokens, 9)
    want = reference_logits(share, dict(CONFIG, first_expert=4), tokens, 9)
    np.testing.assert_allclose(got, want, atol=EXACT, rtol=0)
    assert np.abs(got - served_logits(exact, tokens, 9)).max() > 0.01


LONG = configured(reserved_positions=1024, hidden_size=32, num_hidden_layers=2,
                  sa_config={"topk": 256, "q_chunk_size": 64})


def test_a_session_crosses_the_rungs_with_every_program_compiled_ahead():
    """Two rungs (256, 1,024) and ``topk`` at the first: a prompt of 250
    prefilled in chunks of 64 and an output that crosses to the selecting
    rung, against the reference; nothing compiles after the warm-up."""
    timeline.COMPILES.listen()
    decoder = built(LONG, seed=2)
    assert decoder.ladder() == decoder._rungs == (256, 1024)
    decoder._ensure_warm()
    compiled = timeline.COMPILES.count
    tokens = np.random.default_rng(3).integers(0, 300, 262)
    got = served_logits(decoder, tokens, 250)
    assert timeline.COMPILES.count == compiled
    want = reference_logits(decoder, LONG, tokens, 250)
    np.testing.assert_allclose(got, want, atol=EXACT, rtol=0)
    assert decoder.steps_by_rung.by_rung() == {256: 6, 1024: 6}
    assert decoder.steps_by_rung.totals() == {
        "selecting_steps": 6, "prefill_tokens": 250, "prefill_chunks": 4,
        "prefill_ns": 0}


def test_the_weights_are_what_the_arithmetic_counts():
    import jax

    decoder = RoutedDecoderModel(CONFIG, seed=None)
    decoder._ensure_built()
    leaves = jax.tree_util.tree_leaves(decoder._params)
    assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == \
        arithmetic.total_params(CONFIG)


def test_a_seeded_model_is_drawn_by_the_rule_it_is_given():
    """One rule for the family's weights, the arithmetic's, which the
    benchmark draws by; the program's seeded constructor takes it, and has
    a plain one of its own for a model made without."""
    wide = configured(hidden_size=256, num_experts=4, num_experts_per_tok=2)
    for rule, embed, gain in ((arithmetic.init_scale, 1.0, 0.1), (None, 1.0, 0.0)):
        args = {} if rule is None else {"init_scale": rule}
        decoder = RoutedDecoderModel(wide, seed=3, **args)
        decoder._ensure_built()
        params = decoder._params
        layer = params["layers"][0]
        std = lambda leaf: float(np.asarray(leaf, np.float32).std())
        assert std(params["embed"]) == pytest.approx(embed, rel=0.05)
        assert std(layer["ln1"]) == pytest.approx(gain, abs=0.03)
        assert std(layer["wq"]) == pytest.approx(256 ** -0.5, rel=0.05)
        assert std(layer["experts_down"]) == pytest.approx(32 ** -0.5, rel=0.05)


@pytest.mark.parametrize("change", [
    {"sa_config": {"indexer_num_kv_heads": 2}}, {"mlp_only_layers": [0]},
    {"decoder_sparse_step": 2}, {"num_key_value_heads": 3},
    {"reserved_positions": 66}])
def test_a_configuration_the_block_cannot_run_is_refused(change):
    with pytest.raises(ValueError):
        RoutedDecoderModel(configured(**change), seed=None)


def _stream(core, prompt, max_tokens):
    return [int(r["outputs"][0]["array"].reshape(-1)[0]) for r in core.infer_stream(
        "tiny_lm_generate", "", {"inputs": [
            {"name": "TOKENS", "datatype": "INT32", "shape": [1, len(prompt)],
             "array": np.array([prompt], np.int32)},
            {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
             "array": np.array([max_tokens], np.int32)}]})]


def test_served_on_the_stream_path_with_its_counts_in_the_registry(exact):
    """``TinyGenerateModel(decoder=...)`` through ``ServerCore``'s decoupled
    stream, as the GPT-2 decoder is served: greedy tokens equal to stepping
    the decoder by hand, and the registry's series of the prefill and of the
    selecting steps."""
    core = ServerCore([TinyGenerateModel(decoder=exact)])
    prompt = [int(t) for t in TOKENS[:10]]
    tokens = _stream(core, prompt, 5)
    logits, caches = exact.prefill(exact._fresh_cache(), prompt, 0)
    by_hand, pos = [], len(prompt)
    for _ in range(5):
        by_hand.append(int(np.asarray(logits).argmax()))
        logits, caches = exact.decode_step(caches, by_hand[-1], pos)
        pos += 1
    assert tokens == by_hand
    assert _stream(core, prompt[:3], 3)  # a prompt under topk: no step selects yet
    snapshot = core.metrics_registry().snapshot()
    value = lambda name: {row["labels"]["model"]: row["value"]
                          for row in snapshot[name]["series"]}
    assert value("client_tpu_server_selecting_steps") == {"tiny_lm_generate": 4}
    assert value("client_tpu_server_prefill_tokens") == {"tiny_lm_generate": 13}
    assert value("client_tpu_server_prefill_chunks") == {"tiny_lm_generate": 4}
    assert value("client_tpu_server_prefill_ns")["tiny_lm_generate"] > 0
    steps = {row["labels"]["live"]: row["value"]
             for row in snapshot["client_tpu_server_decode_steps"]["series"]}
    assert steps == {"64": 6}  # decode steps alone: a chunk is not a step


def test_the_sequence_api_prefills_a_prompt_in_chunks(exact):
    prompt = [int(t) for t in TOKENS[:9]]
    out = exact.execute({"TOKENS": np.array([prompt], np.int32)},
                        {"sequence_id": 7, "sequence_start": True})
    np.testing.assert_allclose(out["LOGITS"], reference_logits(exact, CONFIG, prompt, 9),
                               atol=EXACT, rtol=0)
    out = exact.execute({"TOKENS": np.array([[5]], np.int32)},
                        {"sequence_id": 7, "sequence_end": True})
    np.testing.assert_allclose(
        out["LOGITS"], reference_logits(exact, CONFIG, prompt + [5], 10),
        atol=EXACT, rtol=0)
    assert exact.live_sequences() == 0


@pytest.mark.parametrize("padded", [None, 64])
@pytest.mark.parametrize("first,count", [(0, 3), (17, 5)])
def test_the_reference_s_padding_reaches_no_real_position(
        exact, monkeypatch, first, count, padded):
    """A session passed at a longer length (its own rounded up, or the
    traffic's longest) in blocks of 4, so that 23 tokens have whole blocks of
    padding, which are not worked, and rows of padding, which go to no
    expert: the logits and margins are those of the pass in one block."""
    tokens = [int(t) for t in TOKENS[:23]]
    want = np.arange(first, first + count)
    whole, whole_margins = reference.forward(exact._params, CONFIG, tokens,
                                             np.arange(23))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    logits, margins = reference.forward(exact._params, CONFIG, tokens, want,
                                        padded=padded)
    assert logits.shape == (count, 300)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(whole)[want],
                               atol=EXACT, rtol=0)
    np.testing.assert_allclose(margins, whole_margins[:, want], atol=EXACT, rtol=0)


def test_the_gpt2_decoder_s_sequence_path_enqueues_a_prompt_whole():
    """``TinyDecoderModel.execute`` steps a prompt as it did before PR 32:
    through ``decode_step`` a token, nothing waited for, no prefill counted
    (only the routed decoder sends a prompt through ``prefill`` there)."""
    decoder = TinyDecoderModel(seed=0)
    prompt = [int(t) for t in TOKENS[:5] % 256]
    out = decoder.execute({"TOKENS": np.array([prompt], np.int32)},
                          {"sequence_id": 3, "sequence_start": True,
                           "sequence_end": True})
    caches, logits = decoder._fresh_cache(), None
    for pos, token in enumerate(prompt):
        logits, caches = decoder.decode_step(caches, token, pos)
    assert (out["LOGITS"][0] == np.asarray(logits, np.float32)).all()
    assert decoder.steps_by_rung.totals()["prefill_tokens"] == 0


@pytest.mark.parametrize("prompt", [1, 2, 7])
def test_the_gpt2_decoder_s_prefill_is_the_loop_it_replaces(prompt):
    """``TinyDecoderModel.prefill``: the step a token, each waited for, bit
    for bit what ``generate.py`` did itself."""
    decoder = TinyDecoderModel(seed=0)
    decoder._ensure_built()
    tokens = [int(t) for t in TOKENS[:prompt] % 256]
    caches, logits = decoder._fresh_cache(), None
    for pos, token in enumerate(tokens):  # the loop as it stood in generate.py
        logits, caches = decoder.decode_step(caches, token, pos)
        logits.block_until_ready()
    got, got_caches = decoder.prefill(decoder._fresh_cache(), tokens, 0)
    assert (np.asarray(got) == np.asarray(logits)).all()
    for a, b in zip(got_caches, caches):
        assert (np.asarray(a["k"]) == np.asarray(b["k"])).all()
        assert (np.asarray(a["v"]) == np.asarray(b["v"])).all()
    assert decoder.steps_by_rung.by_rung() == {128: 2 * prompt}
    assert decoder.steps_by_rung.totals()["prefill_tokens"] == prompt
    assert decoder.steps_by_rung.totals()["prefill_chunks"] == prompt


def test_a_profiler_session_holds_the_prefill_chunk_span(tmp_path, exact):
    import glob

    import jax
    from jax.profiler import ProfileData

    exact._ensure_warm()
    jax.profiler.start_trace(str(tmp_path))
    try:
        exact.prefill(exact._fresh_cache(), TOKENS[:6], 0)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = [event.name for plane in ProfileData.from_file(found[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for event in line.events]
    assert names.count(timeline.SPAN_PREFILL_CHUNK) == 2

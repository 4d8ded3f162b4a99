"""The plain reference against the served decoder at a small size on the CPU:
prefill and then decoding through the cache, through the slot batcher too,
agrees with the reference's full forward pass, and the same pass in fp8, the
control, does not."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import builders, family, reference, run
from benchmark.server import make_params

CONFIG = {"n_layer": 3, "n_embd": 64, "n_head": 4, "n_positions": 48,
          "n_inner": None, "vocab_size": 211}
PROMPTS = [[5, 9, 200, 3, 17, 17, 42], [1, 2, 3], [100] * 12]
NEW_TOKENS = 20


@pytest.fixture(scope="module")
def decoder():
    decoder = builders.build_decoder(CONFIG, seed=0)
    decoder._params = make_params(decoder._params, seed=2**31 + 3)
    return decoder


def decode(decoder, prompt, n):
    """Prefill, then greedy decoding through the cache: the served path."""
    caches, pos, logits = decoder._fresh_cache(), 0, None
    tokens, rows = [], []
    for t in prompt:
        logits, caches = decoder._step_fn(decoder._params, caches, int(t), pos)
        pos += 1
    for _ in range(n):
        rows.append(np.asarray(logits, np.float32))
        tokens.append(int(rows[-1].argmax()))
        logits, caches = decoder._step_fn(decoder._params, caches, tokens[-1], pos)
        pos += 1
    return tokens, np.stack(rows)


@pytest.mark.parametrize("prompt", PROMPTS, ids=["seven", "three", "twelve"])
def test_prefill_then_decode_agrees_with_the_full_pass(decoder, prompt):
    tokens, served = decode(decoder, prompt, NEW_TOKENS)
    full = np.asarray(reference.forward(
        decoder._params, np.array([prompt + tokens], np.int32), CONFIG["n_head"]))
    first = len(prompt) - 1
    want = full[0, first:first + NEW_TOKENS]
    # bf16 products against float32 ones: logits of about 0.7 in size
    assert np.abs(served - want).max() < 0.03


def sessions(decoder):
    return [{"prompt": p, "tokens": decode(decoder, p, NEW_TOKENS)[0]} for p in PROMPTS]


def test_served_tokens_lie_at_the_references_best(decoder):
    read = reference.served_token_gaps(
        decoder._params, CONFIG, sessions(decoder), length=40)
    assert read["positions"] == len(PROMPTS) * NEW_TOKENS
    assert 0.0 <= read["served_gap_max"] < 0.02


def test_the_fp8_control_does_not(decoder):
    read = reference.served_token_gaps(
        decoder._params, CONFIG, sessions(decoder), length=40, control=True)
    assert read["control_gap_max"] > 3 * max(read["served_gap_max"], 0.005)
    # through the run's own comparison, at a limit between the two readings
    exact = {"sessions_failed": 0, "argmax_mismatch": 0, "compiles_in_window": 0}
    limits = {"served_gap_max": 0.02}
    assert run.judge(dict(exact, served_gap_max=read["served_gap_max"]), limits)[1]
    compared, correct = run.judge(
        dict(exact, served_gap_max=read["control_gap_max"]), limits)
    assert correct is False and compared["served_gap_max"]["value"] > 0.02


def test_a_wrong_token_reads_as_a_wide_gap(decoder):
    rows = sessions(decoder)
    rows[1]["tokens"][7] = (rows[1]["tokens"][7] + 1) % CONFIG["vocab_size"]
    read = reference.served_token_gaps(decoder._params, CONFIG, rows, length=40)
    assert read["served_gap_max"] > 0.1


def test_a_session_longer_than_the_room_is_refused(decoder):
    with pytest.raises(ValueError, match="room for"):
        reference.served_token_gaps(
            decoder._params, CONFIG,
            [{"prompt": [1] * 30, "tokens": [2] * 20}], length=40)


def test_fp8_rounding_is_coarse_and_float32_is_not():
    x = jnp.linspace(-1.0, 1.0, 101)
    assert float(jnp.abs(reference._fp8(x) - x).max()) > 1e-3
    with pytest.raises(ValueError, match="unknown precision"):
        reference.forward({}, np.zeros((1, 2), np.int32), 1, precision="int4")


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 9])
def test_weights_come_from_the_seed(decoder, seed):
    a = make_params(decoder._params, seed)
    b = make_params(decoder._params, seed)
    c = make_params(decoder._params, seed + 1)
    same = jax.tree_util.tree_map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree_util.tree_leaves(same))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))
    assert a["embed"].dtype == jnp.bfloat16
    assert float(jnp.std(a["embed"].astype(jnp.float32))) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(a["layers"][0]["qkv"].astype(jnp.float32))) == pytest.approx(
        CONFIG["n_embd"] ** -0.5, rel=0.1)


@pytest.mark.parametrize("init_scale, want", [
    (None, {"embed": 0.02, "qkv": CONFIG["n_embd"] ** -0.5}),
    (lambda path, leaf: None, {"embed": 0.02, "qkv": CONFIG["n_embd"] ** -0.5}),
    (lambda path, leaf: 0.05 if path[-1] == "qkv" else None,
     {"embed": 0.02, "qkv": 0.05}),
    (lambda path, leaf: (1.0, 0.01) if path == ("embed",) else 0.1,
     {"embed": 0.01, "embed_mean": 1.0, "qkv": 0.1}),
], ids=["no_family_rule", "family_says_nothing", "one_leaf", "mean_and_deviation"])
def test_a_leaf_is_drawn_as_the_family_says_and_as_before_where_it_says_nothing(
        decoder, init_scale, want):
    drawn = make_params(decoder._params, 7, init_scale)
    embed = drawn["embed"].astype(jnp.float32)
    qkv = drawn["layers"][1]["qkv"].astype(jnp.float32)
    assert float(jnp.std(embed)) == pytest.approx(want["embed"], rel=0.1)
    assert float(jnp.mean(embed)) == pytest.approx(want.get("embed_mean", 0.0), abs=0.01)
    assert float(jnp.std(qkv)) == pytest.approx(want["qkv"], rel=0.1)
    if init_scale is not None and want == {"embed": 0.02, "qkv": CONFIG["n_embd"] ** -0.5}:
        # a family that says nothing gets the very weights of one that has no rule
        plain = make_params(decoder._params, 7)
        assert bool(jnp.array_equal(drawn["layers"][1]["qkv"], plain["layers"][1]["qkv"]))
    seen = []
    make_params(decoder._params, 7, lambda path, leaf: seen.append(path))
    assert ("embed",) in seen and ("layers", "0", "qkv") in seen


def test_the_reference_reads_its_sizes_from_the_configuration(decoder):
    rows = sessions(decoder)[:1]
    read = reference.served_token_gaps(decoder._params, CONFIG, rows, length=40)
    other = reference.served_token_gaps(
        decoder._params, dict(CONFIG, n_head=2), rows, length=40)
    assert other["served_gap_max"] > 10 * max(read["served_gap_max"], 0.001)


def test_a_family_module_that_lacks_part_of_the_contract_is_named(tmp_path, monkeypatch):
    (tmp_path / "half_arithmetic.py").write_text("def vocab(config):\n    return 3\n")
    (tmp_path / "half_reference.py").write_text(
        "def served_token_gaps(params, config, sessions, length):\n    return {}\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    config = {"arithmetic": "half_arithmetic", "reference": "half_reference"}
    with pytest.raises(AttributeError, match="half_arithmetic.*max_len, work"):
        family.arithmetic(config)
    assert family.reference(config).__name__ == "half_reference"
    with pytest.raises(AttributeError, match="half_reference.*control"):
        family.reference(config, control=True)
    assert family.reference({}) is reference
    assert family.reference({}, control=True) is reference


def test_the_batcher_serves_the_same_decoder_at_the_configured_sizes():
    model, decoder = builders.decoder_lm_batched(CONFIG, seed=0, slots=2)
    assert model._decoder is decoder and decoder.VOCAB == CONFIG["vocab_size"]
    assert (decoder.LAYERS, decoder.D_MODEL, decoder.HEADS, decoder.MAX_LEN) == (3, 64, 4, 48)
    assert model.outputs()[0].shape == [1, CONFIG["vocab_size"]]
    with pytest.raises(KeyError):
        builders.resolve("no_such_builder")


def test_the_program_draws_no_weights_on_the_host(decoder):
    # the build got zeros made on the device, of the program's own shapes
    # and type, for the benchmark's weights to take the place of
    built = builders.build_decoder(CONFIG, seed=5)
    assert built._params["embed"].dtype == jnp.bfloat16
    assert built._params["embed"].shape == (CONFIG["vocab_size"], CONFIG["n_embd"])
    assert not bool(jnp.any(built._params["layers"][0]["qkv"]))
    shapes_of = lambda tree: jax.tree_util.tree_map(lambda x: x.shape, tree)
    assert shapes_of(built._params) == shapes_of(decoder._params)

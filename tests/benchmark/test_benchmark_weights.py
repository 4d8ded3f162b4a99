"""The weights of a seed: the same arrays as before for every group of at
most 2 GiB, drawn in runs above that, from shapes alone, and one copy of them
on the device."""

import hashlib
import json
import os
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import benchmark_fixture
from benchmark import builders, run, server
from benchmark.server import make_params

# sha256 over the leaves as they flatten, from the code of PR 27 (the parent
# of the PR that cut the draw into runs), on the CPU, for the fixture
# configuration's decoder
PARENT_DIGESTS = {
    11: "b630582718a8206024edeafc30c877816ef70bf42d56f0d09f0f4606eb8421c7",
    2**31 + 12: "9b12040684e4a0805c56a383bbdbb9b387bb1486159c52cb860ca02f29df5269"}


def digest(leaves):
    h = hashlib.sha256()
    for leaf in leaves:
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    return h.hexdigest()


def shapes_of_the_program(config):
    """The decoder's weights at a configuration's sizes, as shapes: built
    under ``eval_shape``, so nothing is allocated."""
    return jax.eval_shape(lambda: builders.build_decoder(config, 0)._params)


@pytest.fixture(scope="module")
def template():
    return shapes_of_the_program(benchmark_fixture.TINY_CONFIG)


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_a_seeds_weights_are_the_parents_bit_for_bit(template, seed):
    assert digest(jax.tree_util.tree_leaves(make_params(template, seed))) \
        == PARENT_DIGESTS[seed]


def test_a_template_of_shapes_draws_what_a_template_of_arrays_draws(template):
    arrays = builders.build_decoder(benchmark_fixture.TINY_CONFIG, 0)._params
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree_util.tree_leaves(template))
    assert all(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(arrays))
    seed = 2**31 + 12
    assert digest(jax.tree_util.tree_leaves(make_params(arrays, seed))) \
        == PARENT_DIGESTS[seed]
    assert server.shapes_of(arrays) == server.shapes_of(template) == template


@pytest.mark.parametrize("name", ["gpt2-large", "cerebras-gpt-1.3b"])
def test_no_group_of_a_present_configuration_is_cut_into_runs(name):
    """Every group of both configurations is under 2 GiB and so drawn in the
    one call, under the group's own key, as before: the same arrays."""
    with open(os.path.join(benchmark_fixture.REPO, "benchmark", "configs",
                           name + ".json")) as f:
        groups = server.weight_groups(shapes_of_the_program(json.load(f)))
    blocks = [server.block_nbytes(g, len(m)) for g, m in groups.items()]
    assert max(blocks) <= server.BLOCK_BYTES == 2 << 30
    if name == "cerebras-gpt-1.3b":  # the largest there is: 24 ``mlp_in``
        assert max(blocks) == 24 * 2048 * 8192 * 4


EXPERTS = {"embed": jax.ShapeDtypeStruct((40, 16), jnp.bfloat16), "layers": [
    {"router": jax.ShapeDtypeStruct((16, 4), jnp.bfloat16),
     "experts_in": jax.ShapeDtypeStruct((4, 16, 32), jnp.bfloat16),
     "experts_out": jax.ShapeDtypeStruct((4, 32, 16), jnp.bfloat16)}
    for _ in range(5)]}


def expert_scale(path, leaf):
    return leaf.shape[1] ** -0.5 if path[-1].startswith("experts_") else None


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_a_group_over_the_threshold_is_drawn_in_runs(seed):
    """At 2 KiB a block: the table (2.5 KB, a group of one) and both groups
    of stacked experts (five leaves of 8 KB) are over it, the routers' group
    (five of 256 B) is under it."""
    whole = make_params(EXPERTS, seed, expert_scale)
    cut = make_params(EXPERTS, seed, expert_scale, block_bytes=2048)
    # a group under the threshold: the same leaves as at the default
    for a, b in zip(whole["layers"], cut["layers"]):
        assert bool(jnp.array_equal(a["router"], b["router"]))
        assert not bool(jnp.array_equal(a["experts_in"], b["experts_in"]))
    leaves = jax.tree_util.tree_leaves(cut)
    assert [leaf.shape for leaf in leaves] == [
        leaf.shape for leaf in jax.tree_util.tree_leaves(EXPERTS)]
    # every leaf of the stated deviation, and no two leaves equal
    deviation = lambda x: float(jnp.std(x.astype(jnp.float32)))
    assert deviation(cut["embed"]) == pytest.approx(0.02, rel=0.1)
    for layer in cut["layers"]:
        assert layer["experts_in"].dtype == jnp.bfloat16
        assert deviation(layer["experts_in"]) == pytest.approx(16 ** -0.5, rel=0.1)
        assert deviation(layer["experts_out"]) == pytest.approx(32 ** -0.5, rel=0.1)
        assert deviation(layer["experts_in"][0]) == pytest.approx(16 ** -0.5, rel=0.15)
    routers = jnp.stack([layer["router"] for layer in cut["layers"]])
    assert deviation(routers) == pytest.approx(16 ** -0.5, rel=0.1)
    digests = [digest([leaf]) for leaf in leaves]
    assert len(set(digests)) == len(digests)
    # the same seed, the same runs
    again = make_params(EXPERTS, seed, expert_scale, block_bytes=2048)
    assert digest(jax.tree_util.tree_leaves(again)) == digest(leaves)


def test_runs_hold_as_many_members_as_fit_and_one_at_least(monkeypatch):
    drawn = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda out: drawn.append(len(out)) or real(out))
    make_params(EXPERTS, 1, expert_scale, block_bytes=3 * 8192)
    assert drawn == [3, 2, 3, 2]  # five leaves of 8 KB a group, three a run
    del drawn[:]
    make_params(EXPERTS, 1, expert_scale, block_bytes=1)
    assert drawn == [1] * 16  # the table, five routers, ten stacks: one each


class Decoder:
    """What ``Served`` asks of a builder's decoder."""

    def __init__(self, params):
        self._params = params


def served_with(params):
    served = object.__new__(server.Served)
    served.decoder, served.init_scale = Decoder(params), None
    served.template, served.params = server.shapes_of(params), None
    return served


@pytest.mark.parametrize("handed_over", ["arrays", "shapes"])
def test_reseed_lets_go_of_the_former_weights_before_it_draws(
        template, monkeypatch, handed_over):
    """What the CPU shows: no live reference to a former leaf is left (a weak
    one is dead), and at the draw the decoder holds nothing; the draw reads
    shapes alone."""
    params = template if handed_over == "shapes" else jax.tree_util.tree_map(
        lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), template)
    served = served_with(params)
    handed = [weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(params)
              if isinstance(leaf, jax.Array)]
    del params
    seen = []

    def draw(template_, seed, init_scale=None):
        seen.append((served.decoder._params, served.params, [
            type(leaf) for leaf in jax.tree_util.tree_leaves(template_)]))
        return make_params(template_, seed, init_scale)

    monkeypatch.setattr(server, "make_params", draw)
    served.reseed(11)
    first = [weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(served.params)]
    assert served.decoder._params is served.params
    assert digest(jax.tree_util.tree_leaves(served.params)) == PARENT_DIGESTS[11]
    assert len(handed) == (11 if handed_over == "arrays" else 0)
    assert all(leaf() is None for leaf in handed)
    served.reseed(2**31 + 12)
    assert all(leaf() is None for leaf in first)
    assert digest(jax.tree_util.tree_leaves(served.decoder._params)) \
        == PARENT_DIGESTS[2**31 + 12]
    assert len(seen) == 2
    for held_by_decoder, held_by_served, kinds in seen:
        assert held_by_decoder is None and held_by_served is None
        assert set(kinds) == {jax.ShapeDtypeStruct}
    assert served.weight_operands() == {
        "bf16[300,64]": "weights", "bf16[64,64]": "weights",
        "bf16[64,192]": "weights", "bf16[64,256]": "weights",
        "bf16[256,64]": "weights", "bf16[64,300]": "weights"}


EXACT = {"sessions_failed": 0, "argmax_mismatch": 0, "compiles_in_window": 0}
ANSWER = {"ok": True, "positions": 40, "served_gap_max": 0.002,
          "near_tie_share": 0.125, "reference_s": 1.5}


@pytest.mark.parametrize("answer, limits, correct, at_fault", [
    (ANSWER, {"served_gap_max": 0.01, "near_tie_share": 0.25}, True, None),
    (ANSWER, {"served_gap_max": 0.01, "near_tie_share": 0.1}, False, "near_tie_share"),
    ({k: v for k, v in ANSWER.items() if k != "near_tie_share"},
     {"served_gap_max": 0.01, "near_tie_share": 0.25}, False, "near_tie_share"),
    (dict(ANSWER, served_gap_max=0.02),
     {"served_gap_max": 0.01, "near_tie_share": 0.25}, False, "served_gap_max"),
    (ANSWER, {"served_gap_max": 0.01}, True, None),
], ids=["both_within", "the_second_over_its_limit", "the_second_not_read",
        "the_gap_over_its_limit", "no_limit_named_for_it"])
def test_a_reading_of_the_familys_own_is_held_by_its_limit(
        answer, limits, correct, at_fault):
    readings = run.own_readings(answer)
    assert "ok" not in readings and readings["positions"] == 40
    compared, verdict = run.judge({**readings, **EXACT}, limits)
    assert verdict is correct
    assert set(compared) == set(EXACT) | set(limits)
    for name, limit in limits.items():
        assert compared[name] == {"value": answer.get(name), "limit": limit}
    if at_fault:
        value, limit = compared[at_fault]["value"], compared[at_fault]["limit"]
        assert value is None or value > limit

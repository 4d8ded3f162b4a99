"""CI tier for tools/top_rung_read_chip.py: the micro-benchmark that decided
the form of the slot batcher's read at its top rung, and of the stream round's
at a shorter rung, must run end to end on the CPU backend (tables of four and
eight slots), so that a chip call never dies on its argument handling, and its
own checks of the forms against the parent's must be able to fail."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import top_rung_read_chip  # noqa: E402


def test_a_small_run_times_every_form():
    out = top_rung_read_chip.run(small=True, repeats=2)
    assert out["platform"] == "cpu"
    assert out["agreement"]["ok"], out["agreement"]
    rows = out["forms"]
    assert [r["form"] for r in rows] == [
        *top_rung_read_chip.BASELINES, *top_rung_read_chip.FORMS]
    assert all(r["ms_a_dispatch"] > 0 for r in rows), rows
    assert all(r["read_gb_s"] >= 0 for r in rows[2:]), rows
    assert not any("read_gb_s" in r for r in rows[:2])


def test_a_small_run_times_every_round_form_at_each_membership():
    out = top_rung_read_chip.run(small=True, repeats=2)
    assert out["round_agreement"]["ok"], out["round_agreement"]
    (shape, _, live, memberships), = top_rung_read_chip.SMALL_ROUND_TABLES.values()
    assert [case["members"] for case in out["round_agreement"]["cases"]] == list(
        memberships)
    rows = out["round_forms"]
    baselines = top_rung_read_chip.BASELINES
    forms = [*baselines, *top_rung_read_chip.ROUND_FORMS]
    assert [(r["members"], r["form"]) for r in rows] == [
        (members, form) for members in memberships for form in forms]
    assert all(r["ms_a_dispatch"] > 0 and r["live"] == live for r in rows), rows
    # eight slots, four a turn: three members are one turn, eight are two
    read = {(r["members"], r["form"]): r.get("slots_read") for r in rows}
    assert read == {
        (members, form): (None if form in baselines else
                          shape[0] if form.endswith("every_slot") or members > 4
                          else 4)
        for members in memberships for form in forms}


def test_the_packed_forms_read_the_table_as_the_decoder_lays_it():
    """A packed form reads (and its rows and weights write) the table laid
    ``heads_a_row`` heads a row, as ``_fresh_table`` lays it, and is held to
    the parent's attention, read a head a row; only the baselines the chosen
    forms' layouts need are timed."""
    from client_tpu.models.decoder import heads_a_row

    out = top_rung_read_chip.run(
        small=True, repeats=1, chosen=("packed_two_turns",),
        round_chosen=("every_slot", "packed_every_slot"))
    assert out["agreement"]["ok"] and out["round_agreement"]["ok"], out
    (case,) = out["agreement"]["cases"]
    assert set(case) == {"table", "shape", "packed_two_turns"}
    assert [r["form"] for r in out["forms"]] == [
        "packed_rows_and_weights", "packed_two_turns"]
    (slots, heads, length, dim), _, _ = top_rung_read_chip.SMALL_TABLES[
        "small"]
    P = heads_a_row(heads, dim)
    assert P > 1
    assert {tuple(r["shape"]) for r in out["forms"]} == {
        (slots, heads // P, length, P * dim)}
    round_rows = out["round_forms"]
    assert {r["form"] for r in round_rows} == {
        *top_rung_read_chip.BASELINES, "every_slot", "packed_every_slot"}
    assert all(r["shape"][-1] == (P * dim if "packed" in r["form"] else dim)
               for r in round_rows), round_rows
    assert all("read_ms" in r for r in round_rows
               if r["form"] not in top_rung_read_chip.BASELINES), round_rows


@pytest.mark.parametrize("broken", top_rung_read_chip.ROUND_FORMS[1:])
def test_a_round_form_that_reads_past_its_position_fails_the_run(
        monkeypatch, capsys, broken):
    """The round's forms are held to the parent's round, over the members:
    one that lets every slot see one position more is no candidate, at every
    number of members."""
    round_forms = top_rung_read_chip.round_forms

    def with_a_fault(jax, jnp, lax, live, head=None):
        made = round_forms(jax, jnp, lax, live, head)
        sound = made[broken]
        made[broken] = lambda q, k, v, pos, active: sound(q, k, v, pos + 1, active)
        return made

    monkeypatch.setattr(top_rung_read_chip, "round_forms", with_a_fault)
    assert top_rung_read_chip.main(["--small", "--repeats", "1"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["agreement"]["ok"]
    for case in out["round_agreement"]["cases"]:
        assert [form for form in top_rung_read_chip.ROUND_FORMS[1:]
                if not case[form]["agrees"]] == [broken]


def test_main_prints_what_it_writes_and_exits_0(tmp_path, capsys):
    path = tmp_path / "top_rung_read.json"
    rc = top_rung_read_chip.main(
        ["--small", "--repeats", "1", "--json-out", str(path)])
    assert rc == 0
    assert json.loads(path.read_text()) == json.loads(capsys.readouterr().out)


@pytest.mark.parametrize("argv", [["--repeats", "many"], ["--large"],
                                  ["--forms", "every_slot"],
                                  ["--round-forms", "two_turns"]])
def test_main_refuses_what_it_does_not_know(argv, capsys):
    with pytest.raises(SystemExit) as refused:
        top_rung_read_chip.main(argv)
    assert refused.value.code == 2
    capsys.readouterr()


@pytest.mark.parametrize("broken", top_rung_read_chip.FORMS[1:])
def test_a_form_that_reads_past_its_position_fails_the_run(
        monkeypatch, capsys, broken):
    """The tool's exit code is its agreement check: a form whose attention
    is not the parent's (here, one that lets every slot see one position
    more) is no candidate."""
    forms = top_rung_read_chip.forms

    def with_a_fault(jax, jnp, lax, piece, head=None):
        made = forms(jax, jnp, lax, piece, head)
        sound = made[broken]
        made[broken] = lambda q, k, v, pos: sound(q, k, v, pos + 1)
        return made

    monkeypatch.setattr(top_rung_read_chip, "forms", with_a_fault)
    assert top_rung_read_chip.main(["--small", "--repeats", "1"]) == 1
    (case,) = json.loads(capsys.readouterr().out)["agreement"]["cases"]
    assert [form for form in top_rung_read_chip.FORMS[1:]
            if not case[form]["agrees"]] == [broken]

"""CI tier for tools/row_write_chip.py: the micro-benchmark that decided the
form of a round's row writes must run end to end on the CPU backend (a table
of four slots), so that a chip call never dies on its argument handling, and
its own check of the forms against each other must be able to fail."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import row_write_chip  # noqa: E402


def test_a_small_run_times_every_form_at_every_width():
    out = row_write_chip.run(small=True, repeats=2)
    assert out["platform"] == "cpu"
    assert out["agreement"]["ok"], out["agreement"]
    rows = out["forms"]
    assert [(r["form"], r["active"]) for r in rows] == [
        (form, width) for form in row_write_chip.FORMS for width in (1, 4)]
    assert all(r["ms_a_dispatch"] > 0 and r["us_a_layer"] > 0 for r in rows), rows


def test_main_prints_what_it_writes_and_exits_0(tmp_path, capsys):
    path = tmp_path / "row_write.json"
    rc = row_write_chip.main(
        ["--small", "--repeats", "1", "--json-out", str(path)])
    assert rc == 0
    assert json.loads(path.read_text()) == json.loads(capsys.readouterr().out)


def test_the_packed_form_writes_the_table_as_the_decoder_lays_it():
    """``packed`` times ``loop`` over the table laid ``heads_a_row`` heads a
    row, as ``_fresh_table`` lays it, and is checked a head a row."""
    from client_tpu.models.decoder import heads_a_row

    out = row_write_chip.run(small=True, repeats=1,
                             chosen=("loop_window", "packed"))
    assert out["agreement"]["ok"], out["agreement"]
    (case,) = out["agreement"]["cases"]
    assert set(case) == {"table", "shape", "loop_window", "packed"}
    (slots, heads, length, dim), _ = row_write_chip.SMALL_TABLES["small"]
    P = heads_a_row(heads, dim)
    assert P > 1
    shapes = {(r["form"], tuple(r["shape"])) for r in out["forms"]}
    assert shapes == {("loop_window", (slots, heads, length, dim)),
                      ("packed", (slots, heads // P, length, P * dim))}


def test_a_small_run_of_the_kernel_writes_the_loops_table(capsys):
    """``kernel``, ops/row_write.py in interpret mode off the chip, over the
    table as the decoder lays it, is checked bit for bit against ``loop``
    read a head a row."""
    from client_tpu.models.decoder import heads_a_row

    assert row_write_chip.main(
        ["--small", "--repeats", "1", "--forms", "kernel"]) == 0
    out = json.loads(capsys.readouterr().out)
    (case,) = out["agreement"]["cases"]
    assert case["kernel"] is True
    (slots, heads, length, dim), _ = row_write_chip.SMALL_TABLES["small"]
    P = heads_a_row(heads, dim)
    assert [(r["form"], r["active"], tuple(r["shape"])) for r in out["forms"]] == [
        ("kernel", width, (slots, heads // P, length, P * dim))
        for width in (1, 4)]


def test_main_times_the_forms_it_is_given(capsys):
    assert row_write_chip.main(
        ["--small", "--repeats", "1", "--forms", "packed"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {r["form"] for r in out["forms"]} == {"packed"}
    assert set(out["agreement"]["cases"][0]) == {"table", "shape", "packed"}


@pytest.mark.parametrize("argv", [["--repeats", "many"], ["--large"],
                                  ["--forms", "loop,elsewhere"]])
def test_main_refuses_what_it_does_not_know(argv, capsys):
    with pytest.raises(SystemExit) as refused:
        row_write_chip.main(argv)
    assert refused.value.code == 2
    capsys.readouterr()


@pytest.mark.parametrize("broken", row_write_chip.FORMS[1:])
def test_a_form_that_writes_a_row_a_step_could_read_fails_the_run(
        monkeypatch, capsys, broken):
    """The tool's exit code is its agreement check: a form that leaves another
    table than the loop's, where a step could read it, is no candidate."""
    forms = row_write_chip.forms

    def with_a_fault(jnp, lax):
        made = forms(jnp, lax)
        sound = made[broken]
        # every slot's row lands one position late
        made[broken] = lambda caches, rows, pos, active: sound(
            caches, rows, pos + 1, active)
        return made

    monkeypatch.setattr(row_write_chip, "forms", with_a_fault)
    assert row_write_chip.main(["--small", "--repeats", "1"]) == 1
    (case,) = json.loads(capsys.readouterr().out)["agreement"]["cases"]
    assert [form for form in row_write_chip.FORMS[1:] if not case[form]] == [
        broken]

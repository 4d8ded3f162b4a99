"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusals.

The chip run itself happens through the chip tool (``python chip_smoke.py``);
here the same phase functions run against a server built the same way, so a
wrong path, argument or control flow is found without chip time. Nothing
here is a device measurement.
"""

import json
import os
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from client_tpu import compile_cache  # noqa: E402

CACHE = chip_smoke.CompileCache("unit-test-cache")

TINY = chip_smoke.Sizes(
    vision_arch="lite", vision_width=8, vision_classes=16,
    vision_concurrency=2, identity_bytes=(4096, 1 << 16), xproc_bytes=4096,
    lm_prompt=3, lm_new_tokens=3, lm_sequences=2,
    long_context_seqs=(256, 72), sharded_seq=256,
    kernel_marker=None,  # Pallas interprets on the CPU: no Mosaic call
)


@pytest.fixture(scope="module")
def served():
    with chip_smoke.Served(chip_smoke.one_chip_models(TINY)) as s:
        yield s


def _phase_line(capsys, name):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    (line,) = [l for l in lines if l.get("phase") == name]
    assert line["ok"] is True
    assert line["compile_cache"]["dir"] == "unit-test-cache"
    assert "smoke_wall_s" in line and "smoke_first_compile_s" in line
    return line


@pytest.mark.parametrize("phase", [
    "protocol", "vision", "cross_process", "language_model", "long_context"])
def test_phase_on_cpu(served, capsys, phase):
    getattr(chip_smoke, f"phase_{phase}")(served, TINY, CACHE)
    _phase_line(capsys, phase)


def test_data_plane_phase_on_cpu(served, capsys):
    chip_smoke.phase_data_plane(served, TINY, CACHE, "cpu")
    line = _phase_line(capsys, "data_plane")
    assert line["colocated_host_copy_bytes"] == 0


def test_data_plane_phase_names_the_wrong_platform(served):
    """The colocated arm checks where the result lives, not that it came."""
    with pytest.raises(chip_smoke.SmokeFailure, match="lives on"):
        chip_smoke.phase_data_plane(served, TINY, CACHE, "tpu")


def test_sharded_phase_on_four_virtual_devices(capsys):
    assert len(jax.devices()) >= 4
    chip_smoke.phase_sharded(TINY, CACHE, 4)
    out = capsys.readouterr().out
    decoder, ring = [json.loads(l) for l in out.splitlines()
                     if l.startswith("{")]
    assert decoder["phase"] == "sharded_decoder"
    assert decoder["param_devices"] == decoder["cache_devices"] == 4
    assert decoder["max_abs_logit_diff"] <= chip_smoke.LM_LOGIT_TOL
    assert ring["phase"] == "sharded_long_context"
    assert ring["input_devices"] == 4


def test_a_decoder_off_its_reference_fails():
    """Logits beyond the tolerance fail; a parted token passes only at a
    near-tie of the reference."""
    import numpy as np

    ref_logits = np.zeros((2, 4), np.float32)
    ref_logits[:, 0] = 1.0  # token 0 leads by a wide margin
    want = [([0, 0], ref_logits)]
    near = ref_logits + chip_smoke.LM_LOGIT_TOL / 2
    held = chip_smoke._held_to_reference("m", [([0, 0], near)], want)
    assert held["greedy_tokens_parted"] == 0 and not held["logits_bit_equal"]
    with pytest.raises(chip_smoke.SmokeFailure, match="logits off"):
        chip_smoke._held_to_reference(
            "m", [([0, 0], ref_logits + 1.0)], want)
    with pytest.raises(chip_smoke.SmokeFailure, match="margin"):
        chip_smoke._held_to_reference("m", [([0, 3], near)], want)


def test_a_failed_check_prints_no_phase_line(capsys):
    with pytest.raises(chip_smoke.SmokeFailure):
        with chip_smoke.Phase("doomed", CACHE):
            chip_smoke.check(False, "no")
    assert "doomed" not in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_the_cpu(capsys, argv):
    """No chip: ``"ok": false`` at once, non-zero, and no phase ran."""
    assert chip_smoke.main(argv) == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


def test_more_devices_than_exist_raise():
    from client_tpu.models.decoder_tp import TPDecoderModel
    from client_tpu.models.vision import DenseNetModel

    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="only"):
        TPDecoderModel(tp=too_many)._ensure_built()
    with pytest.raises(ValueError, match="only"):
        DenseNetModel(num_classes=16, width=8,
                      tensor_parallel=too_many)._ensure_built()


def test_auto_tp_refuses_a_one_device_host(monkeypatch):
    """decoder_lm_tp must not serve tp=1 under the tp name."""
    from client_tpu.models.decoder_tp import TPDecoderModel

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(ValueError, match="at least 2 devices"):
        TPDecoderModel()._ensure_mesh()


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing is set in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache") == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored

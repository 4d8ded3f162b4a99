"""CI tier for tools/chip_bench.py: the measurement harness itself must
work on the CPU backend (tiny shapes) so chip-day runs never die on a
harness bug. The single-dispatch chaining protocol is also pinned here —
per-dispatch timing measures dispatch overhead, not the device."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import chip_bench  # noqa: E402


def test_matmul_bench_small():
    out = chip_bench.bench_matmul(jax, jnp, np, n=128, chain=3, pipeline=2)
    assert out["tflops"] > 0
    assert out["ms_per_matmul_blocked"] > 0
    assert out["ms_per_matmul_pipelined"] > 0


def test_dispatch_overhead_small():
    ms = chip_bench.bench_dispatch_overhead(jax, jnp, np, repeats=3)
    assert ms >= 0


def test_flash_attention_bench_small():
    out = chip_bench.bench_flash_attention(
        jax, jnp, np, batch=1, seq=128, heads=2, dim=64, steps=2
    )
    assert out["tflops"] > 0


def test_densenet_bench_small():
    out = chip_bench.bench_densenet(
        jax, jnp, np, width=8, arch="lite", steps=2, batch=1
    )
    assert out["images_per_sec"] > 0
    # XLA cost analysis must see real conv work, not an empty graph
    assert out["gflops_per_image"] > 0.01


def test_generate_bench_small():
    out = chip_bench.bench_generate(jax, jnp, np, prompt=4, k=4)
    assert out["chunk"] == 4
    assert out["ms_per_token_dispatch"] > 0
    assert out["ms_per_token_chunked"] > 0
    assert out["tokens_per_sec_chunked"] > 0
    assert out["chunk_amortization"] > 0


def test_peak_lookup():
    assert chip_bench._peak_for("TPU v5 lite") == 197.0
    assert chip_bench._peak_for("TPU v5") == 459.0
    assert chip_bench._peak_for("TPU v5p chip") == 459.0
    # a device that is not in the table is an error, not a default
    for kind in ("unknown accelerator", "cpu"):
        with pytest.raises(ValueError, match="no bf16 peak"):
            chip_bench._peak_for(kind)


@pytest.mark.parametrize("kind,expected", [("TPU v6 lite", 918.0), ("TPU v4", 275.0)])
def test_peak_generations(kind, expected):
    assert chip_bench._peak_for(kind) == expected

"""REAL multi-process distributed tests: separate OS processes form one
global mesh over the Gloo/TCP transport (the CPU stand-in for DCN) and the
results are asserted against single-process math.

This is the multi-host claim made executable — not a virtual-device
simulation: each worker is its own interpreter with its own PJRT client,
jax.distributed handshake, and cross-process collectives
(`client_tpu/parallel/multihost.py`)."""

import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); nprocs = int(sys.argv[2]); coord = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {repo!r})
from client_tpu.parallel import multihost

multihost.initialize(coord, nprocs, proc_id)

import numpy as np
import jax
import jax.numpy as jnp
from functools import partial
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

assert jax.process_count() == nprocs, jax.process_count()
assert len(jax.devices()) == 4 * nprocs

mesh = multihost.global_mesh(("data", "model"))
assert mesh.devices.shape == (nprocs, 4)

# 1) cross-process psum over both axes
@partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P())
def allsum(v):
    return jax.lax.psum(v, ("data", "model")) / (4.0 * nprocs)

x = jnp.arange(8.0)
np.testing.assert_allclose(np.asarray(jax.jit(allsum)(x)), np.arange(8.0),
                           rtol=1e-6)

# 2) dp-sharded global array: each process contributes its local rows, the
#    jitted global sum must equal the full-batch sum
assert multihost.process_local_batch(8 * nprocs) == 8


def place(arr, sharding_, slice_of_device):
    # each process device_puts only its own devices' shards; the global
    # array is then assembled from the local pieces
    pieces = []
    for pos, d in np.ndenumerate(sharding_.mesh.devices):
        if d.process_index == jax.process_index():
            pieces.append(jax.device_put(arr[slice_of_device(pos)], d))
    return jax.make_array_from_single_device_arrays(
        arr.shape, sharding_, pieces)


global_shape = (8 * nprocs, 16)
sharding = NamedSharding(mesh, P("data", None))
local = np.arange(np.prod(global_shape), dtype=np.float32).reshape(global_shape)
# rows shard over the data axis and REPLICATE over model: device at mesh
# position (di, mi) holds data-group di's rows
per_group = global_shape[0] // nprocs
row_slice = lambda pos: np.s_[pos[0] * per_group:(pos[0] + 1) * per_group]
garr = place(local, sharding, row_slice)

total = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(garr)
np.testing.assert_allclose(float(total), float(local.sum()), rtol=1e-5)

# 3) data-parallel train step across processes: per-shard grads reduce
#    over DCN (the Gloo stand-in); the updated weights must equal the
#    single-process full-batch step on every host
rng = np.random.default_rng(0)
w0 = rng.standard_normal((16, 4)).astype(np.float32)
targets = rng.standard_normal((global_shape[0], 4)).astype(np.float32)
lr = 0.1

def loss_fn(w, xb, yb):
    return jnp.mean((xb @ w - yb) ** 2)

@partial(jax.jit,
         in_shardings=(NamedSharding(mesh, P()), sharding,
                       NamedSharding(mesh, P("data", None))),
         out_shardings=NamedSharding(mesh, P()))
def train_step(w, xb, yb):
    return w - lr * jax.grad(loss_fn)(w, xb, yb)

gy = place(targets, NamedSharding(mesh, P("data", None)), row_slice)
w1 = train_step(jnp.asarray(w0), garr, gy)

# reference: plain numpy full-batch gradient
pred = local @ w0
grad = 2.0 * local.T @ (pred - targets) / (global_shape[0] * 4)
np.testing.assert_allclose(np.asarray(w1), w0 - lr * grad, rtol=2e-4)

# 4) ring attention with the sequence sharded ACROSS PROCESSES: K/V blocks
#    rotate host-to-host over ppermute (Gloo here, ICI/DCN on pods);
#    every local shard must match the dense single-host reference
from jax.sharding import Mesh
from client_tpu.parallel import ring

seq_mesh = Mesh(mesh.devices.reshape(-1), ("seq",))
B, S, H, D = 1, 8 * nprocs * 4, 2, 8
rng2 = np.random.default_rng(7)
qn = rng2.standard_normal((B, S, H, D)).astype(np.float32)
kn = rng2.standard_normal((B, S, H, D)).astype(np.float32)
vn = rng2.standard_normal((B, S, H, D)).astype(np.float32)
seq_shard = NamedSharding(seq_mesh, P(None, "seq", None, None))
per_seq = S // (4 * nprocs)
seq_slice = lambda pos: np.s_[:, pos[0] * per_seq:(pos[0] + 1) * per_seq]

def shard_seq(arr):
    return place(arr, seq_shard, seq_slice)

qg, kg, vg = shard_seq(qn), shard_seq(kn), shard_seq(vn)
out = ring.ring_attention(qg, kg, vg, seq_mesh, axis="seq")
ref = np.asarray(ring.full_attention(jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn)))
for shard in out.addressable_shards:
    lo = shard.index[1].start or 0
    hi = shard.index[1].stop or S
    np.testing.assert_allclose(
        np.asarray(shard.data), ref[:, lo:hi], rtol=2e-4, atol=2e-5)

# 5) Ulysses: the all_to_all head<->sequence repartition also crosses the
#    process boundary (heads divide over all 8 devices)
from client_tpu.parallel import ulysses

B2, S2, H2, D2 = 1, 8 * nprocs * 4, 4 * nprocs, 8
qn2 = rng2.standard_normal((B2, S2, H2, D2)).astype(np.float32)
kn2 = rng2.standard_normal((B2, S2, H2, D2)).astype(np.float32)
vn2 = rng2.standard_normal((B2, S2, H2, D2)).astype(np.float32)
qg2, kg2, vg2 = shard_seq(qn2), shard_seq(kn2), shard_seq(vn2)
out2 = ulysses.ulysses_attention(qg2, kg2, vg2, seq_mesh, axis="seq")
ref2 = np.asarray(ring.full_attention(
    jnp.asarray(qn2), jnp.asarray(kn2), jnp.asarray(vn2)))
for shard in out2.addressable_shards:
    lo = shard.index[1].start or 0
    hi = shard.index[1].stop or S2
    np.testing.assert_allclose(
        np.asarray(shard.data), ref2[:, lo:hi], rtol=2e-4, atol=2e-5)

print(f"WORKER_OK {proc_id}", flush=True)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("nprocs", [2])
def test_two_process_global_mesh(tmp_path, nprocs):
    script = tmp_path / "worker.py"
    script.write_text(WORKER.replace("{repo!r}", repr(str(REPO))))
    coord = f"127.0.0.1:{_free_port()}"
    # keep the parent environment (LD_LIBRARY_PATH etc. matter for jax in
    # conda-style installs); strip only the jax pins the worker sets for
    # itself
    import os

    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(nprocs), coord],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for i in range(nprocs)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        assert f"WORKER_OK {i}" in out

"""The one process that holds the chip: a cell's model behind the GRPC frontend.

Started by ``benchmark/run.py`` (which never imports jax), it builds the
cell's model from its configuration file, gives it weights made on the device
from the seed (of the shapes the builder handed over: the chip holds one copy
of them, ``benchmark/family.py``), serves it through ``ServerCore`` and
``GrpcInferenceServer`` on a loopback port, and then takes commands, one JSON
object a line on standard input, answering each with one line on standard
output:

- ``mark``: the counters as they stand (compiles so far, the batcher's
  histogram, every series of the program's own metrics registry that is
  labelled with the served model), so that the window's share can be told
  from the warm-up's;
- ``trace_start`` / ``trace_stop``: the profiler, for a few seconds of the
  window of a traced run;
- ``finish``: close the frontend, read the counters and the chip's peak
  memory, free the model and its caches, reduce the trace;
- ``check``: run the configuration's own plain reference
  (``benchmark/family.py``) over the sample of sessions it is sent (the
  weights are the benchmark's own arrays, made here from the seed);
- ``reseed``: new weights from another seed, the old ones let go of first
  (``calibrate.py`` reads a dozen seeds in one process);
- ``exit``.

The host spans of a traced run are put round the model object's ``execute``
and ``execute_decoupled`` from outside; spans inside the program are a later
issue's.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TABLES = ("embed", "pos", "unembed")  # drawn at 0.02; matrices at fan_in ** -0.5
HLO_TYPES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
             "float64": "f64", "int8": "s8", "int32": "s32", "uint8": "u8"}


def emit(obj: Dict[str, Any]) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


BLOCK_BYTES = 2 << 30  # the largest float32 block one call of the draw makes


def weight_groups(template, init_scale=None) -> Dict[Any, List[int]]:
    """The template's leaves, numbered as they flatten, by ``(shape, dtype,
    mean, deviation)``: the leaves of one group are drawn as one block."""
    import jax

    groups: Dict[Any, List[int]] = {}
    paths, _ = jax.tree_util.tree_flatten_with_path(template)
    for i, (path, leaf) in enumerate(paths):
        keys = tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        drawn = init_scale(keys, leaf) if init_scale is not None else None
        if drawn is None:
            drawn = 0.02 if keys[0] in TABLES else leaf.shape[0] ** -0.5
        mean, scale = drawn if isinstance(drawn, tuple) else (0.0, drawn)
        groups.setdefault((tuple(leaf.shape), str(leaf.dtype), float(mean),
                           float(scale)), []).append(i)
    return groups


def block_nbytes(group, members: int = 1) -> int:
    """The float32 block of ``members`` leaves of a group."""
    return 4 * math.prod(group[0]) * members


def make_params(template, seed: int, init_scale=None,
                block_bytes: int = BLOCK_BYTES):
    """Weights of the template's shapes and types, drawn on the device from
    the seed: normal, at the deviation (or ``(mean, deviation)``) the family's
    ``init_scale(path, leaf)`` gives a leaf, ``path`` being its keys as
    strings; where it gives ``None``, or there is none, scaled as the program
    scales its own (0.02 for the tables and the output head,
    ``shape[0] ** -0.5`` for the matrices).

    Of the template only each leaf's ``shape`` and ``dtype`` are read, here
    and by ``init_scale``: it may be a tree of ``jax.ShapeDtypeStruct``, and a
    tree of arrays draws the same.

    The leaves of one shape, type and deviation are a group, drawn as one
    float32 block under a key folded from the seed's by the group's number.
    The groups whose block is at most ``block_bytes`` (2 GiB) are drawn in
    one jitted call together. A larger group (stacked experts) is cut into
    runs of as many members as fit in ``block_bytes``, one at least, each run
    drawn by a call of its own under a key folded from the group's by the
    run's number, and waited for: what lives on the chip beside the finished
    leaves is then one run, its bits and its cast (the chip's compiler keeps
    no float32 block; ``peak_bytes_in_use`` counts neither, they are the
    program's temporaries)."""
    import jax
    import jax.numpy as jnp

    groups = weight_groups(template, init_scale)
    whole = [block_nbytes(group, len(members)) <= block_bytes
             for group, members in groups.items()]

    def block(key, n, shape, dtype, mean, scale):
        drawn = jax.random.normal(key, (n,) + shape, jnp.float32) * scale
        if mean:
            drawn = drawn + mean
        return drawn.astype(dtype)

    @jax.jit
    def draw(key):
        out = {}
        for g, (group, members) in enumerate(groups.items()):
            if whole[g]:
                drawn = block(jax.random.fold_in(key, g), len(members), *group)
                out.update((i, drawn[j]) for j, i in enumerate(members))
        return out

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw_run(key, n, group):
        drawn = block(key, n, *group)
        return [drawn[j] for j in range(n)]

    # a seed may be a little over 2**31: fold its high part in
    key = jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), (seed >> 31) & 0x7FFFFFFF)
    out = draw(key)
    for g, (group, members) in enumerate(groups.items()):
        if whole[g]:
            continue
        n = max(1, block_bytes // block_nbytes(group))
        for r, first in enumerate(range(0, len(members), n)):
            run = members[first:first + n]
            run_key = jax.random.fold_in(jax.random.fold_in(key, g), r)
            out.update(zip(run, jax.block_until_ready(
                draw_run(run_key, len(run), group))))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template), [out[i] for i in range(len(out))])


def shapes_of(tree):
    """The tree as a template: a ``jax.ShapeDtypeStruct`` for each leaf, be
    the leaf an array or already a shape."""
    import jax

    return jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tree)


def annotate(model) -> None:
    """Host spans round the model's entry points, from outside."""
    import jax

    name = model.name
    execute, decoupled = model.execute, model.execute_decoupled

    def traced_execute(inputs, parameters):
        with jax.profiler.TraceAnnotation(f"{name}.execute"):
            return execute(inputs, parameters)

    def traced_decoupled(inputs, parameters):
        responses = iter(decoupled(inputs, parameters))
        while True:
            with jax.profiler.TraceAnnotation(f"{name}.execute_decoupled"):
                try:
                    response = next(responses)
                except StopIteration:
                    return
            yield response

    model.execute = traced_execute
    if getattr(model, "decoupled", False):
        model.execute_decoupled = traced_decoupled


class Served:
    """The cell's model, its weights, the frontend and the counters."""

    def __init__(self, cell: Dict[str, Any], config: Dict[str, Any],
                 users: int, seed: int, work_dir: str):
        import jax

        from client_tpu.server import GrpcInferenceServer, ServerCore

        from benchmark import builders, family

        self.config = config
        self.init_scale = getattr(family.arithmetic(config), "init_scale", None)
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        t = time.perf_counter()
        self.model, self.decoder = builders.resolve(cell["builder"])(
            config, seed, **cell.get("args", {}))
        self.decoder._ensure_built()
        # the template is shapes, taken once from what the builder handed
        # over, arrays or shapes alike; no draw ever reads the old weights
        self.template = shapes_of(self.decoder._params)
        self.params = None
        t_built = time.perf_counter()
        self.reseed(seed)
        jax.block_until_ready(self.params)
        t_seeded = time.perf_counter()
        self.model._ensure_built()
        print(f"set-up: the program's build {t_built - t:.1f} s, weights from "
              f"the seed {t_seeded - t_built:.1f} s, the model's own "
              f"{time.perf_counter() - t_seeded:.1f} s", file=sys.stderr)
        annotate(self.model)
        self.core = ServerCore([self.model])
        # every user may have a request in flight, and a stream holds its
        # worker for as long as it is open
        self.frontend = GrpcInferenceServer(
            self.core, max_workers=users + 4).start()
        self.trace_dir = os.path.join(work_dir, "trace")
        self.traced = False

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def reseed(self, seed: int) -> None:
        """New weights in the old ones' place. The chip holds one copy: what
        the decoder holds (the last seed's, or what the builder left there)
        is let go of before the draw, which reads the shapes alone."""
        self.params = self.decoder._params = None
        self.params = self.decoder._params = make_params(
            self.template, seed, self.init_scale)

    def registry(self) -> Dict[str, float]:
        """Every series of the program's own metrics registry that carries
        the served model's label, as it stands: ``<metric>`` (with its other
        labels, ``<metric>{k=v}``) -> value; a histogram gives ``_count`` and
        ``_sum``. What the program counts for a model reaches a reader by
        adding a series there."""
        out: Dict[str, float] = {}
        for name, metric in self.core.metrics_registry().snapshot().items():
            for series in metric["series"]:
                labels = dict(series["labels"])
                if labels.pop("model", None) != self.model.name:
                    continue
                key = name + ("{" + ",".join(
                    f"{k}={v}" for k, v in sorted(labels.items())) + "}"
                    if labels else "")
                if "value" in series:
                    out[key] = series["value"]
                else:
                    out[key + "_count"] = series["count"]
                    out[key + "_sum"] = series["sum"]
        return out

    def counters(self) -> Dict[str, Any]:
        histogram = getattr(self.model, "batch_histogram", None)
        return {"compiles": self.compiles,
                "batch_histogram": dict(histogram) if histogram is not None else None,
                "registry": self.registry()}

    def weight_operands(self) -> Dict[str, str]:
        """The weights' arrays as the trace's operations name them
        (``bf16[1280,3840]``), for ``trace_reduce.reduce`` to call by name."""
        import jax

        return {"{}[{}]".format(HLO_TYPES.get(str(leaf.dtype), str(leaf.dtype)),
                                ",".join(str(d) for d in leaf.shape)): "weights"
                for leaf in jax.tree_util.tree_leaves(self.template)}

    def trace_start(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.traced = True

    def trace_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def finish(self, step_program: str) -> Dict[str, Any]:
        import jax

        self.frontend.stop(grace=1.0)
        out = self.counters()
        out["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())
        # free the program's state before the reference runs: the frontend
        # is closed, the batcher's worker joined, the caches dropped
        self.model.unload()
        if hasattr(self.model, "_caches"):
            self.model._caches = None
        if self.traced:
            from benchmark import trace_reduce

            path = trace_reduce.find_xplane(self.trace_dir)
            out["trace"] = (trace_reduce.reduce(
                trace_reduce.load_xplane(path), step_program,
                self.weight_operands())
                if path else None)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return out

    def check(self, sessions, length: int, control: bool) -> Dict[str, Any]:
        from benchmark import family

        reference = family.reference(self.config, control=control)
        t = time.perf_counter()
        out = reference.served_token_gaps(
            self.params, self.config, sessions, length,
            **({"control": True} if control else {}))
        if control and "control_gap_max" not in out:
            raise AttributeError(
                f"the configuration's reference module {reference.__name__!r} "
                "gave no control_gap_max")
        out["reference_s"] = time.perf_counter() - t
        return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--users", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--work-dir", required=True)
    args = parser.parse_args(argv)
    with open(args.cell) as f:
        cell = json.load(f)
    with open(args.config) as f:
        config = json.load(f)

    from client_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # keep every program, however quickly it compiled: a second run of a
    # cell then finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = jax.devices()[0]
    emit({"event": "device", "platform": device.platform,
          "kind": device.device_kind, "count": jax.device_count(),
          "compile_cache": cache_dir})

    served = Served(cell, config, args.users, args.seed, args.work_dir)
    emit({"event": "ready", "url": served.frontend.url,
          "model": served.model.name})

    for line in sys.stdin:
        command = json.loads(line)
        name = command["cmd"]
        try:
            if name == "mark":
                reply = served.counters()
            elif name == "trace_start":
                served.trace_start()
                reply = {}
            elif name == "trace_stop":
                served.trace_stop()
                reply = {}
            elif name == "finish":
                reply = served.finish(cell["step_program"])
            elif name == "check":
                reply = served.check(command["sessions"], command["length"],
                                     command.get("control", False))
            elif name == "reseed":
                served.reseed(int(command["seed"]))
                reply = {}
            elif name == "exit":
                emit({"ok": True})
                return 0
            else:
                raise ValueError(f"unknown command {name!r}")
        except Exception as e:  # the parent decides what a failure means
            import traceback

            traceback.print_exc()
            emit({"ok": False, "error": f"{type(e).__name__}: {e}"})
        else:
            emit({"ok": True, **reply})
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's one command: a cell, a seed, a window.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the users: numpy and the GRPC client, never jax. It starts
``benchmark/server.py`` as the one process that holds the chip, waits until
the cell's model is built and warm, runs the cell's traffic for ``--seconds``
seconds, has the plain reference judge a sample of what was served, prints
one JSON object as its last line, and stops the child.

Everything that belongs to one cell is found by name from ``BENCHMARK.json``:
``configs/<configuration>.json``, ``traffic/<mix>.json`` (which names its
``lengths/<name>.json``), ``cells/<cell>.json`` (which names its builder) and
``layer_metrics/<metric>.json`` (with a reader module beside it where the
metric is computed and not simply looked up). A configuration names its
family's own arithmetic and reference modules (``benchmark/family.py`` has
the contract; absent, the GPT-2 family's ``shapes.py`` and ``reference.py``),
and this file, ``server.py``, ``calibrate.py`` and the readers ask those and
read no other key of a configuration. Every number the reference returns is
handed to ``judge`` by its name, so a family's reading of its own is held by
the limit its cell's file names for it. A new cell, mix, per-layer metric,
configuration or family of models is new files and new entries, and no edit
here.

A traced run hands its readers, in ``facts``: ``trace`` (``trace_reduce``'s
reduction, with device time by named scope under ``scopes``), ``server`` (the
statistics verb's seven pairs over the window), ``registry`` (the window's
difference of every series of the program's metrics registry labelled with
the served model), ``client``, ``batch_histogram``, ``work``, ``window``,
``peaks``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import queue
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_T_START = time.perf_counter()
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402

from benchmark import family, stats  # noqa: E402
from benchmark.sessions import SessionEngine, SessionPlan  # noqa: E402

CHECK_SESSIONS = 8  # sessions the reference is run over, the longest among them
WARMUP = {"prompt": 8, "output": 4, "sessions_per_user": 2}
TRACE_SECONDS = 3.0
READY_TIMEOUT_S = 1100.0


class NoResult(Exception):
    """The run cannot give a result; the command exits non-zero without one."""


# -- the cell's files ---------------------------------------------------------

def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def resolve_cell(root: str, workload: str) -> Dict[str, Any]:
    """Everything a cell names, loaded: its ``BENCHMARK.json`` entry, its
    configuration, its traffic mix, its cell file, and the metrics it owes."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    home = os.path.join(root, bench["paths"][0])
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise NoResult(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[0]
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def owed(metrics: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    traffic = _load(os.path.join(home, "traffic", entry["traffic"] + ".json"))
    traffic["lengths"] = _load(
        os.path.join(home, "lengths", traffic["lengths"] + ".json"))
    return {
        "root": root, "home": home, "entry": entry,
        "config_path": os.path.join(root, config_entry["file"]),
        "config": _load(os.path.join(root, config_entry["file"])),
        "traffic": traffic,
        "cell_path": os.path.join(home, "cells", workload + ".json"),
        "cell": _load(os.path.join(home, "cells", workload + ".json")),
        "end_to_end": owed(bench["end_to_end"]),
        "per_layer": owed(bench["per_layer"]),
    }


def read_layer_metric(home: str, name: str, facts: Dict[str, Any]) -> Optional[float]:
    """A per-layer metric's file either names a fact to look up
    (``{"fact": "trace.step_device_ms"}``) or a reader module beside it whose
    ``read(facts)`` computes the number. Nothing to read gives ``None``."""
    spec = _load(os.path.join(home, "layer_metrics", name + ".json"))
    if "fact" in spec:
        value: Any = facts
        for key in spec["fact"].split("."):
            value = value.get(key) if isinstance(value, dict) else None
        return value
    path = os.path.join(home, "layer_metrics", spec["reader"])
    module_spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read(facts)


# -- the child ----------------------------------------------------------------

class Child:
    """``server.py`` and the line protocol with it."""

    def __init__(self, command: List[str]):
        self._proc = subprocess.Popen(
            command, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)
        self._lines: "queue.Queue[Optional[str]]" = queue.Queue()
        self._lock = threading.Lock()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self._proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def receive(self, timeout: float) -> Dict[str, Any]:
        while True:
            try:
                line = self._lines.get(timeout=timeout)
            except queue.Empty:
                raise NoResult(f"the serving process said nothing for {timeout:.0f} s")
            if line is None:
                raise NoResult("the serving process ended "
                               f"(exit code {self._proc.wait()})")
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)

    def ask(self, cmd: str, timeout: float = 300.0, **fields: Any) -> Dict[str, Any]:
        with self._lock:
            self._proc.stdin.write(json.dumps({"cmd": cmd, **fields}) + "\n")
            self._proc.stdin.flush()
            reply = self.receive(timeout)
        if not reply.get("ok"):
            raise NoResult(f"{cmd}: {reply.get('error')}")
        return reply

    def stop(self) -> None:
        """End the child and wait until it has ended."""
        if self._proc.poll() is None:
            try:
                self.ask("exit", timeout=10.0)
            except (NoResult, OSError, ValueError):
                pass
        try:
            self._proc.wait(timeout=20.0)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait()
        for stream in (self._proc.stdin, self._proc.stdout):
            try:
                stream.close()
            except OSError:
                pass


# -- the run ------------------------------------------------------------------

def _delta(after: Optional[Dict], before: Optional[Dict]) -> Optional[Dict]:
    if after is None or before is None:
        return None
    return {k: after[k] - before.get(k, 0) for k in after}


def _model_stats(client, model: str) -> Dict[str, int]:
    reply = client.get_inference_statistics(model)
    row = reply["model_stats"][0]["inference_stats"]
    return {f"{kind}_{field}": int(row.get(kind, {}).get(field, 0))
            for kind in ("success", "fail", "cancel", "queue", "compute_input",
                         "compute_infer", "compute_output")
            for field in ("count", "ns")}


def window_positions(records: List[Dict[str, Any]], t0: float,
                     seconds: float) -> List[int]:
    """The positions of the tokens the model processed inside the window. A
    prompt is processed between its send and its first token, so it counts by
    the share of that span that lies inside the window; the step that gave
    token ``i >= 1`` processed position ``prompt + i - 1`` and counts if the
    token was received inside it."""
    t1 = t0 + seconds
    positions: List[int] = []
    for r in records:
        times = r["token_times"]
        if not times:
            continue
        span = max(times[0] - r["t_send"], 1e-9)
        inside = max(0.0, min(times[0], t1) - max(r["t_send"], t0)) / span
        n_prompt = len(r["prompt"])
        positions.extend(range(int(round(n_prompt * inside))))
        positions.extend(n_prompt + i - 1 for i in range(1, len(times))
                         if t0 <= times[i] <= t1)
    return positions


def pick_sample(records: List[Dict[str, Any]], seed: int,
                since: float = float("-inf"),
                until: float = float("inf")) -> List[Dict[str, Any]]:
    """Of the sessions that finished between ``since`` and ``until``, the
    longest and others drawn from the seed."""
    done = [r for r in records
            if r["error"] is None and len(r["tokens"]) == r["tokens_out"]
            and since <= r["token_times"][-1] <= until]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed & ((1 << 63) - 1), 2])
    picks = rng.permutation(len(rest))[:CHECK_SESSIONS - 1]
    return [longest] + [rest[i] for i in sorted(picks)]


def own_readings(checked: Dict[str, Any]) -> Dict[str, float]:
    """Every number of the reference's answer, by its own name: beside
    ``served_gap_max`` a family's reference may return readings of its own
    (the share of positions it set aside as near ties of the router), and a
    limit named for one in ``cells/<cell>.json`` holds it."""
    return {name: value for name, value in checked.items()
            if isinstance(value, (int, float)) and not isinstance(value, bool)}


def judge(readings: Dict[str, Any],
          limits: Dict[str, float]) -> Tuple[Dict[str, Any], bool]:
    """Every number compared beside its limit, and the verdict: the three
    exact ones and every one the cell's file names a limit for, be it
    ``served_gap_max`` or a reading of the family's own. A number that was
    not read is not within its limit."""
    exact = {"sessions_failed": 0, "argmax_mismatch": 0, "compiles_in_window": 0}
    compared = {name: {"value": readings.get(name), "limit": limit}
                for name, limit in {**exact, **limits}.items()}
    return compared, all(c["value"] is not None and c["value"] <= c["limit"]
                         for c in compared.values())


class Serving:
    """The cell's model served by the child, warm, with its users connected."""

    def __init__(self, cell: Dict[str, Any], seed: int, require_tpu: bool = True,
                 server_command: Optional[List[str]] = None):
        traffic = cell["traffic"]
        self.vocab = family.arithmetic(cell["config"]).vocab(cell["config"])
        work_dir = os.path.join(cell["root"], ".benchmark_run", cell["entry"]["name"])
        os.makedirs(work_dir, exist_ok=True)
        command = (server_command
                   or [sys.executable, os.path.join(cell["home"], "server.py")]) + [
            "--cell", cell["cell_path"], "--config", cell["config_path"],
            "--users", str(cell["cell"]["users"]), "--seed", str(seed),
            "--work-dir", work_dir]
        self.child = Child(command)
        self.engine = self.control = None
        try:
            self.device = self.child.receive(READY_TIMEOUT_S)
            chips = cell["entry"]["chips"]
            if require_tpu and (self.device.get("platform") != "tpu"
                                or self.device.get("count", 0) < chips):
                raise NoResult(f"needs {chips} TPU chip(s), found "
                               f"{self.device.get('count')} x "
                               f"{self.device.get('platform')}")
            ready = self.child.receive(READY_TIMEOUT_S)
            self.model = ready["model"]

            import client_tpu.grpc as grpcclient

            self.engine = SessionEngine(ready["url"], self.model,
                                        traffic["api"], cell["cell"]["users"])
            self.control = grpcclient.InferenceServerClient(ready["url"])
            # warm-up: the cell's own programs through the cell's own path,
            # every user at once, so every slot and every stream has been used
            fixed = lambda n: {"mean": n, "sigma": 0.0, "min": n, "max": n}
            warm = dict(traffic, lengths={
                "pool": 1, "prompt": fixed(WARMUP["prompt"]),
                "output": fixed(WARMUP["output"])})
            warmed = self.engine.run(
                SessionPlan(warm, self.vocab, seed), first_index=0,
                sessions_per_user=WARMUP["sessions_per_user"])
            errors = [r["error"] for r in warmed["records"] if r["error"]]
            if errors:
                raise NoResult(f"warm-up failed: {errors[0]}")
        except BaseException:
            self.close()
            raise

    def __enter__(self) -> "Serving":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    def close_users(self) -> None:
        for client in (self.engine, self.control):
            if client is not None:
                client.close()
        self.engine = self.control = None

    def close(self) -> None:
        self.close_users()
        self.child.stop()

    def check(self, sample: List[Dict[str, Any]], length: int,
              control: bool = False) -> Dict[str, Any]:
        """The plain reference over a sample of finished sessions, each
        padded to ``length`` positions."""
        if not sample:
            return {}
        return self.child.ask(
            "check", timeout=600.0, control=control, length=length,
            sessions=[{"prompt": [int(t) for t in r["prompt"]],
                       "tokens": r["tokens"]} for r in sample])


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             server_command: Optional[List[str]] = None,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """One run of one cell; the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = resolve_cell(root, workload)
    config, traffic, home = cell["config"], cell["traffic"], cell["home"]

    with Serving(cell, seed, require_tpu, server_command) as serving:
        child, engine, control = serving.child, serving.engine, serving.control
        device, model, vocab = serving.device, serving.model, serving.vocab

        # the users begin one after another over a ramp before the window
        # opens, so that the window finds them out of step with each other,
        # as a server finds them, and not all sending their first prompt at
        # once
        ramp = float(traffic["ramp_seconds"])
        plan = SessionPlan(traffic, vocab, seed)
        opened: Dict[str, Any] = {}

        def open_window() -> None:
            try:
                opened["counters"] = child.ask("mark")
                opened["server"] = _model_stats(control, model)
                opened["client"] = engine.client_stats()
            except Exception as e:  # raised below, on the main thread
                opened["error"] = e

        def traced() -> None:
            try:
                child.ask("trace_start")
                time.sleep(min(TRACE_SECONDS, seconds * 0.5))
                child.ask("trace_stop", timeout=600.0)
            except Exception as e:
                opened["error"] = e

        timers = [threading.Timer(ramp, open_window)]
        if trace:
            timers.append(threading.Timer(ramp + min(5.0, seconds * 0.25), traced))
        compiles_warm = child.ask("mark")["compiles"]
        for timer in timers:
            timer.daemon = True
            timer.start()
        # indices far past the warm-up's, so no sequence id comes twice
        ran = engine.run(plan, first_index=1024, seconds=ramp + seconds,
                         stagger=ramp)
        records, t0 = ran["records"], ran["t0"] + ramp
        for timer in timers:
            timer.join()
        if "error" in opened:
            raise NoResult(f"in the window: {opened['error']}")
        counters0, server0, client0 = (
            opened["counters"], opened["server"], opened["client"])

        server = _delta(_model_stats(control, model), server0)
        client = _delta(engine.client_stats(), client0)
        serving.close_users()
        finished = child.ask("finish", timeout=600.0)

        window = stats.window_metrics(records, t0, seconds)
        window["setup_s"] = t0 - t_start
        failed = [r for r in records if r["error"] is not None or (
            not r["cut"] and len(r["tokens"]) != r["tokens_out"])]
        for r in failed[:3]:
            print(f"session {r['index']} failed: {r['error']} "
                  f"({len(r['tokens'])} of {r['tokens_out']} tokens)", file=sys.stderr)
        sample = pick_sample(records, seed, since=t0, until=t0 + seconds)
        checked = serving.check(sample, plan.longest)

    compared, correct = judge({
        **own_readings(checked),
        "sessions_failed": len(failed),
        "argmax_mismatch": sum(r["argmax_mismatch"] for r in records),
        "compiles_in_window": finished["compiles"] - compiles_warm,
        "served_gap_max": checked.get("served_gap_max"),
    }, cell["cell"]["limits"])

    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": finished["memory_peak_bytes"]}
    begun = [r for r in records if t0 <= r["t_send"] <= t0 + seconds]
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": len(begun),
        "failed": len(failed), "metrics": {}, "device": device_out}
    if not trace:
        owed, values = cell["end_to_end"], window
    else:
        facts = {
            "config": config, "traffic": traffic, "cell": cell["cell"],
            "seconds": seconds, "chips": cell["entry"]["chips"],
            "device": device_out, "window": window,
            "trace": finished.get("trace"), "client": client, "server": server,
            "batch_histogram": _delta(finished["batch_histogram"],
                                      counters0["batch_histogram"]),
            "registry": _delta(finished.get("registry"),
                               counters0.get("registry")),
            "work": family.arithmetic(config).work(
                config, window_positions(records, t0, seconds)),
        }
        # only a TPU has peaks to be held against; an unknown kind of TPU is
        # an error, not a default
        facts["peaks"] = (stats.peaks_for(device["kind"])
                          if device["platform"] == "tpu" else None)
        owed = cell["per_layer"]
        values = {m["name"]: read_layer_metric(home, m["name"], facts) for m in owed}
        if facts["trace"]:
            device_out["busy_s"] = facts["trace"]["busy_s"]
            device_out["window_s"] = facts["trace"]["window_s"]
            scopes = facts["trace"].get("scopes", [])
            result["breakdown"] = {
                "device_ops": facts["trace"]["device_ops"],
                "idle_gaps": facts["trace"]["idle_gaps"],
                "device_scopes": [[name, s] for name, s, _, _ in scopes[:10]]}
            result["scopes"] = scopes  # every row, with counts and mean us
    for m in owed:
        if values.get(m["name"]) is not None:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    # every user metric of the window, also those this cell holds to no bound
    result["window"] = {k: v for k, v in window.items() if v is not None}
    result["counts"] = {"sessions_checked": len(sample),
                        "sessions_finished": sum(
                            1 for r in records if r["token_times"]
                            and len(r["tokens"]) == r["tokens_out"]
                            and t0 <= r["token_times"][-1] <= t0 + seconds),
                        "compiles_before_window": compiles_warm,
                        "reference_s": checked.get("reference_s")}
    result["compared"] = compared
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run_cell(_ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=_T_START)
    except NoResult as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

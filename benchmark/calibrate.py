"""Readings the limit of ``served_gap_max`` is set from, on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 3 --seconds 20

Not run by the benchmark. One serving process, many seeds: each seed gets new
weights (``reseed``: the old ones are let go of first, the chip holds one
copy) and its own traffic at the cell's own load for ``--seconds`` seconds,
sessions in flight at the close run to their end, and the configuration's own
plain reference (``benchmark/family.py``) reads the widest gap over the usual
sample: the lower reading. Whatever other numbers that reference returns are
held, each by the limit the cell's file names for it, as in a run. On the
first ``--control-seeds`` seeds that reference's control (for the GPT-2 family
the fp8 pass) is run over the same prompts and tokens as well; the gap of the
token it puts first is the upper reading, and it goes through the run's own
comparison in the served gap's place, the other readings as they stood, which
has to say ``control_correct: false``. A reference that has no control fails
here, by its module's name. One JSON object a seed on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Iterator, List, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import run  # noqa: E402
from benchmark.sessions import SessionPlan  # noqa: E402


def read_seeds(serving: "run.Serving", cell: Dict[str, Any], seeds: List[int],
               control_seeds: int, seconds: float) -> Iterator[Dict[str, Any]]:
    traffic, limits = cell["traffic"], cell["cell"]["limits"]
    for k, seed in enumerate(seeds):
        serving.child.ask("reseed", seed=seed)
        plan = SessionPlan(traffic, serving.vocab, seed)
        ran = serving.engine.run(plan, first_index=(k + 1) * 100_000,
                                 seconds=seconds, drain=True,
                                 stagger=min(seconds, traffic["ramp_seconds"]))
        records = ran["records"]
        sample = run.pick_sample(records, seed)
        read = serving.check(sample, plan.longest, control=k < control_seeds)
        failed = [r["error"] for r in records if r["error"]]
        exact = {"sessions_failed": len(failed), "compiles_in_window": 0,
                 "argmax_mismatch": sum(r["argmax_mismatch"] for r in records)}
        out = {"workload": cell["entry"]["name"], "seed": seed,
               "device": serving.device.get("kind"),
               "sessions": len(records), "failed": len(failed),
               "first_error": failed[0] if failed else None,
               "sessions_checked": len(sample),
               **{k2: v for k2, v in read.items() if k2 != "ok"}}
        readings = {**run.own_readings(read), **exact}
        out["compared"], out["correct"] = run.judge(
            dict(readings, served_gap_max=read.get("served_gap_max")), limits)
        if "control_gap_max" in read:
            # the control in the program's place, through the same comparison:
            # its gap for the served one, the family's other readings as read
            _, out["control_correct"] = run.judge(
                dict(readings, served_gap_max=read["control_gap_max"]), limits)
        yield out


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--seconds", type=float, default=20.0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = run.resolve_cell(_ROOT, args.workload)
    with run.Serving(cell, seeds[0]) as serving:
        for reading in read_seeds(serving, cell, seeds, args.control_seeds,
                                  args.seconds):
            print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

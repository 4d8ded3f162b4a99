"""The least time the chip could take for a mean step, over the step's
measured device time. The least bytes and the FLOPs of a step of the mean
width (the batcher's; 1 where every step is one sequence's) are the
configuration's own arithmetic's (``step_least``, ``benchmark/family.py``).
Never over 100: it is a lower bound over a measured time."""

from benchmark import family
from benchmark.layer_metrics.batch_width_mean import mean_width


def read(facts):
    trace, peaks, work = facts.get("trace"), facts.get("peaks"), facts.get("work")
    if not trace or not peaks or not trace.get("step_device_ms"):
        return None
    if not work or not work["tokens_processed"]:
        return None
    width = mean_width(facts.get("batch_histogram")) or 1.0
    least = family.arithmetic(facts["config"]).step_least(
        facts["config"], work, width)
    least_s = max(least["bytes"] / peaks["hbm_bytes_per_s"],
                  least["flops"] / peaks["flops_per_s"])
    return 100.0 * least_s / (trace["step_device_ms"] / 1e3)

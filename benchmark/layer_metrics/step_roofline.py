"""Least bytes of a mean step: every matmul weight once, and for each of the
sequences in it (the batcher's mean width; 1 where every step is one
sequence's) the mean token's cache rows, table rows and logits row. The same
count whatever implements the step. Never over 100: it is a lower bound over
a measured time."""

from benchmark import shapes
from benchmark.layer_metrics.batch_width_mean import mean_width


def read(facts):
    trace, peaks, work = facts.get("trace"), facts.get("peaks"), facts.get("work")
    if not trace or not peaks or not trace.get("step_device_ms"):
        return None
    if not work or not work["tokens_processed"]:
        return None
    width = mean_width(facts.get("batch_histogram")) or 1.0
    tokens = work["tokens_processed"]
    step_bytes = (shapes.step_weight_bytes(facts["config"])
                  + width * (work["cache_bytes"] + work["row_bytes"]) / tokens)
    step_flops = width * work["flops"] / tokens
    least_s = max(step_bytes / peaks["hbm_bytes_per_s"],
                  step_flops / peaks["flops_per_s"])
    return 100.0 * least_s / (trace["step_device_ms"] / 1e3)

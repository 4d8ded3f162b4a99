"""The round's attention over its two kinds of state against its own
roofline: the least time of the ring rows and summary rows a mean round's
members attend to (the configuration's arithmetic: ``step_parts(config,
work, width)``, whose ``attention`` is counted from the exact rows of the
window's positions) over the device time a round spends in the attention's
named scopes. Nothing to read (no trace, no such scope, an arithmetic whose
``work`` counts no such rows) gives nothing."""

from benchmark import family
from benchmark.layer_metrics.batch_width_mean import mean_width

SCOPES = ("window_attention", "summary_attention", "eva_attention")


def read(facts):
    trace, peaks, work = facts.get("trace"), facts.get("peaks"), facts.get("work")
    if not trace or not peaks or not work or not trace.get("step_count"):
        return None
    if not work.get("tokens_processed") or "summary_rows" not in work:
        return None
    seconds = sum(row[1] for row in trace.get("scopes", []) if row[0] in SCOPES)
    if not seconds:
        return None
    width = mean_width(facts.get("batch_histogram")) or 1.0
    parts = family.arithmetic(facts["config"]).step_parts(facts["config"], work, width)
    least_s = parts["attention"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / trace["step_count"])

"""The routed layers of a round against their own roofline: the least time
of the routers' bytes and of each expert the round's grouped products read,
once (the program's tally, ``experts_reached_mean.per_round``; the
configuration's arithmetic, ``experts_bytes``), over the device time a round
spends in ``moe_route`` and ``moe_experts`` (``trace.scopes`` over
``trace.step_count``). Never over 100: the experts counted are those read.
Nothing to read (no trace, no tally, no such scope) gives nothing."""

from benchmark import family
from benchmark.layer_metrics.experts_reached_mean import per_round

SCOPES = ("moe_route", "moe_experts")


def read(facts):
    trace, peaks = facts.get("trace"), facts.get("peaks")
    if not trace or not peaks or not trace.get("step_count"):
        return None
    reached = per_round(facts)
    least = getattr(family.arithmetic(facts["config"]), "experts_bytes", None)
    if reached is None or least is None:
        return None
    seconds = sum(row[1] for row in trace.get("scopes", []) if row[0] in SCOPES)
    if not seconds:
        return None
    least_s = least(facts["config"], reached) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / trace["step_count"])

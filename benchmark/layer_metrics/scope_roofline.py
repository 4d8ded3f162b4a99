"""A part of the decode step against its own roofline: the least time of
the part's bytes (the configuration's arithmetic: ``step_parts``, at the mean
reach of the window's tokens) over the device time a step spends in the
part's named scopes (``trace.scopes``: seconds inside the executions of the
cell's step program, over ``trace.step_count`` of them). Nothing to read (no
trace, no such scope, an arithmetic without ``step_parts``) gives nothing."""

from benchmark import family


def share(facts, part, scopes):
    trace, peaks, work = facts.get("trace"), facts.get("peaks"), facts.get("work")
    if not trace or not peaks or not work or not trace.get("step_count"):
        return None
    parts = getattr(family.arithmetic(facts["config"]), "step_parts", None)
    if parts is None or not work.get("tokens_processed") or "reach" not in work:
        return None
    seconds = sum(row[1] for row in trace.get("scopes", []) if row[0] in scopes)
    if not seconds:
        return None
    live = work["reach"] / work["tokens_processed"]
    least_s = parts(facts["config"], live)[part] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / trace["step_count"])

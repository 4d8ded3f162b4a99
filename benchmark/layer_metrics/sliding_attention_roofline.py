"""A window layer's attention of a round against its own roofline: the least
time of the ring rows a mean round's members attend to, ``min(t + 1,
window)`` a window layer a member (the program's count,
``client_tpu_server_attention_rows_read{kind=sliding}``, over the rounds of
``batch_histogram``; the configuration's arithmetic, ``attention_bytes``),
over the device time a round spends in the ``sliding_attention`` scope
(``trace.scopes`` over ``trace.step_count``). The rows are counted from the
decode steps' own positions: the window's positions (``work``) hold the
prompts' too, which the rounds do not read. Never over 100: the rows counted
are those the members attend to, and the scope's own operations read each
member's rows from where the table lies, rounded up to whole blocks (a round
whose program stages the table in fast memory before the scope reads above
it). Nothing to read (no trace, no count, no such scope) gives nothing."""

from benchmark import family


def roofline(facts, kind, scope):
    """The share for the rows of ``kind`` over the device time of ``scope``."""
    trace, peaks = facts.get("trace"), facts.get("peaks")
    if not trace or not peaks or not trace.get("step_count"):
        return None
    rows = (facts.get("registry") or {}).get(
        f"client_tpu_server_attention_rows_read{{kind={kind}}}")
    rounds = sum((facts.get("batch_histogram") or {}).values())
    least = getattr(family.arithmetic(facts["config"]), "attention_bytes", None)
    if not rows or not rounds or least is None:
        return None
    seconds = sum(row[1] for row in trace.get("scopes", []) if row[0] == scope)
    if not seconds:
        return None
    least_s = least(facts["config"], rows / rounds) / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / trace["step_count"])


def read(facts):
    return roofline(facts, "sliding", "sliding_attention")

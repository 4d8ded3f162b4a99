def read(facts):
    peaks, work = facts.get("peaks"), facts.get("work")
    if not peaks or not work or not work["tokens_processed"]:
        return None
    return 100.0 * work["flops"] / (peaks["flops_per_s"] * facts["chips"] * facts["seconds"])

def read(facts):
    trace = facts.get("trace")
    scopes = (trace or {}).get("scopes") or []
    own = sum(row[1] for row in scopes if row[0] == "short_conv")
    if not own:  # a program with no conv layer has no such scope
        return None
    return 100.0 * own / sum(row[1] for row in scopes)

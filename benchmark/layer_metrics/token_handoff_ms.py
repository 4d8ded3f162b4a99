def read(facts):
    registry = facts.get("registry") or {}
    tokens = registry.get("client_tpu_server_token_handoff_count")
    ns = registry.get("client_tpu_server_token_handoff_ns")
    if not tokens or not ns:
        return None
    return ns / tokens / 1e6

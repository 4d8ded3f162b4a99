def read(facts):
    registry = facts.get("registry") or {}
    tokens = registry.get("client_tpu_server_prefill_tokens")
    if not tokens or "client_tpu_server_prefill_ns" not in registry:
        return None
    return registry["client_tpu_server_prefill_ns"] / 1e6 / (tokens / 1e3)

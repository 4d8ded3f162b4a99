def read(facts):
    registry = facts.get("registry") or {}
    requests = registry.get("client_tpu_server_answer_wake_count")
    ns = registry.get("client_tpu_server_answer_wake_ns")
    if not requests or not ns:
        return None
    return ns / requests / 1e6

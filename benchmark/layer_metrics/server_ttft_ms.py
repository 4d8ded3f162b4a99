def read(facts):
    registry = facts.get("registry") or {}
    streams = registry.get("client_tpu_server_first_response_count")
    ns = registry.get("client_tpu_server_first_response_ns")
    if not streams or not ns:
        return None
    return ns / streams / 1e6

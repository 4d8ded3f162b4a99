def read(facts):
    registry = facts.get("registry") or {}
    requests = registry.get("client_tpu_server_sequence_stride_count")
    rounds = registry.get("client_tpu_server_sequence_stride_rounds")
    if not requests or not rounds:
        return None
    return rounds / requests

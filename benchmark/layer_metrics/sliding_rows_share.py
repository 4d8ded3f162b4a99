def read(facts):
    registry = facts.get("registry") or {}
    sliding = registry.get("client_tpu_server_attention_rows_read{kind=sliding}")
    full = registry.get("client_tpu_server_attention_rows_read{kind=full}")
    if sliding is None or full is None or not sliding + full:
        return None
    return 100.0 * sliding / (sliding + full)

def read(facts):
    registry = facts.get("registry") or {}
    summaries = registry.get("client_tpu_server_summary_rows_read")
    exact = registry.get("client_tpu_server_window_rows_read")
    if summaries is None or exact is None or not summaries + exact:
        return None
    return 100.0 * summaries / (summaries + exact)

def read(facts):
    client, server = facts.get("client"), facts.get("server")
    if not client or not server:
        return None
    if not client.get("completed_request_count") or not server.get("success_count"):
        return None
    total = client["cumulative_total_request_time_ns"] / client["completed_request_count"]
    inside = server["success_ns"] / server["success_count"]
    return (total - inside) / 1e6

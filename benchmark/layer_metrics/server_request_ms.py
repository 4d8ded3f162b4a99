def read(facts):
    server = facts.get("server")
    if not server or not server.get("success_count"):
        return None
    return server["success_ns"] / server["success_count"] / 1e6

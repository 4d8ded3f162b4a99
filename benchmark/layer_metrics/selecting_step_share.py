def read(facts):
    registry = facts.get("registry")
    if not registry or "client_tpu_server_selecting_steps" not in registry:
        return None
    steps = sum(value for name, value in registry.items()
                if name.startswith("client_tpu_server_decode_steps{"))
    if not steps:
        return None
    return 100.0 * registry["client_tpu_server_selecting_steps"] / steps

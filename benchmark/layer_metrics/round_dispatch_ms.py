PREFIX = "client_tpu_server_round_phase_"


def read(facts):
    registry = facts.get("registry") or {}
    rounds = registry.get(PREFIX + "count{phase=dispatch}")
    ns = registry.get(PREFIX + "ns{phase=dispatch}")
    if not rounds or not ns:
        return None
    return ns / rounds / 1e6

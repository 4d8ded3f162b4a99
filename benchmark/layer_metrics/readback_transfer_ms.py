PREFIX = "client_tpu_server_round_phase_"


def read(facts):
    registry = facts.get("registry") or {}
    transfers = registry.get(PREFIX + "count{phase=readback}")
    ns = registry.get(PREFIX + "ns{phase=readback}")
    if not transfers or not ns:
        return None
    return ns / transfers / 1e6

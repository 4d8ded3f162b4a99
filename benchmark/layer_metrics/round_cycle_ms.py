PREFIX = "client_tpu_server_round_phase_"


def read(facts):
    registry = facts.get("registry") or {}
    rounds = registry.get(PREFIX + "count{phase=dispatch}")
    ns = sum(value for series, value in registry.items()
             if series.startswith(PREFIX + "ns{")
             and series != PREFIX + "ns{phase=wait_work}")
    if not rounds or not ns:
        return None
    return ns / rounds / 1e6

PREFIX = "client_tpu_server_round_phase_"
NOT_THE_HOSTS = (PREFIX + "ns{phase=wait_work}", PREFIX + "ns{phase=device_wait}")


def read(facts):
    registry = facts.get("registry") or {}
    rounds = registry.get(PREFIX + "count{phase=dispatch}")
    ns = sum(value for series, value in registry.items()
             if series.startswith(PREFIX + "ns{") and series not in NOT_THE_HOSTS)
    if not rounds or not ns:
        return None
    return ns / rounds / 1e6

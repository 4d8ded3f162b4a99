"""The mean distinct experts a routed layer's grouped product read a round,
from the program's tally (``client_tpu_server_experts_reached`` and its
``_rounds``, program ``round``) over the configuration's routed layers (its
arithmetic's ``routed_layers``). A program or a family without them gives
nothing."""

from benchmark import family

REACHED = "client_tpu_server_experts_reached{program=round}"
ROUNDS = "client_tpu_server_experts_reached_rounds{program=round}"


def per_round(facts):
    """The distinct experts a round read, summed over its routed layers."""
    registry = facts.get("registry") or {}
    if not registry.get(ROUNDS) or REACHED not in registry:
        return None
    return registry[REACHED] / registry[ROUNDS]


def read(facts):
    reached = per_round(facts)
    layers = getattr(family.arithmetic(facts["config"]), "routed_layers", None)
    if reached is None or layers is None or not layers(facts["config"]):
        return None
    return reached / layers(facts["config"])

from benchmark.layer_metrics.scope_roofline import share


def read(facts):
    return share(facts, "experts", ("moe_route", "moe_experts"))

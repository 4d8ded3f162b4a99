from benchmark.layer_metrics.request_parts import mean_ms


def read(facts):
    total, inside = mean_ms(facts, "success"), mean_ms(facts, "compute_infer")
    if total is None or inside is None:
        return None
    return total - inside

from benchmark.layer_metrics.request_parts import mean_ms


def read(facts):
    return mean_ms(facts, "compute_infer")

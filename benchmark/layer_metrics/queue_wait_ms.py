from benchmark.layer_metrics.request_parts import mean_ms


def read(facts):
    return mean_ms(facts, "queue") or None  # a queue of 0 ns is none to read

from benchmark.layer_metrics.request_parts import mean_ms


def read(facts):
    total, queue, own = (mean_ms(facts, part)
                         for part in ("success", "queue", "compute_infer"))
    if not queue or own is None:  # no queue: not a batcher's request
        return None
    return total - queue - own

from benchmark.layer_metrics.scope_roofline import share


def read(facts):
    return share(facts, "sparse_attention", ("indexer", "select", "sparse_attention"))

def mean_width(histogram):
    rounds = sum(histogram.values()) if histogram else 0
    if not rounds:
        return None
    return sum(int(width) * n for width, n in histogram.items()) / rounds


def read(facts):
    return mean_width(facts.get("batch_histogram"))

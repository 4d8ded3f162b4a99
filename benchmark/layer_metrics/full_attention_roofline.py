"""A full layer's attention of a round against its own roofline: the least
time of the prefix rows a mean round's members attend to, ``t + 1`` a full
layer a member (``client_tpu_server_attention_rows_read{kind=full}`` over the
rounds), over the device time a round spends in the ``full_attention``
scope. Each member's rows are read up to its own position, rounded up to a
whole block of rows, so the share reads a little low by construction where
members sit short of a block's end. See ``sliding_attention_roofline.py``."""

from benchmark.layer_metrics.sliding_attention_roofline import roofline


def read(facts):
    return roofline(facts, "full", "full_attention")

"""Mean self time of a ``frontend.round`` span in the traced window: the
dispatcher's own work in a round (gather, pad, split, hand back), the
service calls inside it left out."""

from bench.metrics_util import span_ms


def read(rec):
    return span_ms(rec, "frontend.round", own=True)

"""Samples per engine call over the bucket each call ran in, over the
window (``ServeRuntime.stats``)."""


def read(r):
    return r.counters["batch_fill"]

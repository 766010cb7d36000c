"""Real tokens over the tokens of the bucket programs that served them,
over the window (``ServeRuntime.stats``: ``batch_tokens`` and
``bucket_tokens``)."""


def read(r):
    real, padded = r.counters.get("real_tokens"), r.counters.get("bucket_tokens")
    return 100.0 * real / padded if padded else None

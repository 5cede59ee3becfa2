"""Share of a replay window's wall time inside TTL selection: host spans
around ``AdaptiveTTLController.edge_ttl_table`` and ``edge_ttl``."""


def read(run):
    if run.kind != "replay" or "bench.ttl" not in run.spans.intervals:
        return None
    return 100.0 * run.spans.total("bench.ttl", run.window) / run.window_s

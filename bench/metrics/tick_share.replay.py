"""Share of a replay window's wall time inside the maintenance tick: host
spans ``bench.tick`` around ``SkyStorePolicy.periodic`` (the paused-bytes
census and, on the ``tick`` refresh schedule, the sweep)."""


def read(run):
    if "bench.tick" not in run.spans.intervals:
        return None
    return 100.0 * run.spans.total("bench.tick", run.window) / run.window_s

"""The sweep's share of its roofline: the least time the chip needs for the
(bucket, region) pairs the window's sweeps solved (``bench/device/
ttl_work.py``: a pair's sweep solves what its refresh solves), over the
device time of every program that ran inside the maintenance tick's host
spans (``bench.tick``)."""

from bench.device import peaks, ttl_work


def read(run):
    n = run.counters.get("sweep_pairs")
    if run.device is None or not n:
        return None
    device_s = run.device.seconds_inside("bench.tick")
    if device_s <= 0:
        return None
    edges = len(run.config["regions"]) - 1
    h = run.config["ttl"]["histogram"]
    cells = h["linear_cells"] + h["log_cells"]
    least = ttl_work.least_seconds(n, edges, cells, peaks.peaks(run.device_kind))
    return 100.0 * least / device_s

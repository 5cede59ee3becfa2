"""Median time of ``VirtualStore.dispatch`` (the typed-op layer under the
S3 codec) over every request of a served window."""

from bench.stats import percentile


def read(run):
    if run.kind != "served":
        return None
    lo, hi = run.window
    d = [b - a for a, b in run.spans.intervals.get("bench.dispatch", [])
         if a >= lo and b <= hi]
    return None if not d else 1e3 * percentile(d, 50)

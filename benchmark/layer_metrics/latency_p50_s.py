"""Whole-request latency at the HTTP client, median (closed loop: from
send to reply; open loop: from when the request was due)."""
from benchmark import stats

LAYER, SOURCE, UNIT, BETTER = "entry_serve", "host_clock", "s", "lower"


def reduce(run):
    lat = run.samples.get("latency_s")
    if not lat:
        return None
    return stats.percentile(lat, 50)

"""How late the benchmark's own load generator sent requests (send time
minus due time), 90th percentile: a starved generator invalidates a run."""
from benchmark import stats

LAYER, SOURCE, UNIT, BETTER = "loadgen", "host_clock", "ms", "lower"


def reduce(run):
    late = run.samples.get("send_late_s")
    if not late:
        return None
    return 1e3 * stats.percentile(late, 90)

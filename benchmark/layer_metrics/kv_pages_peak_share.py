"""Most pool pages in use at once over the pages the plan carved, sampled
each second by the load generator."""
LAYER, SOURCE, UNIT, BETTER = "kv_pool", "program_counter", "%", "lower"


def reduce(run):
    samples = run.samples.get("kv_pages_used_share")
    if not samples:
        return None
    return 100.0 * max(samples)

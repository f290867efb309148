"""Most recurrent-state slots in use at once over the slots the plan
holds (gauges `serving.state.slots_used` / `slots_total`), sampled each
tick by the load generator."""
LAYER, SOURCE, UNIT, BETTER = "kv_pool", "program_counter", "%", "higher"


def reduce(run):
    samples = run.samples.get("state_slots_used_share")
    if not samples:
        return None
    return 100.0 * max(samples)

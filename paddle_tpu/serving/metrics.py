"""Serving-tier metrics — every number the batcher/engine/server emits.

One namespace (``serving.*``) over core/monitor so operators get the
whole serving story from a single ``/stats`` scrape:

  counters    serving.requests.admitted / rejected / timeout / completed /
              failed, serving.batch.runs, serving.batch.coalesced,
              serving.gen.admitted / completed / steps / tokens,
              serving.gen.prefills / queue_wait_us / h2d_bytes / d2h_bytes,
              serving.kv.gather_bytes / append_bytes (the engine's span
              sites, docs/observability.md "Spans"),
              serving.gen.state_resets (a prefill overwriting a state slot
              that held a sequence before), serving.gen.state_in_place /
              state_copied (decode steps whose donated state arrays were
              all dead afterwards / steps where one was not: a backend
              that ignored the donation),
              serving.gen.sampled_on_device / logits_rows_fetched (the
              compiled step route: tokens taken from the step program's
              own argmax / logits rows downloaded, every slot's in a step
              where some row samples, none under greedy traffic),
              serving.gen.steps_ahead / rows_past_end (the compiled step
              route keeps one launch in flight: decode steps dispatched
              from the device's ids before the step before them was read,
              over serving.gen.steps how often that engages / rows a
              step computed for a sequence that had already ended, an EOS
              found out one step late),
              serving.moe.pairs_routed / pairs_held / experts_touched /
              expert_steps (a model with routed experts, counted on the
              device by its compiled steps and fetched behind the ids:
              token-expert pairs routed, those that landed on experts
              this chip holds, held experts with at least one pair, and
              expert layers run)
  gauges      serving.moe.load_max_over_mean (the last call's largest
              expert load over the mean load of the held experts),
              serving.queue.depth, serving.batch.last_size,
              serving.gen.active_slots, serving.server.inflight
  histograms  serving.latency_ms (end-to-end request latency),
              serving.batch.occupancy (rows per device run),
              serving.gen.seq_len (retired sequence lengths)

The histogram percentiles come from core/monitor's bounded reservoir, so
a week of traffic costs the same memory as a minute.
"""
from __future__ import annotations

from ..core.monitor import (gauge_get, gauge_set, hist_observe,
                            hist_snapshot, monitor_snapshot, stat_add,
                            stat_get, stat_reset)

__all__ = ["NAMESPACE", "count", "counter", "gauge", "gauge_value",
           "observe", "latency_ms", "percentiles", "serving_stats",
           "reset_serving_stats"]

NAMESPACE = "serving."


def _qual(name: str) -> str:
    return name if name.startswith(NAMESPACE) else NAMESPACE + name


def count(name: str, value: int = 1):
    """Bump a serving counter (name auto-prefixed with ``serving.``)."""
    stat_add(_qual(name), value)


def counter(name: str) -> int:
    return stat_get(_qual(name))


def gauge(name: str, value: float):
    gauge_set(_qual(name), value)


def gauge_value(name: str, default: float = 0) -> float:
    return gauge_get(_qual(name), default)


def observe(name: str, value: float):
    hist_observe(_qual(name), value)


def latency_ms(seconds: float):
    """Record one end-to-end request latency (seconds in, ms stored)."""
    hist_observe(_qual("latency_ms"), seconds * 1000.0)


def percentiles(name: str = "latency_ms"):
    """{count,min,max,mean,p50,p95,p99} for a serving histogram."""
    return hist_snapshot(_qual(name))


def serving_stats():
    """Full ``serving.*`` snapshot — counters, gauges and histogram
    percentile dicts (the /stats route payload)."""
    return monitor_snapshot(NAMESPACE)


def reset_serving_stats():
    """Drop every ``serving.*`` metric (test isolation)."""
    for key in list(serving_stats()):
        stat_reset(key)

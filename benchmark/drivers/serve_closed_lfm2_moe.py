"""Driver `serve_closed_lfm2_moe`: `serve_closed_pangu_ultra_moe`'s
measurement for the `lfm2_moe` decoder (gated short convolutions with
their tails on the device, QK-normed attention over a device-only KV
cache, every expert of a layer held), one pipeline stage of it.

That driver's loop is run as it is, from a copy of its module of our own
whose serving module is `serving_lfm2_moe` (its `Served`, warm-up,
`Sampler`, `moe_counters` and `conclude` in place of
`serving_pangu_ultra_moe`'s, which are `PanguUltraMoeConfig` and
`reference/pangu_ultra_moe.py` by name): a fixed number of callers each
post their next request when the reply to the last one arrives; the window
is `--seconds` long; the metric is the median latency from send to full
reply of the replies that arrived inside it; the device's memory peak is
read BEFORE the reference check and logged.
"""
import importlib.util
import os

from benchmark import serving_lfm2_moe


def _closed_loop():
    """A fresh copy of `serve_closed_pangu_ultra_moe` (the one the harness
    loads is left as it is) with this model's serving module bound."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "serve_closed_pangu_ultra_moe.py")
    spec = importlib.util.spec_from_file_location(
        "serve_closed_pangu_ultra_moe_for_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.serving_pangu_ultra_moe = serving_lfm2_moe
    return module


def run(run):
    _closed_loop().run(run)

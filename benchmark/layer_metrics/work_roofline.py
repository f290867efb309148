"""The least time the chip could take for the work done in the traced
slice — the larger of required FLOPs over the bf16 peak and required bytes
over the HBM peak, from benchmark/work.py — over the time it was busy."""
from benchmark import work

LAYER, SOURCE, UNIT, BETTER = "kernels", "device_trace", "%", "higher"


def reduce(run):
    if not run.work or not run.trace["busy_s"]:
        return None
    flops, bytes_moved = run.work
    least, bound = work.roofline_seconds(
        flops, bytes_moved, work.peaks(run.devices[0].device_kind))
    run.log(f"work_roofline: {flops:.4g} FLOPs and {bytes_moved:.4g} B a "
            f"chip in the slice need {least:.4f} s ({bound}-bound) of "
            f"{run.trace['busy_s']:.4f} s busy")
    return 100.0 * least / run.trace["busy_s"]

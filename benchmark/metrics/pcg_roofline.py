"""The solves' share of the HBM roofline: iterations run in the traced
window times the bytes each must stream (``benchmark.roofline``), over
the device's busy time times peak HBM bandwidth, in %. Nothing to read
where the Krylov vectors fit in VMEM."""

from benchmark import roofline


def read(view):
    if view.trace is None or not view.record.get("iters"):
        return None
    peak = roofline.peaks(view.device_kind)
    block = roofline.block_nodes(view.config["grid"], view.chips)
    per_iter = roofline.krylov_bytes_per_iter(block, peak["vmem_bytes"])
    if per_iter == 0 or view.trace["busy_s"] <= 0:
        return None
    moved = per_iter * sum(view.record["iters"])
    return 100.0 * moved / (view.trace["busy_s"] * peak["hbm_bytes_per_s"])

"""Share of the memory roofline the device programs reach over the span."""

from lib import roofline


def read(facts: dict, run: dict):
    op_s = facts.get("trace.busy_s")
    if not op_s or not run["span_row_bytes"]:
        return None
    cfg = run["config"]["transfer"]
    least = sum(roofline.least_bytes(n, cfg["cdc_min_bytes"], cfg["cdc_avg_bytes"]) for n in run["span_row_bytes"])
    return 100.0 * least / roofline.peaks_for(run["device_kind"])["hbm_bytes_per_s"] / op_s

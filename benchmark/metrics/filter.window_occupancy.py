"""Mean share of the in-flight window's slots that were taken when a
frame acquired one (``transfer_report()`` ``occupancy_avg`` over the
window's size). Source: the filter's own counters, read after the
window.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "tensor_filter + in-flight window", moves ``frames_per_s``."""


def read(run):
    rep = run["counters"].get("transfer") or {}
    if not rep.get("window") or "occupancy_avg" not in rep:
        return None
    return 100.0 * rep["occupancy_avg"] / rep["window"]

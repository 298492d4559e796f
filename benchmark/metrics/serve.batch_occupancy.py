"""Share of the dispatched bucket rows that carried a request, not
padding, inside the window (scheduler counters ``bucket_rows`` and
``rows_padded``, end of window less start).

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "serve", moves ``frames_per_s``."""


def read(run):
    a = run["counters"].get("scheduler_stats_start")
    b = run["counters"].get("scheduler_stats_end")
    if not a or not b:
        return None
    rows = b["bucket_rows"] - a["bucket_rows"]
    if rows <= 0:
        return None
    return 100.0 * (rows - (b["rows_padded"] - a["rows_padded"])) / rows

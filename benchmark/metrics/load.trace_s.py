"""Seconds of the load spent tracing: the sum of ``trace_s`` over the
records of ``transfer_report()["load"]["programs"]`` built before the
first buffer was through (``at: "load"``; one record a program,
``jit_nns_filter_prepare`` among them): the model's Python trace
(``nns.load.trace``) and JAX's trace events outside it (the second pass
over the cut program), nested events counted once. None on a program
without the block (every parent of PR 36).

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def _built(block, key):
    return sum(r[key] for r in block["programs"] if r["at"] == "load")


def read(run):
    block = _load(run)
    return None if block is None else _built(block, "trace_s")

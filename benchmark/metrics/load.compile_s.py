"""Seconds of the load in the backend compiler or, on a hit of the
persistent cache, in the cache's retrieval and the executable's load
(each record's ``cache`` says which, ``retrieval_s`` how much of it was
the read): the sum of ``compile_s`` (JAX's ``backend_compile_duration``
events, charged to the open program of their thread) over the records
of ``transfer_report()["load"]["programs"]`` with ``at: "load"``. None
on a program without the block (every parent of PR 36).

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def _built(block, key):
    return sum(r[key] for r in block["programs"] if r["at"] == "load")


def read(run):
    block = _load(run)
    return None if block is None else _built(block, "compile_s")

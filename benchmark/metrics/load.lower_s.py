"""Seconds of the load spent lowering to MLIR: the sum of ``lower_s``
(JAX's ``jaxpr_to_mlir_module_duration`` events, charged to the open
program of their thread) over the records of
``transfer_report()["load"]["programs"]`` with ``at: "load"``. None on a
program without the block (every parent of PR 36).

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def _built(block, key):
    return sum(r[key] for r in block["programs"] if r["at"] == "load")


def read(run):
    block = _load(run)
    return None if block is None else _built(block, "lower_s")

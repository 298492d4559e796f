"""What of ``load.total_s`` the other four do not name:
``total_s - (model_s + place_s) - trace - lower - compile``, the sums
over the records with ``at: "load"``: the prepare program's run, weights
still materialising, the first execution and the window's hand-over,
the element's own ``start()``. What is still unseen, as a number. None
on a program without the block (every parent of PR 36) and before a
first buffer is through.

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def _built(block, key):
    return sum(r[key] for r in block["programs"] if r["at"] == "load")


def read(run):
    block = _load(run)
    if block is None:
        return None
    return block["total_s"] - block["model_s"] - block["place_s"] - sum(
        _built(block, key) for key in ("trace_s", "lower_s", "compile_s"))

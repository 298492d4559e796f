"""Seconds of the load in the model file: ``nns.load.model`` (its
``get_model()``: in a cell, the seeded weights made on the device) plus
``nns.load.place`` (the tree's ``device_put``), ``model_s + place_s`` of
``transfer_report()["load"]``. The calling thread's time: weights still
materialising when it returns show in ``load.other_s``. None on a
program without the block (every parent of PR 36).

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def read(run):
    block = _load(run)
    return None if block is None else block["model_s"] + block["place_s"]

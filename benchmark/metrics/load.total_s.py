"""Seconds from the filter's ``start()`` (or the framework's opening,
where the fusion planner asks for it first) to its first buffer's
completion: the program's whole share of ``setup_s``, from its own
spans (``nnstreamer_tpu/obs/load.py``; ``transfer_report()["load"]``,
which every driver copies into ``info.counters.transfer``). ``setup_s``
less this and the traffic's ``ramp_s`` is the process's imports, the
harness's pool and the chip's reach. None on a program without the
block (every parent of PR 36) and before a first buffer is through.

Entry in BENCHMARK.json: unit s, better lower, source program_counter,
layer "entry + load", moves ``setup_s``."""


def _load(run):
    block = (run["counters"].get("transfer") or {}).get("load")
    return block if block and block.get("total_s") is not None else None


def read(run):
    block = _load(run)
    return None if block is None else block["total_s"]

"""Host time the filter's chain thread spends dispatching one buffer,
milliseconds: mean self time of the program's ``nns.filter.dispatch``
spans in the traced stretch (slot taken -> program enqueued and handed
to the completer), what their children cover - the input's staging,
``nns.transfer.upload`` - taken off.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "tensor_filter + in-flight window", moves
``frames_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    return progtrace.mean_ms(
        prog.self_ns(s) for s in prog.regions("nns.filter.dispatch"))

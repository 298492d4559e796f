"""Share of the traced stretch the llm scheduler's thread is inside an
admission: the time covered by the program's ``nns.llm.admit`` spans
(prefill dispatch, the paged cache's copies through the host, seating)
over the stretch. No lane decodes meanwhile.

Entry for BENCHMARK.json (the generation cell is not listed yet): unit
%, better lower, source program_counter, layer "filter backend llm",
moves ``tokens_per_s``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None or prog.window_ns <= 0 \
            or not prog.regions("nns.llm.admit"):
        return None
    return 100.0 * prog.busy_ns("nns.llm.admit") / prog.window_ns

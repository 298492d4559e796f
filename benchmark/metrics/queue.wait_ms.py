"""Time a buffer waits in the pipeline's queues, milliseconds: the
program's ``nns.queue.wait`` spans (``appsrc``'s entry queue, every
``queue`` element; a blocked ``put`` counts, the stamp is taken before
it) that ended in the traced stretch, averaged per queue element, the
elements' means added up. In a steady stream that is the mean over
buffers of a buffer's own waits; it is taken per element because one
buffer's waits lie 0.7 s apart in the listed cell and few buffers have
all of theirs inside a 1 s stretch.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "sources, converters, queue", moves
``latency_p95_ms``."""
from nnsbench import progtrace


def read(run):
    prog = progtrace.of_run(run)
    if prog is None:
        return None
    by_element = {}
    for s in prog.waits("nns.queue.wait"):
        by_element.setdefault(s.meta.get("element", ""), []).append(
            s.hi - s.lo)
    means = [progtrace.mean_ms(v) for v in by_element.values()]
    if not means or None in means:
        return None
    return sum(means)

"""What every traffic driver shares. A traffic mix is a data file
(``traffic/<mix>.json``) naming a driver ``kind`` and its parameters; the
driver is ``drivers/<kind>.py``, a file of its own found by that name,
so a new kind is a new file. A driver builds the cell's pipeline(s)
through ``parse_launch``, warms up the shapes its traffic uses, and
drives one measured window. All inputs come from the seed; every seed
gets the same sizes and arrivals in another order.

From the program a driver takes only public entry points:
``parse_launch``, ``Buffer``, element properties, and the elements'
documented counters.

A driver is ``Driver(ctx)`` with ``setup()``, ``run(window)``,
``results()`` (``attempted``, ``failed``, ``units_delivered``, latencies,
what the check compares; optionally ``quantities``: further end-to-end
numbers by the names a mix's ``end_to_end`` maps metrics to),
``teardown()``, a ``counters`` dict, and what its family's check asks of
it (``check_inputs`` or ``check_sample``)."""
from __future__ import annotations

import threading
import time

DRAIN_S = 60.0           # an answer that comes late is late, not wrong


def annotate(name):
    """A host span in the profiler's own trace (no-op cost when no trace
    is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Window:
    """Clock and bookkeeping of one measured window, shared by the
    drivers: when it began and ended, a hook that starts and stops the
    profiler inside it, and a sampler for rolling program averages."""

    def __init__(self, seconds, tracer=None):
        self.seconds = float(seconds)
        self.tracer = tracer
        self.t0 = self.t1 = None
        self._samplers = []
        self._stop = threading.Event()
        self._thread = None
        self.samples = {}

    def sample(self, name, fn):
        self._samplers.append((name, fn))
        self.samples[name] = []

    def _poll(self):
        while not self._stop.wait(0.25):
            for name, fn in self._samplers:
                v = fn()
                if v:
                    self.samples[name].append(v)

    def run(self):
        """Sleep through the window on the caller's thread; the traffic
        runs on the driver's threads."""
        self._thread = threading.Thread(target=self._poll, daemon=True,
                                        name="bench-sampler")
        self.t0 = time.perf_counter()
        self._thread.start()
        end = self.t0 + self.seconds
        if self.tracer is not None:
            self.tracer.run_inside(self.t0, end)
        while True:
            left = end - time.perf_counter()
            if left <= 0:
                break
            time.sleep(min(left, 0.05))
        self.t1 = time.perf_counter()
        self._stop.set()
        self._thread.join(5.0)

    def inside(self, t):
        return self.t0 is not None and self.t0 <= t and (
            self.t1 is None or t <= self.t1)


def wait_for(cond, seconds, what):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    raise TimeoutError(f"timed out after {seconds:.0f} s waiting for {what}")


FILTER_FAULTS = ("invoke_errors", "frames_dropped", "shed", "dropped")


def counted(after, base, keys):
    """How often the counters ``keys`` rose between two snapshots."""
    return sum(after.get(k, 0) - base.get(k, 0) for k in keys)


def tensor_caps(dtype, dims):
    return ('"other/tensors,format=static,num_tensors=1,'
            f'types=(string){dtype},dimensions=(string){dims},'
            'framerate=(fraction)0/1"')

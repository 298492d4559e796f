"""Driver kind ``token_stream_closed`` (a traffic mix names it under
``kind``; run.py loads ``drivers/<kind>.py`` and builds its ``Driver``)."""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from nnsbench.generator import (DRAIN_S, FILTER_FAULTS, annotate, counted,
                                tensor_caps, wait_for)


class Driver:
    """``token_stream_closed``: ``stream_closed``'s loop with a language
    model's frames. One pipeline; a buffer is one int32 sequence of
    ``tokens_per_buffer`` ids, uniform over the held vocabulary, from a
    pool drawn from the seed; at most ``max_outstanding`` buffers lie
    between the push and the sink, and one is pushed as soon as one has
    arrived. The sink gets three tensors a buffer (last-position logits,
    per-token log-probabilities, the expert layers' load); a frame is a
    sequence. Element names and counters are ``stream_closed``'s, so the
    same readers read both."""

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.rows = 1                     # a buffer is one sequence
        self.seq = int(t["tokens_per_buffer"])
        rng = np.random.default_rng(ctx.seed)
        self.pool = [rng.integers(0, int(ctx.sizes["vocab_size"]),
                                  self.seq, np.int32)
                     for _ in range(int(t["pool_buffers"]))]
        # which pool buffer the n-th push carries: the seed's order
        self.order = np.random.default_rng(ctx.seed + 1)
        self.pipe = None
        self.pushed = {}          # seq no -> (t_push, pool index)
        self.arrived = {}         # seq no -> (t_arrive, the three tensors)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._pusher = None
        self._credits = threading.Semaphore(int(t["max_outstanding"]))

    def setup(self):
        from nnstreamer_tpu import parse_launch
        self.pipe = parse_launch(self.ctx.traffic["pipeline"].format(
            caps=tensor_caps("int32", str(self.seq)),
            model=self.ctx.model_file))
        self.pipe["out"].connect(self._on_buffer)
        self.pipe.start()
        self._pusher = threading.Thread(target=self._push_loop, daemon=True,
                                        name="bench-push")
        self._pusher.start()
        # warm-up and ramp: the first buffer compiles the one program;
        # the window opens on a full pipeline
        wait_for(lambda: len(self.arrived) >= 1 or self._errors(),
                 self.ctx.compile_wait_s, "the first buffer")
        time.sleep(float(self.ctx.traffic["ramp_s"]))

    def _errors(self):
        return self.pipe["f"].stats["invoke_errors"]

    def _push_loop(self):
        from nnstreamer_tpu import Buffer
        seq = 0
        while not self._stop.is_set():
            with annotate("bench.wait_credit"):
                if not self._credits.acquire(timeout=0.1):
                    continue
            idx = int(self.order.integers(len(self.pool)))
            with annotate("bench.push"):
                t = time.perf_counter()
                with self._lock:
                    self.pushed[seq] = (t, idx)
                self.pipe["in"].push_buffer(
                    Buffer.from_arrays([self.pool[idx]], pts=seq))
            seq += 1

    def _on_buffer(self, buf):
        with annotate("bench.pull"):
            out = tuple(np.asarray(c.host()) for c in buf.chunks)
            t = time.perf_counter()
        with self._lock:
            self.arrived[buf.pts] = (t, out)
        self._credits.release()

    def run(self, window):
        f = self.pipe["f"]
        window.sample("filter_latency_us", f.latency_average_us)
        self.base = f.stats.snapshot()
        window.run()
        self._stop.set()
        # whatever was pushed inside the window is waited for
        due = [s for s, (t, _) in self.pushed.items() if window.inside(t)]
        try:
            wait_for(lambda: all(s in self.arrived for s in due)
                     or self._errors(), DRAIN_S, "the window's buffers")
        except TimeoutError:
            pass
        self.window = window
        self.counters = {"filter": f.stats.snapshot(),
                         "filter_base": self.base,
                         "transfer": f.transfer_report(),
                         "queue_backend": self.pipe["q0"].active_backend}

    def teardown(self):
        self._stop.set()
        if self.pipe is not None:
            # unblock a pusher stuck on the full entry, then stop
            with contextlib.suppress(Exception):
                self.pipe.stop()
            if self._pusher is not None:    # start() may have failed
                self._pusher.join(10.0)
            self.pipe = None

    def results(self):
        """Counts, latencies, the answers to compare and the expert
        layers' load of every buffer that arrived in the window."""
        w = self.window
        due = {s: v for s, v in self.pushed.items() if w.inside(v[0])}
        got = {s: self.arrived[s] for s in due if s in self.arrived}
        inside = [out for t, out in self.arrived.values() if w.inside(t)]
        lat_ms = [(got[s][0] - due[s][0]) * 1e3 for s in got]
        bad = counted(self.counters["filter"], self.counters["filter_base"],
                      FILTER_FAULTS + ("jit_recompiles",))
        return {
            "attempted": len(due),
            "failed": len(due) - len(got) + bad,
            "units_delivered": len(inside),
            "latencies_ms": lat_ms,
            "answers": [(due[s][1], got[s][1]) for s in sorted(got)],
            "expert_loads": [out[2] for out in inside if len(out) == 3],
        }

    def check_inputs(self):
        """Pool indices to compare, drawn from the seed, and their
        sequences."""
        rng = np.random.default_rng(self.ctx.seed + 2)
        n = min(int(self.ctx.traffic["check_sequences"]), len(self.pool))
        picked = sorted(int(i) for i in rng.choice(
            len(self.pool), n, replace=False))
        return picked, [self.pool[i] for i in picked]

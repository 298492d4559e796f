"""Driver kind ``token_doc_stream_closed`` (a traffic mix names it under
``kind``; run.py loads ``drivers/<kind>.py`` and builds its ``Driver``)."""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from nnsbench.generator import (DRAIN_S, FILTER_FAULTS, annotate, counted,
                                wait_for)

# two tensors a buffer: a multi-tensor caps value is quoted, the caps not
CAPS = ('other/tensors,format=static,num_tensors=2,'
        'types=(string)"int32,int32",dimensions=(string)"{seq},1",'
        'framerate=(fraction)0/1')


class Driver:
    """``token_doc_stream_closed``: ``token_stream_closed``'s loop with
    documents longer than a buffer. One pipeline, one stream; a document
    is ``buffers_per_document x tokens_per_buffer`` int32 ids, uniform
    over the held vocabulary, from a pool drawn from the seed, and goes
    down the stream as consecutive buffers in order, always whole. A
    buffer carries two tensors, its tokens and ``position0`` (its first
    token's position in its document: 0 starts a document), and the
    model carries its state from one to the next inside the filter. At
    most ``max_outstanding`` buffers lie between the push and the sink,
    and one is pushed as soon as one has arrived; which document comes
    next is the seed's order. The sink gets two tensors a buffer (the
    last position's logits, per-token log-probabilities); a frame is a
    buffer. Element names and counters are ``stream_closed``'s, so the
    same readers read both."""

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.rows = 1                     # a buffer is one sequence
        self.seq = int(t["tokens_per_buffer"])
        self.per_doc = int(t["buffers_per_document"])
        rng = np.random.default_rng(ctx.seed)
        self.pool = [rng.integers(0, int(ctx.sizes["vocab_size"]),
                                  self.seq * self.per_doc, np.int32)
                     for _ in range(int(t["pool_documents"]))]
        # which document the n-th pass carries: the seed's order
        self.order = np.random.default_rng(ctx.seed + 1)
        self.pipe = None
        self.pushed = {}     # seq no -> (t_push, document, pass, buffer)
        self.arrived = {}    # seq no -> (t_arrive, the two tensors)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._pusher = None
        self._credits = threading.Semaphore(int(t["max_outstanding"]))

    def setup(self):
        from nnstreamer_tpu import parse_launch
        self.pipe = parse_launch(self.ctx.traffic["pipeline"].format(
            caps=CAPS.format(seq=self.seq), model=self.ctx.model_file))
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
        seq = turn = 0
        while not self._stop.is_set():
            doc = int(self.order.integers(len(self.pool)))
            for k in range(self.per_doc):   # a document always whole
                with annotate("bench.wait_credit"):
                    while not self._credits.acquire(timeout=0.1):
                        if self._stop.is_set():
                            return
                with annotate("bench.push"):
                    tokens = self.pool[doc][k * self.seq:(k + 1) * self.seq]
                    t = time.perf_counter()
                    with self._lock:
                        self.pushed[seq] = (t, doc, turn, k)
                    self.pipe["in"].push_buffer(Buffer.from_arrays(
                        [tokens, np.array([k * self.seq], np.int32)],
                        pts=seq))
                seq += 1
            turn += 1

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
        due = [s for s, v in self.pushed.items() if window.inside(v[0])]
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
        """Counts, latencies and the answers to compare: for every
        buffer pushed in the window that arrived, ``((document, pass,
        buffer), its two tensors)``."""
        w = self.window
        due = {s: v for s, v in self.pushed.items() if w.inside(v[0])}
        got = {s: self.arrived[s] for s in due if s in self.arrived}
        inside = [out for t, out in self.arrived.values() if w.inside(t)]
        lat_ms = [(got[s][0] - due[s][0]) * 1e3 for s in got]
        bad = counted(self.counters["filter"], self.counters["filter_base"],
                      FILTER_FAULTS + ("jit_recompiles",))
        state = (self.counters["transfer"].get("state") or {})
        return {
            "attempted": len(due),
            "failed": len(due) - len(got) + bad + state.get("drops", 0),
            "units_delivered": len(inside),
            "latencies_ms": lat_ms,
            "answers": [(due[s][1:], got[s][1]) for s in sorted(got)],
        }

    def check_document(self, answers):
        """The document to compare: that of the first pass all of whose
        buffers were pushed in the window and arrived -> ``(index,
        tokens, {pass: [its buffers' tensors, in order]})`` with every
        such pass of that document; ``(None, None, {})`` where the
        window holds no whole pass."""
        passes = {}
        for (doc, turn, k), out in answers:
            passes.setdefault((turn, doc), {})[k] = out
        whole = {key: [bufs[k] for k in range(self.per_doc)]
                 for key, bufs in sorted(passes.items())
                 if len(bufs) == self.per_doc}
        if not whole:
            return None, None, {}
        doc = next(iter(whole))[1]
        return doc, self.pool[doc], {turn: bufs for (turn, d), bufs
                                     in whole.items() if d == doc}

"""Driver kind ``query_closed`` (a traffic mix names it under ``kind``; run.py
loads ``drivers/<kind>.py`` and builds its ``Driver``)."""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from nnsbench.generator import (DRAIN_S, FILTER_FAULTS, annotate, counted,
                                tensor_caps, wait_for)


class Driver:
    """``query_closed``: a serve pipeline answering ``clients`` in-process
    query-client pipelines over loopback, each with one request
    outstanding."""

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.n = int(t["clients"])
        hw = ctx.sizes["image_size"]
        rng = np.random.default_rng(ctx.seed)
        self.frames = rng.integers(0, 255, (int(t["pool_frames"]), hw, hw, 3),
                                   np.uint8, endpoint=True)
        pick = np.random.default_rng(ctx.seed + 2)
        self.checked = set(int(i) for i in pick.choice(
            len(self.frames), min(int(t["check_rows"]), len(self.frames)),
            replace=False))
        self.server = None
        self.clients = []
        self.sent = []            # per client: list of (t_send, frame idx)
        self.got = []             # per client: list of (t_reply, logits|None)
        self._events = []
        self._stop = threading.Event()
        self._threads = []

    def setup(self):
        from nnstreamer_tpu import parse_launch
        t = self.ctx.traffic
        hw = self.ctx.sizes["image_size"]
        self.server = parse_launch(t["server"].format(
            model=self.ctx.model_file))
        self.server.start()
        port = self.server["src"].bound_port
        # warm-up: one invoke for each bucket the batcher can form
        fw = self.server["f"].fw
        for b in t["buckets"]:
            outs = fw.invoke([np.zeros((b, hw, hw, 3), np.uint8)])
            np.asarray(outs[0])
        caps = tensor_caps("uint8", f"3:{hw}:{hw}")
        for k in range(self.n):
            cl = parse_launch(t["client"].format(caps=caps, port=port))
            self.sent.append([])
            self.got.append([])
            self._events.append(threading.Event())
            cl["out"].connect(self._on_reply(k))
            cl.start()
            self.clients.append(cl)
        self._threads = [threading.Thread(target=self._client, args=(k,),
                                          daemon=True, name=f"bench-qc{k}")
                         for k in range(self.n)]
        for th in self._threads:
            th.start()
        time.sleep(float(t["ramp_s"]))

    def _on_reply(self, k):
        def on_reply(buf):
            with annotate("bench.pull"):
                idx = self.sent[k][len(self.got[k])][1]
                out = np.asarray(buf.chunks[0].host()).reshape(-1) \
                    if idx in self.checked else None
                t = time.perf_counter()
            self.got[k].append((t, out))
            self._events[k].set()
        return on_reply

    def _client(self, k):
        from nnstreamer_tpu import Buffer
        rng = np.random.default_rng([self.ctx.seed % (1 << 32), k])
        cl, ev = self.clients[k], self._events[k]
        seq = 0
        while not self._stop.is_set():
            idx = int(rng.integers(len(self.frames)))
            ev.clear()
            with annotate("bench.push"):
                self.sent[k].append((time.perf_counter(), idx))
                cl["in"].push_buffer(Buffer.from_arrays(
                    [self.frames[idx]], pts=seq))
            seq += 1
            with annotate("bench.wait_reply"):
                while not ev.wait(0.1):
                    if self._stop.is_set() and self._abandon.is_set():
                        return

    def _errors(self):
        return self.server["f"].stats["invoke_errors"]

    def run(self, window):
        self._abandon = threading.Event()
        src, f = self.server["src"], self.server["f"]
        window.sample("filter_latency_us", f.latency_average_us)
        base = f.stats.snapshot()
        s0 = src.scheduler.stats.snapshot()
        window.run()
        s1 = src.scheduler.stats.snapshot()
        self._stop.set()
        try:
            wait_for(lambda: all(len(self.got[k]) >= len(self.sent[k])
                                 for k in range(self.n)) or self._errors(),
                     DRAIN_S, "the window's replies")
        except TimeoutError:
            pass
        self.window = window
        self.counters = {
            "filter": f.stats.snapshot(), "filter_base": base,
            "scheduler": src.scheduler.report(),
            "scheduler_stats_start": s0, "scheduler_stats_end": s1,
            "clients_shed": sum(cl["qc"].stats["shed"]
                                for cl in self.clients)}

    def teardown(self):
        self._stop.set()
        if self.server is not None:
            self._abandon.set()
            for cl in self.clients:
                with contextlib.suppress(Exception):
                    cl["in"].end_stream()
                with contextlib.suppress(Exception):
                    cl.stop()
            with contextlib.suppress(Exception):
                self.server.stop()
            for th in self._threads:
                th.join(5.0)
            self.server, self.clients = None, []

    def results(self):
        w = self.window
        attempted = answered = delivered = 0
        lat, answers = [], []
        for k in range(self.n):
            for i, (t_send, idx) in enumerate(self.sent[k]):
                reply = self.got[k][i] if i < len(self.got[k]) else None
                if reply is not None and w.inside(reply[0]):
                    delivered += 1
                if not w.inside(t_send):
                    continue
                attempted += 1
                if reply is None:
                    continue
                answered += 1
                lat.append((reply[0] - t_send) * 1e3)
                if reply[1] is not None:
                    answers.append((idx, reply[1][None, :]))
        rep = self.counters["scheduler"]
        bad = counted(self.counters["filter"], self.counters["filter_base"],
                      FILTER_FAULTS + ("jit_recompiles",))
        bad += sum(rep.get(k, 0) for k in
                   ("shed_admission", "shed_deadline", "cancelled",
                    "shed_failed", "result_errors", "invoke_errors"))
        bad += self.counters["clients_shed"]
        return {"attempted": attempted,
                "failed": attempted - answered + bad,
                "units_delivered": delivered, "latencies_ms": lat,
                "answers": answers}

    def check_inputs(self):
        idxs = sorted(self.checked)
        return [(i, 0) for i in idxs], self.frames[idxs]

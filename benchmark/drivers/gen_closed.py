"""Driver kind ``gen_closed`` (a traffic mix names it under ``kind``; run.py
loads ``drivers/<kind>.py`` and builds its ``Driver``)."""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from nnsbench.generator import (DRAIN_S, FILTER_FAULTS, annotate, counted,
                                wait_for)


class Driver:
    """``gen_closed``: ``clients`` closed-loop generation clients on one
    llm pipeline; each pushes a prompt, reads its stream to the last
    token, thinks, and pushes the next."""

    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.n = int(t["clients"])
        self.max_tokens = int(t["max_tokens"])
        self.lens = list(t["prompt_lens"])
        self.thinks = list(t["think_ms"])
        self.vocab = int(ctx.sizes["vocab_size"])
        self.pipe = None
        self.reqs = {}            # id -> dict(prompt, t_push, times, toks)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self._next_id = 0

    # -- requests from the seed ------------------------------------------
    def _plan(self, client):
        """Endless (length, think) pairs of one client. Requests go in
        rounds, one a client: a round deals the mix's lengths and think
        times out to the clients in an order drawn from the seed and the
        round's number, so with as many of each as clients every round
        is the same work for every seed, in another order."""
        seed = self.ctx.seed % (1 << 32)
        ids = np.random.default_rng([seed, 1 << 16, client])
        rnd = 0
        while True:
            deal = np.random.default_rng([seed, rnd])
            lens = deal.permutation(self.lens)
            thinks = deal.permutation(self.thinks)
            yield (int(lens[client % len(lens)]),
                   float(thinks[client % len(thinks)]), ids)
            rnd += 1

    def _submit(self, plen, rng):
        from nnstreamer_tpu import Buffer
        prompt = rng.integers(1, self.vocab, plen).astype(np.int32)
        req = {"prompt": prompt, "times": [], "toks": [],
               "done": threading.Event()}
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self.reqs[rid] = req
        with annotate("bench.push"):
            req["t_push"] = time.perf_counter()
            self.pipe["in"].push_buffer(Buffer.from_arrays([prompt], pts=rid))
        return req

    def _client(self, k):
        for plen, think_ms, rng in self._plan(k):
            if self._stop.is_set():
                return
            req = self._submit(plen, rng)
            while not req["done"].wait(0.1):
                if self._stop.is_set() and self._abandon.is_set():
                    return
            with annotate("bench.think"):
                if self._stop.wait(think_ms / 1e3):
                    return

    def _on_token(self, buf):
        with annotate("bench.pull"):
            tok = int(np.asarray(buf.chunks[0].host()).reshape(-1)[0])
            t = time.perf_counter()
        req = self.reqs.get(buf.pts)
        if req is None:
            return
        req["times"].append(t)
        req["toks"].append(tok)
        if len(req["toks"]) >= self.max_tokens:
            req["done"].set()

    def setup(self):
        from nnstreamer_tpu import parse_launch
        self._abandon = threading.Event()
        self.pipe = parse_launch(self.ctx.traffic["pipeline"].format(
            model=self.ctx.model_file))
        self.pipe["out"].connect(self._on_token)
        self.pipe.start()
        # warm-up: one prompt of every length the mix holds, so every
        # prefill bucket, block count and the chunk program are compiled
        rng = np.random.default_rng([self.ctx.seed % (1 << 32), 1 << 20])
        warm = [self._submit(n, rng) for n in self.lens]
        wait_for(lambda: all(r["done"].is_set() for r in warm)
                 or self._errors(), self.ctx.compile_wait_s,
                 "the warm-up streams")
        self.n_warm = len(warm)
        self._threads = [threading.Thread(target=self._client, args=(k,),
                                          daemon=True, name=f"bench-gen{k}")
                         for k in range(self.n)]
        for th in self._threads:
            th.start()
        time.sleep(float(self.ctx.traffic["ramp_s"]))

    def _errors(self):
        return self.pipe["f"].stats["invoke_errors"]

    def run(self, window):
        f = self.pipe["f"]
        llm0 = f.fw.stats.snapshot()
        base = f.stats.snapshot()
        window.run()
        llm1 = f.fw.stats.snapshot()
        self._stop.set()
        due = [r for r in self.reqs.values()
               if window.inside(r.get("t_push", -1.0))]
        try:
            wait_for(lambda: all(r["done"].is_set() for r in due)
                     or self._errors(), DRAIN_S, "the window's streams")
        except TimeoutError:
            pass
        self.window = window
        self.counters = {"filter": f.stats.snapshot(), "filter_base": base,
                         "llm_start": llm0, "llm_end": llm1}

    def teardown(self):
        self._stop.set()
        if self.pipe is not None:
            self._abandon.set()
            with contextlib.suppress(Exception):
                self.pipe["in"].end_stream()
            with contextlib.suppress(Exception):
                self.pipe.stop()
            for th in self._threads:
                th.join(5.0)
            self.pipe = None

    def results(self):
        w = self.window
        due = {i: r for i, r in self.reqs.items()
               if w.inside(r.get("t_push", -1.0))}
        done = {i: r for i, r in due.items()
                if len(r["toks"]) >= self.max_tokens}
        delivered, work, gaps = 0, [], []
        for r in self.reqs.values():
            inside = [t for t in r["times"] if w.inside(t)]
            if not inside:
                continue
            delivered += len(inside)
            first_in = bool(r["times"]) and w.inside(r["times"][0])
            work.append((len(r["prompt"]) if first_in else 0, len(inside)))
            ts = r["times"]
            gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])
                     if w.inside(a) and w.inside(b)]
        bad = counted(self.counters["filter"], self.counters["filter_base"],
                      FILTER_FAULTS)
        return {
            "attempted": len(due),
            "failed": len(due) - len(done) + bad,
            "units_delivered": delivered,
            "first_latencies_ms": [(r["times"][0] - r["t_push"]) * 1e3
                                   for r in due.values() if r["times"]],
            "pushed_at_s": [r["t_push"] - w.t0
                            for r in due.values() if r["times"]],
            "token_gaps_ms": gaps,
            "work": work,
            "finished": [(r["prompt"], list(r["toks"][:self.max_tokens]))
                         for _, r in sorted(done.items())],
        }

    def check_sample(self, results):
        """A seeded sample of the finished requests, the longest prompt
        among them."""
        fin = results["finished"]
        if not fin:
            return []
        n = min(int(self.ctx.traffic["check_requests"]), len(fin))
        longest = max(range(len(fin)), key=lambda i: len(fin[i][0]))
        rng = np.random.default_rng(self.ctx.seed + 2)
        rest = [i for i in rng.permutation(len(fin)) if i != longest]
        return [fin[i] for i in [longest] + rest[:n - 1]]

#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the main paths once, through the entry points a user
calls (``import nnstreamer_tpu``, ``parse_launch``, element properties),
at the full width of models the repo already has, with random weights
made from a seed:

  label    the README pipeline: MobileNet-v2 -> image_labeling, default
           properties, then the properties the benchmark's stream
           cells set (prefetch-host, in-flight window) and input donation
  serve    tensor_serve_src ! ViT-B/16 ! tensor_serve_sink answering four
           in-process tensor_query_client pipelines over loopback
  decode   paged continuous-batching decode on the largest decoder the
           repo configures (1.0 B parameters), 8 prompts of 16-64 tokens
  kernels  both Pallas kernels, compiled by Mosaic, against their oracles
  mesh4    (four or more devices) the serve phase sharded over a 4x1x1
           mesh, and one train step on a dp=2 x tp=2 mesh

Every phase checks counts (delivered == pushed; every error, drop and
shed counter 0), that the fast path ran and not a stand-in, that nothing
compiled after warm-up, and one numeric comparison against a computation
that does not go through the path under test. The run stops non-zero at
the first failure. Per phase it prints seconds compiling and seconds
running, so a cold and a warm compile cache can be told apart.

It refuses to run unless JAX's platform is ``tpu``, and then prints as
the last line of stdout

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--rehearsal`` is for a sandbox without a chip: tiny zoo sizes, CPU
allowed, kernels through the Pallas interpreter, every line prefixed
``REHEARSAL`` so no line of it can be taken for a pass on the chip.
``--rehearsal=MODEL`` also swaps the label phase's model (how the tests
show that a model whose invokes fail ends the run non-zero).
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import io
import json
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.parse

# the whole run, compilation included, must end inside the driver's 1200 s:
# a hang dumps every thread's stack and exits non-zero instead
DEADLINE_S = 1150
WAIT_S = 600          # any single wait for frames / replies / tokens

# -- sizes: (real, rehearsal) --------------------------------------------
LABEL_MODEL = ("zoo://mobilenet_v2",
               "zoo://mobilenet_v2?width=0.35&size=32&num_classes=11")
SERVE_MODEL = ("zoo://vit",
               "zoo://vit?size=32&patch=16&d_model=64&layers=2&heads=4"
               "&classes=16")
# a 1.0 B-parameter decoder at the zoo's widths
DECODE_MODEL = ("zoo://gpt?vocab=32000&d_model=1536&n_heads=16&n_layers=24",
                "zoo://gpt?vocab=256&d_model=64&n_heads=4&n_layers=2")
ATTN_SHAPE = ((8, 196, 12, 64), (1, 20, 2, 8))       # ViT-B/16: [B,S,H,D]
FRAME_HW = (224, 32)           # label frames and the normalize kernel

# -- tolerances, each with its reason --------------------------------------
# label: exact. The reference jits the same apply_fn at the same shape on
# the same device, so XLA builds the same program; a different label means
# the pipeline fed other bytes or ran another program.
# serve / mesh4: the encoder computes in bfloat16 (8 bits of mantissa),
# and a batch of 1, 2, 4 or 8 rows (or one sharded 4 ways) compiles to a
# different tiling and accumulation order than the reference's one batch
# of 32, so logits agree to a few bf16 roundings accumulated over the
# depth — allowed: this share of the reference logits' range.
LOGITS_TOL = 0.05
# decode: greedy sampling emits argmax(logits). The path under test
# prefills a bucket-padded prompt into a cache; the reference runs the
# plain forward. Same bf16 arithmetic, different programs: the emitted
# token's reference logit must be within this share of the reference
# logits' range of the reference maximum (0 when they agree exactly).
TOKEN_TOL = 0.02
# kernels: bf16 output of an f32 computation — one rounding, 2^-8 relative.
KERNEL_TOL = 2.0 ** -7


class SmokeFailure(Exception):
    """A phase's check did not hold; the run ends non-zero."""


class Smoke:
    def __init__(self, rehearsal):
        """``rehearsal``: None on the chip; "" or a label-phase model
        URI for a sandbox run."""
        self.rehearsal = rehearsal is not None
        self.size = 1 if self.rehearsal else 0
        self.label_model = rehearsal or LABEL_MODEL[self.size]
        self.compile_s = 0.0
        self.steady_s = 0.0        # inside steady(): nothing compiles
        self.compiles = 0          # backend compile requests (hits too)
        self.cache_hits = 0
        self.cache_writes = 0
        self.serve_one_chip = None  # frames + replies kept for mesh4

    # -- output -----------------------------------------------------------
    def say(self, line):
        print(("REHEARSAL " if self.rehearsal else "") + line, flush=True)

    def check(self, ok, what):
        if not ok:
            raise SmokeFailure(what)

    # -- compile accounting (jax.monitoring) --------------------------------
    def watch_compiles(self):
        from jax import monitoring

        def on_duration(event, duration, **_):
            if event.startswith("/jax/core/compile/"):
                # trace + lowering + backend compile (a persistent-cache
                # hit is a short backend_compile: the retrieval)
                self.compile_s += duration
                if event.endswith("backend_compile_duration"):
                    self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_writes += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        c0, h0, w0 = self.compile_s, self.cache_hits, self.cache_writes
        r0 = self.steady_s
        notes = []
        try:
            yield notes
        except SmokeFailure as exc:
            self.say(f"{name}: FAILED — {exc}")
            raise
        # compile_s sums trace + lowering + backend compile over all
        # threads; run_s is the time inside the warmed-up windows
        self.say(f"{name}: ok  wall_s={time.perf_counter() - t0:.1f} "
                 f"compile_s={self.compile_s - c0:.1f} "
                 f"run_s={self.steady_s - r0:.2f} "
                 f"cache_hits={self.cache_hits - h0} "
                 f"cache_writes={self.cache_writes - w0}"
                 + "".join(f"  {n}" for n in notes))

    @contextlib.contextmanager
    def steady(self, what):
        """Nothing may compile inside: not a filter's frame-path
        recompile, not one eager op of a new shape."""
        n0, t0 = self.compiles, time.perf_counter()
        yield
        self.steady_s += time.perf_counter() - t0
        self.check(self.compiles == n0,
                   f"{self.compiles - n0} compilation(s) after warm-up "
                   f"in {what}")

    # -- helpers ------------------------------------------------------------
    @staticmethod
    def zoo_reference(uri):
        """(jitted apply_fn, params) built straight from the zoo — the
        computation that does not go through a pipeline."""
        import jax
        from nnstreamer_tpu.models import zoo
        parsed = urllib.parse.urlparse(uri)
        if parsed.scheme != "zoo":
            raise SmokeFailure(f"no reference for non-zoo model {uri!r}")
        kwargs = {k: v[0] for k, v in
                  urllib.parse.parse_qs(parsed.query).items()}
        apply_fn, params, in_info, _ = zoo.build(
            parsed.netloc or parsed.path.lstrip("/"), **kwargs)
        return jax.jit(apply_fn), params, tuple(in_info[0].shape)

    def wait_for(self, cond, what):
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            if cond():
                return
            time.sleep(0.02)
        raise SmokeFailure(f"timed out waiting for {what}")

    def check_clean(self, name, stats, keys=("invoke_errors",
                                             "frames_dropped", "shed",
                                             "dropped", "jit_recompiles")):
        bad = {k: stats[k] for k in keys if stats.get(k)}
        self.check(not bad, f"{name} counters not zero: {bad}")

    # -- label ----------------------------------------------------------------
    def run_label(self, frames, shape, labels_path, props):
        import numpy as np
        from nnstreamer_tpu import Buffer, parse_launch
        h, w, c = shape
        pipe = parse_launch(
            'appsrc name=in caps="other/tensors,format=static,'
            f'num_tensors=1,types=(string)uint8,dimensions=(string)'
            f'{c}:{w}:{h},framerate=(fraction)0/1" '
            "! queue name=q max-size-buffers=4 "
            f'! tensor_filter name=f framework=jax model="{self.label_model}"'
            f" latency=1 {props} "
            f"! tensor_decoder name=d mode=image_labeling "
            f"option1={labels_path} "
            "! appsink name=out")
        pipe.start()
        try:
            # warm-up: the first frame compiles this property set's program
            pipe["in"].push_buffer(Buffer.from_arrays([frames[0]]))
            self.wait_for(lambda: len(pipe["out"].buffers) >= 1
                          or pipe["f"].stats["invoke_errors"],
                          "the label warm-up frame")
            snap = pipe["f"].stats.snapshot()
            self.check(pipe["out"].buffers and not snap["invoke_errors"],
                       f"label: the warm-up frame was dropped "
                       f"(invoke_errors={snap['invoke_errors']}, "
                       f"frames_dropped={snap['frames_dropped']}) — see "
                       f"the filter's warning above")
            base = snap.get("jit_recompiles", 0)
            with self.steady(f"label ({props or 'defaults'})"):
                for f in frames:
                    pipe["in"].push_buffer(Buffer.from_arrays([f]))
                pipe["in"].end_stream()
                self.check(pipe.wait_eos(WAIT_S), "label: no EOS")
        finally:
            pipe.stop()
        stats = pipe.stats()
        got = [bytes(np.asarray(b.chunks[0].host()).tobytes()).decode()
               for b in pipe["out"].buffers][1:]
        self.check(len(got) == len(frames),
                   f"label delivered {len(got)} of {len(frames)} frames "
                   f"(invoke_errors={stats['f']['invoke_errors']})")
        stats["f"]["jit_recompiles"] = \
            stats["f"].get("jit_recompiles", 0) - base
        self.check_clean("label tensor_filter", stats["f"])
        return got, pipe

    def phase_label(self, tmp):
        import numpy as np
        with self.phase("label") as notes:
            shape = (FRAME_HW[self.size], FRAME_HW[self.size], 3)
            rng = np.random.default_rng(21)
            frames = [rng.integers(0, 255, shape, np.uint8, endpoint=True)
                      for _ in range(8)]
            labels_path = os.path.join(tmp, "labels.txt")
            with open(labels_path, "w") as f:
                f.write("".join(f"class-{i}\n" for i in range(1001)))
            plain, pipe = self.run_label(frames, shape, labels_path, "")
            plan = pipe._fusion_plan.summary()
            # filter ! image_labeling does not fuse: the decoder has no
            # device program, so the filter is a run of one
            self.check(not plan["segments"]
                       and "run of 1" in plan["vetoes"].get("f", ""),
                       f"label fusion plan is not the expected one: {plan}")
            notes.append(f"queue={pipe['q'].active_backend}")
            notes.append("fusion=none (" + plan["vetoes"]["d"] + ")")
            fast, pipe = self.run_label(
                frames, shape, labels_path,
                "prefetch-host=true in-flight=4 donate-input=true")
            win = pipe["f"].transfer_report()
            self.check(win.get("window") == 4
                       and win.get("completed") == len(frames) + 1
                       and not win.get("errors"),
                       f"label: the in-flight window did not run: {win}")
            notes.append(f"window_peak={win['in_flight_peak']}")
            self.check(plain == fast,
                       "label: default and windowed+donated runs differ: "
                       f"{plain} vs {fast}")
            fn, params, _ = self.zoo_reference(self.label_model)
            ref = np.stack([np.asarray(fn(params, f)) for f in frames])
            self.check(np.isfinite(ref).all(), "label: non-finite logits")
            want = [f"class-{int(i)}" for i in ref.argmax(-1)]
            self.check(plain == want,
                       f"label: pipeline {plain} != reference {want}")
            notes.append(f"frames={len(frames)}x2 labels={sorted(set(want))}")

    # -- serve (also mesh4's serve half) -----------------------------------
    def serve_round(self, name, mesh, frames, ref):
        import numpy as np
        from nnstreamer_tpu import Buffer, parse_launch
        model = SERVE_MODEL[self.size]
        h, w, c = frames[0].shape
        sid = 71 if mesh else 70
        server = parse_launch(
            f"tensor_serve_src name=src port=0 id={sid} "
            f"buckets=1,2,4,8 max-wait-ms=5 max-queue=64 "
            + (f"mesh={mesh} " if mesh else "")
            + f'! tensor_filter name=f framework=jax model="{model}" '
            + (f"custom=mesh:{mesh} " if mesh else "")
            + f"! tensor_serve_sink id={sid}")
        server.start()
        port = server["src"].bound_port
        n_clients, per_client = 4, len(frames) // 4
        clients = []
        try:
            # warm-up: one invoke per bucket the scheduler can form, so
            # whatever batches the clients' timing produces are compiled
            fw = server["f"].fw
            buckets = server["src"].scheduler.batcher.buckets
            for b in buckets:
                outs = fw.invoke([np.zeros((b, h, w, c), np.uint8)])
            if mesh:
                ids = {sh.device.id for sh in outs[0].addressable_shards}
                mesh_ids = {d.id for d in fw.mesh.devices.ravel()}
                self.check(len(ids) == 4 and ids == mesh_ids,
                           f"{name}: output shards on devices {ids}, "
                           f"mesh is {mesh_ids}")
            caps = ('"other/tensors,format=static,num_tensors=1,'
                    f'types=(string)uint8,dimensions=(string){c}:{w}:{h},'
                    'framerate=(fraction)0/1"')
            for _ in range(n_clients):
                cl = parse_launch(
                    f"appsrc name=in caps={caps} "
                    f"! tensor_query_client name=qc port={port} "
                    f"timeout=120 max-request={per_client} "
                    "! appsink name=out")
                cl.start()
                clients.append(cl)
            with self.steady(name):
                def push(k):
                    for i in range(per_client):
                        clients[k]["in"].push_buffer(Buffer.from_arrays(
                            [frames[k * per_client + i]], pts=i))
                threads = [threading.Thread(target=push, args=(k,))
                           for k in range(n_clients)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(WAIT_S)
                self.wait_for(
                    lambda: all(len(cl["out"].buffers)
                                + cl["qc"].stats["shed"] >= per_client
                                for cl in clients), f"{name} replies")
            rep = server["src"].scheduler.report()
            fstats = server["f"].stats.snapshot()
            got = np.stack([np.asarray(b.chunks[0].host()).reshape(-1)
                            for cl in clients for b in cl["out"].buffers])
            shed = sum(cl["qc"].stats["shed"] for cl in clients)
        finally:
            for cl in clients:
                cl["in"].end_stream()
                cl.stop()
            server.stop()
        n = len(frames)
        self.check(got.shape[0] == n and shed == 0,
                   f"{name}: {got.shape[0]} replies + {shed} shed of {n}")
        self.check(rep["requests"] == n and rep["completed"] == n,
                   f"{name}: scheduler saw {rep['requests']} requests, "
                   f"completed {rep['completed']} of {n}")
        self.check_clean(f"{name} scheduler", rep,
                         ("shed_admission", "shed_deadline", "cancelled",
                          "shed_failed", "result_errors", "invoke_errors"))
        self.check_clean(f"{name} tensor_filter", fstats)
        self.check(rep["batches"] < n,
                   f"{name}: {rep['batches']} batches for {n} requests — "
                   "no batch had more than one row")
        if mesh:
            self.check(rep.get("placed_batches", 0) > 0
                       and rep.get("devices") == 4,
                       f"{name}: scheduler placed "
                       f"{rep.get('placed_batches')} batches on "
                       f"{rep.get('devices')} devices")
        self.check(np.isfinite(got).all(), f"{name}: non-finite logits")
        span = float(ref.max() - ref.min())
        err = float(np.abs(got - ref).max())
        self.check(got.shape == ref.shape and err <= LOGITS_TOL * span,
                   f"{name}: replies differ from the reference by {err:.4g} "
                   f"(range {span:.4g}, allowed {LOGITS_TOL:.0%})")
        return got, (f"replies={n} batches={rep['batches']} "
                     f"occupancy={rep['occupancy_avg']:.2f} "
                     f"max_err={err / span:.2%}_of_range")

    def phase_serve(self):
        import numpy as np
        with self.phase("serve") as notes:
            fn, params, shape = self.zoo_reference(SERVE_MODEL[self.size])
            rng = np.random.default_rng(22)
            frames = [rng.integers(0, 255, shape, np.uint8, endpoint=True)
                      for _ in range(32)]
            ref = np.asarray(fn(params, np.stack(frames)))
            got, note = self.serve_round("serve", "", frames, ref)
            notes.append(note)
            self.serve_one_chip = (frames, got)

    # -- decode ---------------------------------------------------------------
    def phase_decode(self):
        import jax
        import numpy as np
        from nnstreamer_tpu import Buffer, parse_launch
        from nnstreamer_tpu.filters.kvpool import POOL_TABLE
        from nnstreamer_tpu.models import transformer as tfm
        with self.phase("decode") as notes:
            model = DECODE_MODEL[self.size]
            q = {k: int(v[0]) for k, v in urllib.parse.parse_qs(
                urllib.parse.urlparse(model).query).items()}
            max_tokens, lens = 32, (16, 24, 32, 40, 48, 56, 64, 20)
            pipe = parse_launch(
                'appsrc name=in caps="other/tensors,format=flexible" '
                f'! tensor_filter name=f framework=llm model="{model}" '
                'invoke-async=true invoke-dynamic=true '
                f'custom="paged:true,n_parallel:8,chunk:8,'
                f'max_tokens:{max_tokens},max_len:128,block_size:16,'
                f'pool_blocks:64" '
                "! appsink name=out")
            pools_before = set(POOL_TABLE)
            pipe.start()
            try:
                def one_pass(seed):
                    rng = np.random.default_rng(seed)
                    prompts = [rng.integers(1, q["vocab"], n).astype(np.int32)
                               for n in lens]
                    n0 = len(pipe["out"].buffers)
                    for i, p in enumerate(prompts):
                        pipe["in"].push_buffer(Buffer.from_arrays(
                            [p], pts=seed * 1000 + i))
                    want = n0 + len(prompts) * max_tokens
                    self.wait_for(
                        lambda: len(pipe["out"].buffers) >= want
                        or pipe["f"].stats["invoke_errors"],
                        "decode tokens")
                    streams = {}
                    for b in pipe["out"].buffers[n0:]:
                        streams.setdefault(b.pts, []).append(
                            int(np.asarray(b.chunks[0].host()).reshape(-1)[0]))
                    return prompts, [streams.get(seed * 1000 + i, [])
                                     for i in range(len(prompts))]

                one_pass(31)        # warm-up: compiles every shape used
                with self.steady("decode"):
                    prompts, streams = one_pass(32)  # same lengths, new ids
                fstats = pipe["f"].stats.snapshot()
                lstats = pipe["f"].fw.stats.snapshot()
                (pool_name,) = set(POOL_TABLE) - pools_before
                def pool_settled():
                    d = POOL_TABLE[pool_name].stats_dict()
                    return d["blocks_used"] == d["blocks_cached"]

                # a stream's blocks go back when its last token is out,
                # just after the sink saw it
                self.wait_for(pool_settled,
                              "the KV pool to hold no stream's blocks")
                pool = POOL_TABLE[pool_name].stats_dict()
            finally:
                pipe["in"].end_stream()
                pipe.stop()
            self.check(all(len(s) == max_tokens for s in streams),
                       f"decode delivered {[len(s) for s in streams]} tokens "
                       f"per stream, wanted {max_tokens} "
                       f"(invoke_errors={fstats['invoke_errors']})")
            self.check_clean("decode tensor_filter", fstats)
            self.check(lstats["prefill_dispatches"] == 2 * len(lens)
                       and lstats["decode_dispatches"] > 0
                       and pool["alloc_failures"] == 0,
                       f"decode path counters: {lstats} {pool}")
            # reference: plain forward over the prompts, padded to one
            # shape (causal: the logits at a prompt's last token do not
            # see the padding after it)
            cfg = tfm.GPTConfig(vocab=q["vocab"], d_model=q["d_model"],
                                n_heads=q["n_heads"], n_layers=q["n_layers"])
            params = tfm.init_params(cfg, jax.random.PRNGKey(0))
            padded = np.zeros((len(lens), max(lens)), np.int32)
            for i, p in enumerate(prompts):
                padded[i, :p.size] = p
            logits = np.asarray(jax.jit(
                lambda p, t: tfm.forward(p, t, cfg))(params, padded))
            worst = 0.0
            for i, p in enumerate(prompts):
                row = logits[i, p.size - 1]
                self.check(np.isfinite(row).all(),
                           "decode: non-finite reference logits")
                gap = float(row.max() - row[streams[i][0]]) \
                    / float(row.max() - row.min())
                worst = max(worst, gap)
            self.check(worst <= TOKEN_TOL,
                       f"decode: a first token sits {worst:.2%} of the "
                       f"logit range below the reference argmax "
                       f"(allowed {TOKEN_TOL:.0%})")
            notes.append(
                f"tokens={sum(map(len, streams))}x2 "
                f"prefills={lstats['prefill_dispatches']} "
                f"decode_dispatches={lstats['decode_dispatches']} "
                f"decode_steps={lstats['decode_steps']} "
                f"pool_used={pool['blocks_used']}(all prefix-cache) "
                f"first_token_gap={worst:.2%}")

    # -- kernels ----------------------------------------------------------------
    def phase_kernels(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from nnstreamer_tpu.ops import fused_normalize, normalize_reference
        from nnstreamer_tpu.ops.attention import (fused_attention,
                                                  reference_attention)
        with self.phase("kernels") as notes:
            def compiled(fn, *args):
                # on the chip the kernel must reach Mosaic, not the
                # interpreter and not the oracle
                text = jax.jit(fn).lower(*args).as_text()
                self.check(self.rehearsal or "tpu_custom_call" in text,
                           f"{fn.__name__} did not lower to a Mosaic "
                           f"kernel")

            rng = np.random.default_rng(23)
            q, k, v = (jnp.asarray(rng.standard_normal(
                ATTN_SHAPE[self.size]), jnp.bfloat16) for _ in range(3))
            compiled(fused_attention, q, k, v)
            got = np.asarray(fused_attention(q, k, v), np.float32)
            ref = np.asarray(reference_attention(q, k, v), np.float32)
            err = float(np.abs(got - ref).max() / np.abs(ref).max())
            self.check(np.isfinite(got).all() and err <= 4 * KERNEL_TOL,
                       f"fused_attention off its oracle by {err:.3g}")
            notes.append(f"attention{ATTN_SHAPE[self.size]} err={err:.2g}")
            hw = FRAME_HW[self.size]
            for batch in (1, 32):
                x = jnp.asarray(rng.integers(
                    0, 255, (batch, hw, hw, 3), np.uint8, endpoint=True))
                compiled(fused_normalize, x)
                got = np.asarray(fused_normalize(x), np.float32)
                ref = np.asarray(normalize_reference(
                    x, 1.0 / 127.5, 127.5), np.float32)
                err = float(np.abs(got - ref).max())
                self.check(err <= KERNEL_TOL,
                           f"fused_normalize batch {batch} off its oracle "
                           f"by {err:.3g}")
                notes.append(f"normalize[{batch}x{hw}x{hw}x3] err={err:.2g}")
            notes.append("mode=" + ("interpreted" if self.rehearsal
                                    else "mosaic"))

    # -- mesh4 ------------------------------------------------------------------
    def phase_mesh4(self, n_devices):
        import numpy as np
        if n_devices < 4:
            self.say(f"mesh4: not run ({n_devices} device)")
            return
        from nnstreamer_tpu.parallel import dryrun
        from nnstreamer_tpu.parallel.mesh import make_mesh
        with self.phase("mesh4") as notes:
            frames, one_chip = self.serve_one_chip
            _, note = self.serve_round("mesh4", "4x1x1", frames, one_chip)
            notes.append(note.replace("max_err", "vs_one_chip_err"))
            mesh = make_mesh((2, 1, 2))
            ids = sorted(d.id for d in mesh.devices.ravel())
            self.check(len(set(ids)) == 4, f"mesh4: train mesh on {ids}")
            said = io.StringIO()
            with contextlib.redirect_stdout(said):
                loss4 = dryrun.train_step(mesh)
                loss1 = dryrun.train_step(make_mesh((1, 1, 1)))
            for line in said.getvalue().splitlines():
                self.say(line)
            # one bf16 forward/backward, sharded or not: same math,
            # another reduction order
            self.check(np.isfinite(loss4)
                       and abs(loss4 - loss1) <= 0.02 * abs(loss1),
                       f"mesh4: train loss {loss4} on dp=2 x tp=2 vs "
                       f"{loss1} on one device")
            notes.append(f"train dp=2 x tp=2 on devices {ids} "
                         f"loss={loss4:.4f} (one device {loss1:.4f})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearsal", nargs="?", const="", default=None,
                    metavar="LABEL_MODEL",
                    help="sandbox run: tiny sizes, CPU allowed, every line "
                         "prefixed REHEARSAL; an optional value replaces "
                         "the label phase's model")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax
    smoke = Smoke(args.rehearsal)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    smoke.say(f"device: platform={device['platform']} "
              f"kind={device['kind']!r} count={device['count']}")
    if not smoke.rehearsal and dev.platform != "tpu":
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — "
              "refusing to run (use --rehearsal in a sandbox)",
              file=sys.stderr)
        return 2

    from nnstreamer_tpu.utils import hw
    if not smoke.rehearsal:
        # the one chip this round targets; an unknown kind raises in hw
        peaks = (hw.peak_flops(dev), hw.peak_membw(dev))
        if peaks != (197e12, 819e9):
            print(f"chip_smoke: {dev.device_kind!r} resolves to peaks "
                  f"{peaks}, not the v5e row (197 TFLOP/s, 819 GB/s)",
                  file=sys.stderr)
            return 2
        smoke.say(f"peaks: {peaks[0] / 1e12:.0f} TFLOP/s bf16, "
                  f"{peaks[1] / 1e9:.0f} GB/s (utils/hw.py, v5e row)")
    smoke.watch_compiles()

    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            smoke.phase_label(tmp)
            # the program places the cache itself, at the first open
            cache_dir = jax.config.jax_compilation_cache_dir
            smoke.check(cache_dir, "no persistent compile cache after "
                                   "the first filter opened")
            smoke.say(f"compile cache: {cache_dir} (JAX_COMPILATION_CACHE"
                      f"_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')})")
            smoke.phase_serve()
            smoke.phase_decode()
            smoke.phase_kernels()
            smoke.phase_mesh4(device["count"])
    except SmokeFailure:
        return 1               # the phase said why
    smoke.say(f"total: {time.perf_counter() - t0:.1f} s, "
              f"{smoke.compile_s:.1f} s compiling, "
              f"{smoke.cache_hits} cache hits, "
              f"{smoke.cache_writes} cache writes")
    smoke.say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as exc:          # argparse
        code = exc.code
    except BaseException:              # noqa: BLE001 — a phase blew up
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of stopped pipelines must not keep the process (and
    # with it the chip) alive
    os._exit(code)

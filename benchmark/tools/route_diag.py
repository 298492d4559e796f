#!/usr/bin/env python3
"""Where does an ``afmoe`` cell's last row part from the reference's? For
each seed: the cell's weights and check sequences (no pipeline, no
window), the program's forward pass in its own precision and the plain
reference in float32, each walked once with the router's choices and the
stream after every layer tapped where the two modules make them (the
modules' own functions, wrapped for the walk; nothing of either is
written again here). A line a sequence: per expert layer how many of the
tokens the program routes to another set of experts than the reference,
whether the last token is one of them, how far the reference's last
chosen and first unchosen biased score of that token lie apart, and the
last token's stream after each layer, program against reference (norm of
the difference over the reference's norm); then the last row's
``logit_rms`` / ``logit_gap`` as the check reads them. A diagnostic
(PERF.md section 2); it decides nothing.

    python3 benchmark/tools/route_diag.py --workload <cell> --seeds 11,12 \\
        [--rehearsal] [--out chiprun_out/route_diag.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


class _Taps:
    """``module.name`` wrapped for the length of a ``with``: ``keep``
    sees each call's arguments and result."""

    def __init__(self, *taps):
        self.taps, self.saved = taps, []

    def __enter__(self):
        for module, name, keep in self.taps:
            inner = getattr(module, name)
            self.saved.append((module, name, inner))

            def outer(*a, _inner=inner, _keep=keep, **kw):
                out = _inner(*a, **kw)
                _keep(a, kw, out)
                return out
            setattr(module, name, outer)

    def __exit__(self, *exc):
        for module, name, inner in self.saved:
            setattr(module, name, inner)


def walk_program(weights, tokens, cfg):
    """``(outputs, choices [expert layers, S, top], last token's stream
    after each layer [layers, d])`` of ``models/afmoe.py::forward``, one
    jitted program."""
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import afmoe

    def tapped(params, toks):
        choices, streams = [], []
        with _Taps((afmoe, "sigmoid_route",
                    lambda a, kw, out: choices.append(out[0])),
                   (afmoe, "ffn",
                    lambda a, kw, out: streams.append(out[0][-1]))):
            out = afmoe.forward(params, toks[None], cfg)
        return out, jnp.stack(choices), jnp.stack(streams)

    return jax.jit(tapped)(weights, tokens)


def walk_reference(weights, tokens, sizes):
    """The same of ``refs/afmoe.py::forward`` in float32: ``(outputs,
    chosen bool [expert layers, S, router], the router's normed inputs'
    last rows, last token's stream after each layer)``."""
    import numpy as np
    from refs import afmoe as ref
    chosen, routed, streams = [], [], []

    def entering(a, kw, out):          # a layer's input is the last one's
        streams.append(np.asarray(a[0][-1]))

    with _Taps((ref, "_route", lambda a, kw, out: (
                    routed.append(np.asarray(out[0][-1])),
                    chosen.append(np.asarray(out[1])))),
               (ref, "_attention", entering), (ref, "_head", entering)):
        out = ref.forward(weights, tokens, sizes, "f32")
    return out, np.stack(chosen), routed, np.stack(streams[1:])


def main():
    import numpy as np
    import run as bench_run
    from nnsbench import session
    from nnsbench.compare import Rows
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    workload = bench_run.load_json("workloads", args.workload + ".json")
    config = bench_run.load_json("configs", workload["config"] + ".json")
    traffic = bench_run.load_json("traffic", workload["traffic"] + ".json")
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = bench_run.Ctx(workload, config, traffic, workload, seed,
                            args.rehearsal)
        ses = session.activate(session.Session(
            config, ctx.sizes, ctx.seed, ctx.traffic))
        try:
            import jax.numpy as jnp
            from nnstreamer_tpu.models import afmoe
            scope = {}
            with open(ctx.model_file) as f:
                exec(compile(f.read(), ctx.model_file, "exec"), scope)
            scope["get_model"]()          # makes the seed's weights
            sizes = {**config, **ctx.sizes}
            held = int(sizes["num_experts"]) // int(sizes["expert_parallel"])
            cfg = afmoe.AfmoeConfig.from_hf(
                sizes, held_first=held * int(sizes["expert_rank"]),
                held_count=held, dtype=jnp.bfloat16)
            driver = bench_run.load_part(
                "drivers", traffic["kind"], "Driver")(ctx)
            top = cfg.num_experts_per_tok
            moe_layers = [l for l in ses.weights["layers"] if "moe" in l]
            for i, seq in zip(*driver.check_inputs()):
                got, choice, p_stream = walk_program(
                    ses.weights, jnp.asarray(seq, jnp.int32), cfg)
                want, chosen, routed, r_stream = walk_reference(
                    ses.weights, seq, sizes)
                choice = np.asarray(choice)
                ours = np.zeros(chosen.shape, bool)
                np.put_along_axis(ours, choice, True, axis=-1)
                differ = (ours != chosen).any(-1)          # [layers, S]
                edges = []
                for x, layer in zip(routed, moe_layers):
                    m = layer["moe"]
                    z = x.astype(np.float64) @ np.asarray(
                        m["gate"].astype(jnp.float32), np.float64)
                    biased = np.sort(1 / (1 + np.exp(-z)) + np.asarray(
                        m["bias"].astype(jnp.float32), np.float64))
                    edges.append(float(biased[-top] - biased[-top - 1]))
                p_stream = np.asarray(p_stream, np.float64)
                row = Rows()
                row.add(np.asarray(got[0][0]), want[0])
                line = json.dumps({
                    "workload": args.workload, "seed": seed, "sequence": i,
                    "tokens_routed_otherwise": differ.sum(-1).tolist(),
                    "last_token_routed_otherwise": differ[:, -1].tolist(),
                    "last_token_only_program": [
                        sorted(np.flatnonzero(o & ~c).tolist())
                        for o, c in zip(ours[:, -1], chosen[:, -1])],
                    "last_token_only_reference": [
                        sorted(np.flatnonzero(c & ~o).tolist())
                        for o, c in zip(ours[:, -1], chosen[:, -1])],
                    "last_token_choice_edge": edges,
                    "last_token_stream_gap": (
                        np.linalg.norm(p_stream - r_stream, axis=-1)
                        / np.linalg.norm(r_stream, axis=-1)).tolist(),
                    "logit_rms": row.rms, "logit_gap": row.gap},
                    default=float)
                print(("REHEARSAL " if args.rehearsal else "") + line,
                      flush=True)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".",
                                exist_ok=True)
                    with open(args.out, "a") as f:
                        f.write(line + "\n")
        finally:
            session.deactivate()
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:   # noqa: BLE001 - report, then leave non-zero
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)

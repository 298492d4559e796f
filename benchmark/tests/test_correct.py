"""The comparison that decides ``correct`` has been shown to fail.

Run with ``python -m pytest benchmark/tests -q`` on the CPU (tiny
``rehearsal`` sizes; no chip is looked for). Two kinds of test:

* the control: the plain reference computed in fp8, the precision below
  the bfloat16 the configurations state, put in the program's place. It
  has to come out as not correct under the cell's own limits;
* the fault: the rest of a run driven with the timed path broken
  underneath - an answer (vision) or a token (generation) altered where
  it is produced. ``correct`` has to come out false.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

CELLS = ["vit_h14.stream_b32", "vit_h14.query_closed64",
         "dsllm7b_l12.gen_closed16"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(cell, seed):
    res = bench_run.run_cell(cell, seed, 1.5, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not any(res["info"]["control_correct"].values()), (
        "an fp8 control passed the cell's limits", res["info"]["control"])


def _alter_one_row(apply_fn):
    """An answer altered where it is produced: the logits of row 1 of
    every batch get their two largest entries swapped around."""
    import jax.numpy as jnp

    def broken(params, frames):
        out = apply_fn(params, frames)
        if out.ndim != 2 or out.shape[0] < 2:
            return out + jnp.where(jnp.arange(out.shape[-1]) == 0, 3.0, 0.0)
        return out.at[1].set(jnp.roll(out[1], 1))
    return broken


@pytest.mark.parametrize("cell", CELLS[:2])
def test_altered_answer_is_not_correct(cell):
    res = bench_run.run_cell(cell, 5, 1.5, 0, rehearsal=True,
                             fault=_alter_one_row)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]


def test_altered_token_is_not_correct(monkeypatch):
    cell = CELLS[2]
    from nnstreamer_tpu.models import transformer as tfm
    real = tfm.sample_logits

    def broken(keys, logits, temperature, *rest):
        tok = real(keys, logits, temperature, *rest)
        # lane 0 emits the token after the one the model chose
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(tfm, "sample_logits", broken)
    res = bench_run.run_cell(cell, 5, 2.0, 0, rehearsal=True)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]

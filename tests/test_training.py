"""Training path: datarepo reader/writer + tensor_trainer with the jax
trainer subplugin (≙ tests/nnstreamer_trainer + tests/nnstreamer_datarepo).
"""
import json
import os

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch


def _write_dataset(tmp_path, n=32, in_dim=8, classes=4):
    """Raw sample records: float32[in_dim] input + float32[classes] one-hot."""
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(n, in_dim)).astype(np.float32)
    ys = np.zeros((n, classes), np.float32)
    labels = rng.integers(0, classes, n)
    ys[np.arange(n), labels] = 1.0
    # make the task learnable: class mean offsets
    xs += labels[:, None] * 2.0
    data = tmp_path / "train.data"
    with open(data, "wb") as f:
        for x, y in zip(xs, ys):
            f.write(x.tobytes() + y.tobytes())
    dims = f"{in_dim}.{classes}"
    index = {
        "gst_caps": ("other/tensors, format=(string)static, "
                     "framerate=(fraction)0/1, num_tensors=(int)2, "
                     f"dimensions=(string){dims}, "
                     "types=(string)float32.float32"),
        "total_samples": n,
        "sample_size": (in_dim + classes) * 4,
    }
    jpath = tmp_path / "train.json"
    jpath.write_text(json.dumps(index))
    return data, jpath, xs, ys


def test_datareposrc_reads_samples(tmp_path):
    data, jpath, xs, ys = _write_dataset(tmp_path, n=10)
    pipe = parse_launch(
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=1 ! appsink name=out')
    pipe.run(timeout=30)
    bufs = pipe["out"].buffers
    assert len(bufs) == 10
    np.testing.assert_allclose(bufs[0].chunks[0].host(), xs[0], rtol=1e-6)
    np.testing.assert_array_equal(bufs[0].chunks[1].host(), ys[0])


def test_datareposrc_epochs_and_range(tmp_path):
    data, jpath, _, _ = _write_dataset(tmp_path, n=10)
    pipe = parse_launch(
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=2 start-sample-index=2 stop-sample-index=4 '
        '! appsink name=out')
    pipe.run(timeout=30)
    assert len(pipe["out"].buffers) == 6  # 3 samples x 2 epochs


def test_datareposink_roundtrip(tmp_path):
    data, jpath, xs, ys = _write_dataset(tmp_path, n=6)
    out_data = tmp_path / "copy.data"
    out_json = tmp_path / "copy.json"
    pipe = parse_launch(
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        f'epochs=1 ! datareposink location={out_data} json={out_json}')
    pipe.run(timeout=30)
    pipe.stop()
    index = json.loads(out_json.read_text())
    assert index["total_samples"] == 6
    assert index["sample_size"] == (8 + 4) * 4
    assert os.path.getsize(out_data) == 6 * (8 + 4) * 4
    # and the written repo is readable again
    pipe2 = parse_launch(
        f'datareposrc location={out_data} json={out_json} is-shuffle=false '
        'epochs=1 ! appsink name=out')
    pipe2.run(timeout=30)
    np.testing.assert_allclose(pipe2["out"].buffers[0].chunks[0].host(),
                               xs[0], rtol=1e-6)


def test_trainer_learns_and_saves(tmp_path):
    data, jpath, _, _ = _write_dataset(tmp_path, n=32)
    save = tmp_path / "model_out"
    pipe = parse_launch(
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=20 '
        '! tensor_trainer name=t framework=jax '
        'model-config="zoo://mlp?in_dim=8&hidden=16&out_dim=4&lr=0.05" '
        f'model-save-path={save} '
        'num-training-samples=24 num-validation-samples=8 epochs=20 '
        'num-inputs=1 num-labels=1 '
        '! appsink name=out')
    pipe.run(timeout=300)
    pipe.stop()
    stats = pipe["out"].buffers
    assert len(stats) >= 20  # one per epoch (+ completion)
    first, last = stats[0].chunks[0].host(), stats[-1].chunks[0].host()
    assert last[0] < first[0]  # training loss decreased
    assert last[1] >= 0.5      # learnable toy task fits
    assert (save / "params").exists()  # orbax checkpoint written


def test_trainer_resume_from_checkpoint(tmp_path):
    data, jpath, _, _ = _write_dataset(tmp_path, n=16)
    save = tmp_path / "ckpt"
    desc = (
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=3 '
        '! tensor_trainer framework=jax '
        'model-config="zoo://mlp?in_dim=8&hidden=16&out_dim=4&lr=0.05" '
        'num-training-samples=16 epochs=3 num-inputs=1 num-labels=1 '
        f'{{}} ! appsink name=out')
    pipe = parse_launch(desc.format(f"model-save-path={save}"))
    pipe.run(timeout=300)
    pipe.stop()
    loss_a = pipe["out"].buffers[-1].chunks[0].host()[0]
    pipe = parse_launch(desc.format(
        f"model-save-path={save} model-load-path={save}"))
    pipe.run(timeout=300)
    pipe.stop()
    loss_b = pipe["out"].buffers[-1].chunks[0].host()[0]
    assert loss_b < loss_a  # continued from the saved params


def test_mesh_checkpoint_round_trip_resumes_sharded(tmp_path, caplog):
    """Save mesh-trainer params, restore onto the
    SAME mesh with explicit shardings (no orbax 'Sharding info not
    provided' topology warning), resume training, loss keeps falling."""
    import logging
    import warnings

    import jax
    data, jpath, _, _ = _write_dataset(tmp_path, n=16)
    save = tmp_path / "ckpt"
    desc = (
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=4 '
        '! tensor_trainer name=t framework=jax '
        'model-config="zoo://mlp?in_dim=8&hidden=16&out_dim=4&lr=0.05" '
        'mesh=4x1x2 rules=gpt '
        'num-training-samples=16 epochs=4 num-inputs=1 num-labels=1 '
        f'{{}} ! appsink name=out')
    pipe = parse_launch(desc.format(f"model-save-path={save}"))
    pipe.run(timeout=300)
    pipe.stop()
    loss_a = pipe["out"].buffers[-1].chunks[0].host()[0]
    assert (save / "params").exists()

    with warnings.catch_warnings(record=True) as wrecs:
        warnings.simplefilter("always")
        with caplog.at_level(logging.WARNING):
            pipe = parse_launch(desc.format(
                f"model-save-path={save} model-load-path={save}"))
            pipe.start()
            pipe.wait_eos(300)
            params = pipe["t"].fw.params
            pipe.stop()
    texts = [str(w.message) for w in wrecs] + \
            [r.getMessage() for r in caplog.records]
    assert not any("Sharding info not provided" in t for t in texts), texts
    loss_b = pipe["out"].buffers[-1].chunks[0].host()[0]
    assert loss_b < loss_a  # resumed from the saved mesh state
    # restored-then-trained params live across the full 8-device mesh
    leaves = jax.tree_util.tree_leaves(params)
    devs = {d for l in leaves for d in l.sharding.device_set}
    assert len(devs) == 8


def test_trainer_pipeline_on_mesh(tmp_path):
    """datareposrc -> tensor_trainer on the 8-virtual-device mesh: the
    sharded train step from parallel/train.py must actually run in the
    pipeline path, with decreasing loss and params laid out on the mesh."""
    import jax
    data, jpath, _, _ = _write_dataset(tmp_path, n=32)
    save = tmp_path / "model_out"
    pipe = parse_launch(
        f'datareposrc location={data} json={jpath} is-shuffle=false '
        'epochs=15 '
        '! tensor_trainer name=t framework=jax '
        'model-config="zoo://mlp?in_dim=8&hidden=16&out_dim=4&lr=0.05" '
        f'model-save-path={save} mesh=4x1x2 rules=gpt '
        'num-training-samples=24 num-validation-samples=8 epochs=15 '
        'num-inputs=1 num-labels=1 '
        '! appsink name=out')
    # run() would stop() (and release the trainer) before we can
    # inspect the param shardings, so drive the states manually
    pipe.start()
    pipe.wait_eos(300)
    params = pipe["t"].fw.params
    pipe.stop()
    stats = pipe["out"].buffers
    assert len(stats) >= 15
    first, last = stats[0].chunks[0].host(), stats[-1].chunks[0].host()
    assert last[0] < first[0]          # loss decreased on the mesh path
    # the trainer's params must live on mesh devices (not single-device)
    leaves = jax.tree_util.tree_leaves(params)
    assert leaves, "no params"
    shardings = {str(getattr(l, "sharding", None)) for l in leaves}
    assert any("mesh" in s.lower() or "NamedSharding" in s
               for s in shardings), shardings
    devs = {d for l in leaves for d in l.sharding.device_set}
    assert len(devs) == 8              # laid out across all 8 devices
    assert (save / "params").exists()


def test_train_gpt_in_pipeline_then_serve_with_llm(tmp_path):
    """The full MLOps loop in one framework: datareposrc streams token
    sequences into tensor_trainer (GPT next-token loss via a
    model-config file), the checkpoint saves through orbax, and the llm
    filter serves the trained weights via zoo://gpt?params_dir=... —
    ≙ the reference's train-with-NNTrainer / serve-with-filter story
    (gsttensor_trainer.c + tensor_filter), closed end to end here."""
    cfg_py = tmp_path / "gpt_trainer.py"
    cfg_py.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import optax\n"
        "from nnstreamer_tpu.models import transformer as tfm\n"
        "CFG = tfm.GPTConfig(vocab=32, d_model=16, n_heads=2, n_layers=1)\n"
        "def get_trainer():\n"
        "    params = tfm.init_params(CFG, jax.random.PRNGKey(0))\n"
        "    def loss_fn(p, inputs, labels):\n"
        "        batch = inputs[0].astype(jnp.int32)\n"
        "        return tfm.loss_fn(p, batch, CFG), jnp.zeros(())\n"
        "    return loss_fn, params, optax.adam(5e-2)\n")

    # dataset: a repeated arithmetic token sequence (memorizable)
    n, t = 24, 8
    seqs = np.stack([(np.arange(t + 1) + i) % 32 for i in range(n)])
    data = tmp_path / "tokens.data"
    with open(data, "wb") as f:
        for s in seqs:
            f.write(s.astype(np.int32).tobytes()
                    + np.zeros(1, np.float32).tobytes())
    index = {
        "gst_caps": ("other/tensors, format=(string)static, "
                     "framerate=(fraction)0/1, num_tensors=(int)2, "
                     f"dimensions=(string){t + 1}.1, "
                     "types=(string)int32.float32"),
        "total_samples": n,
        "sample_size": (t + 1) * 4 + 4,
    }
    jpath = tmp_path / "tokens.json"
    jpath.write_text(json.dumps(index))
    ckpt = str(tmp_path / "gpt-trained")

    pipe = parse_launch(
        f"datareposrc location={data} json={jpath} is-shuffle=false "
        "epochs=4 "
        f"! tensor_trainer framework=jax model-config={cfg_py} "
        f"model-save-path={ckpt} num-training-samples={n} "
        "num-validation-samples=0 epochs=4 num-inputs=1 num-labels=1 "
        "! appsink name=out")
    pipe.run(timeout=300)
    losses = [float(b.chunks[0].host()[0]) for b in pipe["out"].buffers]
    assert len(losses) >= 4  # one per epoch (+ final summary record)
    assert losses[-1] < losses[0], losses
    assert os.path.isdir(ckpt)

    # serve the trained weights through the llm filter
    from nnstreamer_tpu.filters.base import FilterProperties
    from nnstreamer_tpu.filters.registry import find_filter
    zoo = ("zoo://gpt?vocab=32&d_model=16&n_heads=2&n_layers=1"
           f"&params_dir={ckpt}")
    fw = find_filter("llm")()
    fw.open(FilterProperties(model_files=(zoo,),
                             custom_properties="max_tokens:6,max_len:32"))
    prompt = np.array([4, 5, 6], np.int32)
    toks = fw.invoke([prompt])[0]
    fw.close()
    assert toks.shape == (6,)
    # the memorized pattern is "+1 each step": the trained model should
    # continue the arithmetic sequence at least at the first step
    assert toks[0] == 7, toks

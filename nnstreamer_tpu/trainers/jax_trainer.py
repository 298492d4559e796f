"""The JAX/optax trainer subplugin — this framework's NNTrainer analog.

model-config is a python file defining::

    def get_trainer():
        # returns (loss_fn, params, optimizer)
        # loss_fn(params, inputs: list[jax.Array], labels: list[jax.Array])
        #   -> (scalar loss, scalar accuracy)
        ...

or ``zoo://<name>?...`` for a zoo classifier trained with softmax
cross-entropy. Samples pushed by tensor_trainer accumulate into
device batches; epochs run on a background thread that DRAINS the
queue each epoch (the streaming-training model of gsttensor_trainer.c:
the src replays the dataset per epoch, e.g. datareposrc epochs=N, and
the trainer consumes num-training-samples every epoch). If the stream
ends early the last complete dataset is reused for remaining epochs,
and once training finishes further pushed samples are discarded so EOS
can propagate.
Checkpoints go through orbax (trainers/checkpoint.py). With the ``mesh``
property set (``tensor_trainer mesh=4x1x2 rules=gpt``) the loop really
uses parallel/train.py: params+optimizer moments placed by the rule
table via create_train_state, the batch sharded over the ``data`` axis
via shard_batch, and make_train_step's jit letting GSPMD insert the
gradient psum/reduce-scatter collectives over ICI.
"""
from __future__ import annotations

import queue as _pyqueue
import threading
import urllib.parse
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..utils.log import logger
from ..utils.xla_cache import ensure_compile_cache
from .base import (TrainerEvent, TrainerFramework, TrainerProperties,
                   TrainerStatus, register_trainer)


def _zoo_classifier_trainer(name: str, **kwargs):
    """Wrap a zoo model as (loss_fn, params, optimizer) for
    cross-entropy classification (labels = int class or one-hot)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ..models import zoo

    lr = float(kwargs.pop("lr", "1e-3"))  # trainer knob, not a model kwarg
    apply_fn, params, _, _ = zoo.build(name, **kwargs)

    def loss_fn(p, inputs, labels):
        logits = jax.vmap(lambda x: apply_fn(p, x))(inputs[0])
        y = labels[0]
        if y.ndim > 1 and y.shape[-1] == logits.shape[-1]:
            targets = jnp.argmax(y, axis=-1)
        else:
            targets = y.reshape(-1).astype(jnp.int32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()
        acc = jnp.mean(jnp.argmax(logits, -1) == targets)
        return nll, acc

    return loss_fn, params, optax.adam(lr)


@register_trainer
class JaxTrainer(TrainerFramework):
    NAME = "jax"

    def __init__(self):
        self._props: Optional[TrainerProperties] = None
        self._queue: _pyqueue.Queue = _pyqueue.Queue(maxsize=256)
        self._thread: Optional[threading.Thread] = None
        self._status = TrainerStatus()
        self._status_lock = threading.Lock()
        self._stop_evt = threading.Event()
        self._done_evt = threading.Event()
        self._eos_evt = threading.Event()
        self.params = None
        # coherent (epoch, params, opt_state) published after every
        # completed step — the ONLY state the preemption snapshot reads,
        # so a snapshot can never see params from step N with optimizer
        # moments from step N-1
        self._ckpt_lock = threading.Lock()
        self._ckpt = None
        # restore-and-resume (checkpoint/): epoch to resume AFTER, and
        # the host-side optimizer state to rebuild from
        self._resume_epoch = 0
        self._resume_opt = None

    # -- lifecycle --------------------------------------------------------
    def create(self, props: TrainerProperties) -> None:
        ensure_compile_cache()  # before the zoo init compiles
        self._props = props
        cfg = props.model_config
        if cfg.startswith("zoo://"):
            parsed = urllib.parse.urlparse(cfg)
            kwargs = {k: v[0] for k, v in
                      urllib.parse.parse_qs(parsed.query).items()}
            name = parsed.netloc or parsed.path.lstrip("/")
            self._loss_fn, self.params, self._optimizer = \
                _zoo_classifier_trainer(name, **kwargs)
        elif cfg.endswith(".py"):
            ns: Dict[str, Any] = {}
            with open(cfg) as f:
                exec(compile(f.read(), cfg, "exec"), ns)  # noqa: S102 — user model config
            if "get_trainer" not in ns:
                raise ValueError(f"{cfg}: must define get_trainer()")
            self._loss_fn, self.params, self._optimizer = ns["get_trainer"]()
        else:
            raise ValueError(f"jax trainer cannot load model-config {cfg!r}")
        if props.model_load_path:
            from .checkpoint import restore_params
            like = self.params
            if props.mesh:
                # place the template on the mesh FIRST so the restore
                # lands directly sharded (explicit restore args, no
                # orbax topology warning, no host round trip)
                from ..parallel.mesh import mesh_from_spec
                from ..parallel.sharding import rules_by_name, shard_params
                like = shard_params(self.params,
                                    rules_by_name(props.rules or ""),
                                    mesh_from_spec(props.mesh))
            self.params = restore_params(props.model_load_path, like)

    def start(self) -> None:
        self._stop_evt.clear()
        self._done_evt.clear()
        self._eos_evt.clear()
        self._thread = threading.Thread(target=self._train_loop,
                                        name="jax-trainer", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop_evt.set()
        if self._thread is not None:
            self._thread.join(timeout=60.0)
            self._thread = None
        if self._props and self._props.model_save_path and \
                self.params is not None:
            from .checkpoint import save_params
            save_params(self._props.model_save_path, self.params)
            logger.info("jax trainer: saved model to %s",
                        self._props.model_save_path)

    def destroy(self) -> None:
        self._stop_evt.set()

    # -- preemption checkpoint/restore (checkpoint/) -----------------------
    def pause(self) -> None:
        """Preemption quiesce: stop at the next step boundary (the loop's
        stop-checks guarantee no partial optimizer update) and join the
        training thread so :meth:`snapshot` reads settled state. Unlike
        ``stop()`` this saves nothing to model-save-path — the snapshot
        store owns persistence on this path."""
        self._stop_evt.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=60.0)

    def snapshot(self, snap_dir: str) -> Optional[Dict]:
        """Serialize the last published (epoch, params, opt_state):
        params through the orbax path (trainers/checkpoint.py) into
        ``snap_dir``, optimizer moments host-side into the returned
        blob. Epoch semantics: ``epoch`` steps are COMPLETE; resume runs
        ``epoch+1..epochs`` — never a repeated or skipped update."""
        import jax
        with self._ckpt_lock:
            ckpt = self._ckpt
        if ckpt is None:
            # no step completed since create/restore: snapshot initial
            # params so restore still lands on a runnable model
            ckpt = (self._resume_epoch, self.params, self._resume_opt)
        epoch, params, opt_state = ckpt
        if params is None:
            return None
        import os
        from .checkpoint import save_params
        save_params(os.path.join(snap_dir, "params"), params)
        host_opt = None
        if opt_state is not None:
            host_opt = jax.device_get(opt_state)
        return {"epoch": int(epoch), "opt_state": host_opt,
                "status": vars(self.get_status())}

    def resume_from(self, state: Dict, snap_dir: str) -> None:
        """Apply a :meth:`snapshot` blob after :meth:`create` and before
        :meth:`start`: params reload through orbax (mesh-aware like the
        model-load-path route), the epoch counter resumes exactly after
        the recorded step, and the optimizer moments are handed to the
        training loop to rebuild on device."""
        import os
        from .checkpoint import restore_params
        assert self._props is not None, "resume_from requires create()"
        like = self.params
        if self._props.mesh:
            from ..parallel.mesh import mesh_from_spec
            from ..parallel.sharding import rules_by_name, shard_params
            like = shard_params(self.params,
                                rules_by_name(self._props.rules or ""),
                                mesh_from_spec(self._props.mesh))
        self.params = restore_params(os.path.join(snap_dir, "params"), like)  # racecheck: ok(resume_from runs from restore_state before start(): the training worker does not exist yet)
        self._resume_epoch = int(state.get("epoch", 0))
        self._resume_opt = state.get("opt_state")
        st = state.get("status") or {}
        with self._status_lock:
            self._status = TrainerStatus(**st) if st else TrainerStatus(
                epoch=self._resume_epoch)
        with self._ckpt_lock:
            self._ckpt = (self._resume_epoch, self.params,
                          self._resume_opt)
        logger.info("jax trainer: resuming after epoch %d",
                    self._resume_epoch)

    # -- data -------------------------------------------------------------
    def push_data(self, tensors: Sequence[Any]) -> None:
        # discard once training has finished so upstream never blocks on a
        # full queue after the last epoch (EOS must still propagate)
        while not self._stop_evt.is_set() and not self._done_evt.is_set():
            try:
                self._queue.put(list(tensors), timeout=0.5)
                return
            except _pyqueue.Full:
                continue

    def end_of_data(self) -> None:
        """Upstream EOS: no more samples will arrive. The training loop
        stops waiting on the queue and reuses the last complete dataset
        for any remaining epochs."""
        self._eos_evt.set()

    def get_status(self) -> TrainerStatus:
        with self._status_lock:
            return TrainerStatus(**vars(self._status))

    def wait_training_complete(self, timeout: Optional[float] = None) -> bool:
        return self._done_evt.wait(timeout)

    # -- training loop ----------------------------------------------------
    def _collect(self, n: int) -> Optional[List[List[np.ndarray]]]:
        samples: List[List[np.ndarray]] = []
        while len(samples) < n and not self._stop_evt.is_set():
            try:
                samples.append(self._queue.get(timeout=0.1))
            except _pyqueue.Empty:
                if self._eos_evt.is_set() and self._queue.empty():
                    break  # stream ended mid-epoch; caller reuses last set
        return samples if len(samples) == n else None

    def _train_loop(self) -> None:
        import jax
        import jax.numpy as jnp

        assert self._props is not None
        p = self._props
        n_in = p.num_inputs

        def batch_of(samples):
            cols = list(zip(*samples))
            arrays = [jnp.asarray(np.stack(c)) for c in cols]
            return arrays[:n_in], arrays[n_in:]

        opt = self._optimizer
        mesh = None
        if p.mesh:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel import train as ptrain
            from ..parallel.mesh import mesh_from_spec
            from ..parallel.sharding import rules_by_name
            mesh = mesh_from_spec(p.mesh)
            rules = rules_by_name(p.rules or "")
            state = ptrain.create_train_state(self.params, opt, mesh, rules)
            if self._resume_opt is not None:
                # land the restored host moments directly on each fresh
                # moment's sharding; on any mismatch keep the fresh init
                # (training stays correct, momentum restarts cold)
                try:
                    state.opt_state = jax.tree_util.tree_map(
                        lambda h, l: jax.device_put(
                            jnp.asarray(h), l.sharding)
                        if hasattr(l, "sharding") else jnp.asarray(h),
                        self._resume_opt, state.opt_state)
                except (TypeError, ValueError):
                    logger.warning("jax trainer: restored optimizer state "
                                   "does not match; reinitializing moments")
            self.params = state.params
            ndp = mesh.shape.get("data", 1)

            def loss_on_batch(params, batch):
                return self._loss_fn(params, batch[0], batch[1])

            sharded_step = ptrain.make_train_step(loss_on_batch, opt,
                                                  has_aux=True)

            def shard(batch):
                n = batch[0][0].shape[0]
                spec = P("data") if ndp > 1 and n % ndp == 0 else P()
                return jax.device_put(batch, NamedSharding(mesh, spec))

            def step(params, opt_state, inputs, labels):
                nonlocal state
                state, loss, acc = sharded_step(state,
                                                shard((inputs, labels)))
                return state.params, state.opt_state, loss, acc

            opt_state = state.opt_state
        else:
            if self._resume_opt is not None:
                opt_state = jax.tree_util.tree_map(jnp.asarray,
                                                   self._resume_opt)
            else:
                # jitcheck: ok(one-shot optimizer init at train start, not per-step)
                opt_state = jax.jit(opt.init)(self.params)

            @jax.jit
            def step(params, opt_state, inputs, labels):
                (loss, acc), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(params, inputs, labels)
                updates, opt_state = opt.update(grads, opt_state, params)
                import optax
                params = optax.apply_updates(params, updates)
                return params, opt_state, loss, acc

        @jax.jit
        def evaluate(params, inputs, labels):
            return self._loss_fn(params, inputs, labels)

        try:
            train: Optional[List[List[np.ndarray]]] = None
            val: Optional[List[List[np.ndarray]]] = None
            for epoch in range(self._resume_epoch + 1, p.epochs + 1):
                if self._stop_evt.is_set():
                    return
                # drain this epoch's samples from the stream; on a short
                # stream (src stopped replaying) reuse the previous epoch's
                t = self._collect(p.num_training_samples)
                if self._stop_evt.is_set():
                    return  # stop requested mid-collection: no extra step
                if t is not None:
                    train = t
                    if p.num_validation_samples:
                        v = self._collect(p.num_validation_samples)
                        if v is not None:
                            val = v
                if train is None:
                    logger.warning("jax trainer: stream ended before a full "
                                   "training set arrived; aborting")
                    return
                inputs, labels = batch_of(train)
                self.params, opt_state, loss, acc = step(
                    self.params, opt_state, inputs, labels)
                vloss = vacc = 0.0
                if val:
                    vi, vl = batch_of(val)
                    vloss, vacc = (float(x) for x in
                                   evaluate(self.params, vi, vl))
                with self._status_lock:
                    self._status = TrainerStatus(
                        epoch, float(loss), float(acc), vloss, vacc)
                # publish the step-coherent checkpoint tuple the
                # preemption snapshot reads — epoch N fully applied
                with self._ckpt_lock:
                    self._ckpt = (epoch, self.params, opt_state)
                self._emit(TrainerEvent.EPOCH_COMPLETION, self.get_status())
            self._emit(TrainerEvent.TRAINING_COMPLETION, self.get_status())
        except Exception:  # noqa: BLE001
            logger.exception("jax trainer loop failed")
        finally:
            self._done_evt.set()

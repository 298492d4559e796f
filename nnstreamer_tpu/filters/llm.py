"""LLM generative filter — async token streaming on the JAX decode loop.

≙ ext/nnstreamer/tensor_filter/tensor_filter_llamacpp.cc: 1 prompt in,
N token frames out via the async dispatcher
(nnstreamer_filter_dispatch_output_async, tensor_filter.c:1099-1170).
Here generation is the KV-cache decode loop of models/transformer.py —
static shapes, one jitted decode step reused every token.

model accepts ``zoo://gpt?...`` (zoo spec) or a ``get_lm()`` python file
returning (params, cfg). custom properties (``custom=key:value,...``):
max_tokens, temperature (0 = greedy), top_k, top_p, seed, max_len,
n_parallel, chunk.

``n_parallel:M`` (M>1) turns on continuous-batching decode: up to M
concurrent prompts share ONE decode dispatch per token step (the
TPU-first answer to llamacpp's n_batch, tensor_filter_llamacpp.cc:267)
— prompts are prefetched into cache slots as they free up, so decode
dispatch count scales with max(stream depth), not streams x tokens.

Disaggregated serving options (see Documentation/llm.md):

* ``paged:true`` — back the scheduler with a block-granular KV pool
  (``block_size:N`` tokens/block, ``pool_blocks:N`` budget) instead of
  per-slot contiguous lanes: admission is token-budgeted, and with
  ``prefix_cache:true`` (default in paged mode) prompts whose
  block-aligned prefix chain is warm skip that part of prefill
  entirely. Emitted token streams are bit-identical to the contiguous
  path (the tests/test_llm_disagg.py parity gate).
* ``role:prefill|decode|both`` — phase split across replicas: a
  prefill replica runs only the prompt pass and ships the KV prefix to
  ``handoff:host:port`` over the negotiated KV_XFER link (edge/kv.py,
  ``kv_precision:none|bf16|fp16``); a decode replica (implies paged)
  listens on ``handoff_port:N`` (0 = ephemeral; see
  ``filter.handoff_port``) and folds shipped streams into its
  continuous-batching loop.
"""
from __future__ import annotations

import threading
import time
import urllib.parse
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..obs import spans as _obs_spans
from ..tensors.info import TensorsInfo
from ..utils.atomic import Counters
from ..utils.log import logger
from ..utils.xla_cache import ensure_compile_cache
from .base import (FilterFramework, FilterProperties,
                   parse_custom_properties as _parse_custom)
from .registry import register_alias, register_filter

# default shared-cache length in n_parallel mode. The batched cache is
# allocated ONCE (static shapes), so unlike the single-stream path the
# default cannot derive from each prompt's bucket; longer prompts need an
# explicit custom=max_len:N.
DEFAULT_BATCH_MAX_LEN = 128

_TRUE = ("1", "true", "yes", "on")


def _ctx_of(ctx: Any):
    """The TraceContext riding on a Buffer-shaped invoke ctx, if any
    (plain correlation tokens — ints, strings — carry none)."""
    try:
        from ..obs import context as _obs_ctx
        return _obs_ctx.ctx_of(ctx)
    except Exception:  # noqa: BLE001 — tracing is best-effort by design
        return None


class _PoolFull(Exception):
    """Paged admission backpressure: the KV pool cannot cover this
    stream right now — the scheduler requeues and retries as running
    streams release blocks."""


class _ContigBackend:
    """Per-slot contiguous cache lanes (decode_step_multi): every slot
    reserves a worst-case [max_len] lane, so occupancy is
    stream-counted. The pre-paging layout, kept as the parity oracle
    and for small deployments where the lane waste is irrelevant."""

    def __init__(self, filt: "LlmFilter", m: int, max_len: int):
        import jax.numpy as jnp

        self.f = filt
        self.max_len = max_len
        self.cache = filt._tfm.init_cache_multi(filt._cfg, batch=m,
                                                max_len=max_len)
        self.logits = jnp.zeros((m, filt._cfg.vocab), jnp.float32)

    def admit(self, slot: int, prompt: np.ndarray, budget: int) -> None:
        import jax.numpy as jnp

        l1, c1 = self.f._prefill_prompt(prompt, self.max_len)
        self.cache = self.f._insert(self.cache, c1,
                                    jnp.asarray(slot, jnp.int32))
        self.logits = self.logits.at[slot].set(l1[0])

    def admit_handoff(self, slot, prompt, kv, budget) -> None:
        raise ValueError("llm: the contiguous cache cannot adopt a KV "
                         "handoff; decode replicas need custom=paged:true")

    def step(self, tok, active_np) -> None:
        import jax.numpy as jnp

        self.logits, self.cache = self.f._decode_multi(
            self.f._params, self.cache, tok, jnp.asarray(active_np))

    def chunk(self, k: int, temperature: float, keys, active_np):
        import jax.numpy as jnp

        toks, self.logits, self.cache, keys = self.f._chunk_fn(
            k, temperature)(self.f._params, self.cache, self.logits,
                            keys, jnp.asarray(active_np))
        return toks, keys

    def free(self, slot: int) -> None:
        pass


class _PagedBackend:
    """Block-pool cache (decode_step_paged): slots address KV through
    per-stream block tables over a shared arena, so occupancy is
    token-budgeted — admission asks for exactly
    ceil(min(plen + budget, max_len) / block_size) blocks, a long
    conversation no longer pins a worst-case lane, and block-aligned
    prompt prefixes can be shared through the content-addressed cache
    (filters/kvpool.py)."""

    def __init__(self, filt: "LlmFilter", m: int, max_len: int):
        import jax.numpy as jnp

        self.f = filt
        self.max_len = max_len
        self.bs = filt._block_size
        self.w = -(-max_len // self.bs)
        self.mgr = filt._pool_mgr
        self.pool = filt._tfm.init_kv_pool(filt._cfg, self.mgr.n_blocks,
                                           self.bs)
        self.table_np = np.zeros((m, self.w), np.int32)
        self._table_dev = None
        self.index = jnp.zeros((m,), jnp.int32)
        self.logits = jnp.zeros((m, filt._cfg.vocab), jnp.float32)
        self.blocks: List[List[int]] = [[] for _ in range(m)]

    def _table(self):
        import jax.numpy as jnp

        if self._table_dev is None:
            self._table_dev = jnp.asarray(self.table_np)
        return self._table_dev

    def _need(self, plen: int, budget: int) -> int:
        span = max(plen, min(plen + int(budget), self.max_len))
        return -(-span // self.bs)

    def _insert_span(self, blocks: List[int], k, v, valid: int) -> None:
        """Block-align (k, v) [L, n, H, Dh] (first ``valid`` rows real;
        device arrays are fetched here) and write them into ``blocks``.
        Rows past ``valid`` are zeros the decode loop overwrites before
        its validity mask can reach them — the same padded-tail
        argument as prefill's. The whole host round trip is one
        ``nns.llm.kv_copy`` span: for a fresh prefill its fetch is
        where the scheduler thread waits for the device."""
        import jax.numpy as jnp

        with _obs_spans.region("nns.llm.kv_copy", "llm",
                               blocks=len(blocks)):
            k_np, v_np = np.asarray(k), np.asarray(v)
            layers, _, heads, hd = k_np.shape
            spanf = len(blocks) * self.bs
            kb = np.zeros((layers, spanf, heads, hd), k_np.dtype)
            vb = np.zeros((layers, spanf, heads, hd), v_np.dtype)
            n = min(int(valid), spanf, k_np.shape[1])
            kb[:, :n] = k_np[:, :n]
            vb[:, :n] = v_np[:, :n]
            sh = (layers, len(blocks), self.bs, heads, hd)
            self.pool = self.f._pool_insert(
                self.pool, jnp.asarray(kb.reshape(sh)),
                jnp.asarray(vb.reshape(sh)),
                jnp.asarray(np.asarray(blocks, np.int32)))

    def _suffix_prefill(self, past_k, past_v, past_len: int,
                        suffix: np.ndarray):
        """One prefill-with-past dispatch over pow2-bucketed shapes
        (O(log^2) compiled variants across all split points)."""
        import jax.numpy as jnp

        sb = 8
        while sb < suffix.size:
            sb *= 2
        padded = np.zeros(sb, np.int32)
        padded[:suffix.size] = suffix
        with _obs_spans.region("nns.llm.prefill", "llm", bucket=sb,
                               tokens=int(suffix.size), past=int(past_len)):
            return self.f._prefill_past(
                self.f._params, past_k, past_v,
                jnp.asarray(past_len, jnp.int32), jnp.asarray(padded[None]),
                jnp.asarray(suffix.size, jnp.int32))

    def admit(self, slot: int, prompt: np.ndarray, budget: int) -> None:
        from .kvpool import chain_hashes

        import jax.numpy as jnp

        f = self.f
        plen = int(prompt.size)
        need = self._need(plen, budget)
        hashes = chain_hashes(prompt, self.bs)     # full blocks only
        # adoption never covers the whole prompt: at least one suffix
        # token recomputes (logits must come from somewhere), and the
        # first decode-written block stays stream-private, which is
        # what makes shared blocks read-only by construction
        cover_cap = (plen - 1) // self.bs
        cov = self.mgr.lookup(hashes[:cover_cap]) if f._prefix_cache \
            else []
        fresh = self.mgr.alloc(need - len(cov))
        if fresh is None:
            if cov:
                self.mgr.release(cov)
            raise _PoolFull(f"need {need - len(cov)} blocks")
        allb = list(cov) + list(fresh)
        p0 = len(cov) * self.bs
        try:
            if cov:
                nbb = 1
                while nbb < len(cov):
                    nbb *= 2
                phys_pad = list(cov) + [cov[-1]] * (nbb - len(cov))
                with _obs_spans.region("nns.llm.kv_copy", "llm",
                                       blocks=len(phys_pad)):
                    pk, pv = f._pool_gather(
                        self.pool,
                        jnp.asarray(np.asarray(phys_pad, np.int32)))
                l1, sk, sv = self._suffix_prefill(pk, pv, p0, prompt[p0:])
                f.stats.add(prefill_dispatches=1, prefill_cached_tokens=p0,
                            prefill_computed_tokens=plen - p0)
                self._insert_span(fresh, sk, sv, plen - p0)
            else:
                l1, c1 = f._prefill_prompt(prompt, self.max_len)
                self._insert_span(allb, c1["k"][:, 0], c1["v"][:, 0], plen)
            if f._prefix_cache and hashes:
                self.mgr.commit(hashes, allb[:len(hashes)])
            self._seat(slot, allb, need, plen, l1)
        except BaseException:
            # admission failed after taking refs: hand every block back.
            # (If commit already ran, release only drops the stream
            # refs — the cache's own refs legitimately keep the prefix
            # blocks resident.)
            self.mgr.release(allb)
            raise

    def admit_handoff(self, slot: int, flat: np.ndarray, kv: Dict,
                      budget: int) -> None:
        """Fold a wire-shipped KV prefix (edge/kv.py handoff dict) into
        the pool. ``flat`` may extend the shipped prompt with tokens a
        pre-crash replica already emitted (snapshot re-adoption): that
        suffix is regrown by one prefill-with-past over the shipped
        prefix, so resurrection costs the suffix, not the prompt."""
        import jax.numpy as jnp

        f = self.f
        plen = int(flat.size)
        t_ship = int(np.asarray(kv["prompt"]).size)
        k_np = np.asarray(kv["k"])
        v_np = np.asarray(kv["v"])
        if k_np.ndim != 4 or k_np.shape[1] < t_ship:
            raise ValueError(f"llm: malformed KV handoff {k_np.shape}")
        f.stats.add(kv_shipped_tokens=t_ship)
        if plen > t_ship:
            pb = 8
            while pb < t_ship:
                pb *= 2
            layers, _, heads, hd = k_np.shape
            pk = np.zeros((layers, pb, heads, hd), k_np.dtype)
            pv = np.zeros((layers, pb, heads, hd), v_np.dtype)
            pk[:, :t_ship] = k_np[:, :t_ship]
            pv[:, :t_ship] = v_np[:, :t_ship]
            l1, sk, sv = self._suffix_prefill(
                jnp.asarray(pk), jnp.asarray(pv), t_ship, flat[t_ship:])
            f.stats.add(prefill_dispatches=1,
                        prefill_computed_tokens=plen - t_ship)
            full_k = np.concatenate(
                [k_np[:, :t_ship],
                 np.asarray(sk)[:, :plen - t_ship].astype(k_np.dtype)],
                axis=1)
            full_v = np.concatenate(
                [v_np[:, :t_ship],
                 np.asarray(sv)[:, :plen - t_ship].astype(v_np.dtype)],
                axis=1)
        else:
            import jax.numpy as _jnp
            l1 = _jnp.asarray(np.asarray(kv["logits"],
                                         np.float32).reshape(1, -1))
            full_k, full_v = k_np, v_np
        need = self._need(plen, budget)
        fresh = self.mgr.alloc(need)
        if fresh is None:
            raise _PoolFull(f"need {need} blocks")
        try:
            self._insert_span(fresh, full_k, full_v, plen)
            if f._prefix_cache:
                from .kvpool import chain_hashes
                hashes = chain_hashes(np.asarray(kv["prompt"], np.int32),
                                      self.bs)
                usable = min(len(hashes), need)
                if usable:
                    self.mgr.commit(hashes[:usable], fresh[:usable])
            self._seat(slot, list(fresh), need, plen, l1)
        except BaseException:
            # a failed handoff fold must not strand the receiver's
            # blocks: the sender only counts kv_handoff_errors, so a
            # leaked ref here would shrink the pool forever
            self.mgr.release(list(fresh))
            raise

    def _seat(self, slot: int, allb: List[int], need: int, plen: int,
              l1) -> None:
        self.table_np[slot, :need] = allb
        self.table_np[slot, need:] = 0
        self._table_dev = None
        self.index = self.index.at[slot].set(plen)
        self.logits = self.logits.at[slot].set(l1[0])
        self.blocks[slot] = allb

    def step(self, tok, active_np) -> None:
        import jax.numpy as jnp

        self.logits, self.pool, self.index = self.f._decode_paged(
            self.f._params, self.pool, self._table(), self.index, tok,
            jnp.asarray(active_np))

    def chunk(self, k: int, temperature: float, keys, active_np):
        import jax.numpy as jnp

        toks, self.logits, self.pool, self.index, keys = \
            self.f._chunk_fn_paged(k, temperature)(
                self.f._params, self.pool, self._table(), self.index,
                self.logits, keys, jnp.asarray(active_np))
        return toks, keys

    def free(self, slot: int) -> None:
        if self.blocks[slot]:
            self.mgr.release(self.blocks[slot])
            self.blocks[slot] = []


@register_filter
class LlmFilter(FilterFramework):
    NAME = "llm"
    EXTENSIONS = (".gguf",)  # reference auto-detect parity (llamacpp slot)

    def __init__(self):
        self._params = None
        self._cfg = None
        self._decode = None
        self._opts: Dict[str, str] = {}
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()
        # continuous-batching scheduler state (n_parallel > 1)
        self._pending: List[tuple] = []
        self._cond = threading.Condition()
        self._sched: Optional[threading.Thread] = None
        # checkpoint/: the live slot table (published by _sched_body,
        # stream bookkeeping mutated under _cond) and the stream state
        # recovered from a preemption snapshot, adopted on prompt match
        # at the next invoke_async (see snapshot_state/restore_state)
        self._streams: Optional[List[Optional[Dict[str, Any]]]] = None
        self._recovered: Optional[Dict[str, Any]] = None
        # disaggregated serving (role prop / paged pool)
        self._role = "both"
        self._paged = False
        self._backend = None
        self._pool_mgr = None
        self._kv_rx = None
        self._kv_tx = None

    def open(self, props: FilterProperties) -> None:
        import jax

        from ..models import transformer as tfm

        ensure_compile_cache()  # before init_params compiles
        model = props.model_files[0] if props.model_files else ""
        if model.startswith("zoo://"):
            parsed = urllib.parse.urlparse(model)
            kwargs = {k: v[0] for k, v in
                      urllib.parse.parse_qs(parsed.query).items()}
            name = parsed.netloc or parsed.path.lstrip("/")
            if name != "gpt":
                raise ValueError(f"llm filter expects zoo://gpt, got {name}")
            self._cfg = tfm.GPTConfig(
                vocab=int(kwargs.get("vocab", "32000")),
                d_model=int(kwargs.get("d_model", "512")),
                n_heads=int(kwargs.get("n_heads", "8")),
                n_layers=int(kwargs.get("n_layers", "6")))
            self._params = tfm.init_params(
                self._cfg, jax.random.PRNGKey(int(kwargs.get("seed", "0"))))
            if "params_dir" in kwargs:
                # trained weights from an orbax checkpoint (e.g. saved by
                # tensor_trainer / trainers/checkpoint.py) — the random
                # init above provides the restore template
                from ..trainers.checkpoint import restore_params
                self._params = restore_params(kwargs["params_dir"],
                                              self._params)
        elif model.endswith(".py"):
            ns: Dict[str, Any] = {}
            with open(model) as f:
                exec(compile(f.read(), model, "exec"), ns)  # noqa: S102 — user script
            self._params, self._cfg = ns["get_lm"]()
        elif model.endswith(".gguf"):
            # the extension routes here for reference auto-detect parity,
            # but gguf weight unpacking is out of scope — fail with a
            # pointer instead of a generic loader error
            raise NotImplementedError(
                "llm: .gguf weight loading is not implemented; export "
                "the weights to a get_lm() python module instead (see "
                "Documentation/tutorials/generative-pipelines.md)")
        else:
            raise ValueError(f"llm filter cannot load model {model!r}")
        self._opts = _parse_custom(props.custom_properties)
        cfg = self._cfg

        # every jitted program carries a stable name into the trace's
        # XLA Modules line (jit_nns_llm_<what>)
        named = _obs_spans.named_program
        self._decode = jax.jit(named(
            "nns_llm_decode",
            lambda p, c, t: tfm.decode_step(p, c, t, cfg)))
        self._prefill = jax.jit(named(
            "nns_llm_prefill",
            lambda p, c, toks, tl: tfm.prefill(p, c, toks, cfg,
                                               true_len=tl)))
        self._decode_multi = jax.jit(named(
            "nns_llm_decode_multi",
            lambda p, c, t, a: tfm.decode_step_multi(p, c, t, a, cfg)))
        self._insert = jax.jit(named("nns_llm_cache_insert",
                                     tfm.cache_insert))
        self._tfm = tfm
        self._n_parallel = int(self._opts.get("n_parallel", "1"))
        # custom=chunk:K folds K sample+decode rounds into one scanned
        # dispatch (models/transformer.py decode_chunk_multi): dispatches
        # AND host round trips per token drop K-fold. Token streams are
        # bit-identical to chunk:1; the tradeoff is admission latency in
        # n_parallel mode (a new prompt waits for the current chunk).
        self._chunk = max(1, int(self._opts.get("chunk", "1")))
        self._chunk_jits: Dict[tuple, Any] = {}
        self._sampling_cache = None  # re-parse on every open()
        # -- disaggregated serving / paged pool --------------------------
        self._role = self._opts.get("role", "both")
        if self._role not in ("both", "prefill", "decode"):
            raise ValueError(f"llm: unknown role {self._role!r}; "
                             "expected prefill|decode|both")
        self._paged = (self._opts.get("paged", "false").lower() in _TRUE
                       or self._role == "decode")
        self._prefix_cache = self._opts.get(
            "prefix_cache", "true").lower() in _TRUE
        self._kv_precision = self._opts.get("kv_precision", "none")
        self._block_size = max(1, int(self._opts.get("block_size", "16")))
        self._batch_max_len = int(self._opts.get(
            "max_len", str(DEFAULT_BATCH_MAX_LEN)))
        self._backend = None
        self._pool_mgr = None
        if self._paged:
            if self._n_parallel < 2:
                raise ValueError(
                    "llm: paged/decode mode requires n_parallel>1 — the "
                    "block pool backs the continuous-batching scheduler")
            from .kvpool import KVBlockPool
            w = -(-self._batch_max_len // self._block_size)
            # default budget matches the contiguous layout's worst case,
            # so paged-by-default admits at least what lanes would
            n_blocks = int(self._opts.get("pool_blocks",
                                          str(self._n_parallel * w)))
            self._pool_mgr = KVBlockPool(n_blocks, self._block_size,
                                         name="llm")
            max_len = self._batch_max_len
            self._decode_paged = jax.jit(named(
                "nns_llm_decode_paged",
                lambda p, pool, tbl, idx, t, a: tfm.decode_step_paged(
                    p, pool, tbl, idx, t, a, cfg, max_len=max_len)))
            self._pool_insert = jax.jit(named("nns_llm_pool_insert",
                                              tfm.pool_insert))
            self._pool_gather = jax.jit(named("nns_llm_pool_gather",
                                              tfm.pool_gather))
            self._prefill_past = jax.jit(named(
                "nns_llm_prefill_past",
                lambda p, pk, pv, pl, toks, tl: tfm.prefill_with_past(
                    p, pk, pv, pl, toks, cfg, true_len=tl)))
        with self._cond:
            # prompts queued before a close() belong to the previous
            # session (and carry its ctx buffers) — never replay them
            self._pending.clear()
        self._stop.clear()
        # dispatch accounting: prompts of any length must cost ONE
        # prefill dispatch (≙ llamacpp n_batch), then one per token STEP
        # (shared across n_parallel streams). decode_steps counts the
        # ACTUAL weight-reading steps executed (a chunked dispatch runs
        # an adaptive k <= chunk of them) — the honest multiplier for
        # decode bandwidth accounting. The token-granular prefill
        # counters split prompt work into locally computed vs
        # prefix-cache-warm vs wire-shipped tokens: computed is the
        # chip-time cost, the other two are the savings.
        self.stats = Counters(prefill_dispatches=0, decode_dispatches=0,
                              decode_steps=0, prefill_computed_tokens=0,
                              prefill_cached_tokens=0, kv_shipped_tokens=0,
                              kv_handoffs_in=0, kv_handoffs_out=0,
                              kv_handoff_errors=0)
        if self._role == "decode" or "handoff_port" in self._opts:
            from ..edge.kv import KvReceiver
            self._kv_rx = KvReceiver(
                "0.0.0.0", int(self._opts.get("handoff_port", "0")),
                self._on_kv_handoff, precision=self._kv_precision,
                name="llm-kv-rx", stats=self.stats).start()

    def close(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._kv_rx is not None:
            self._kv_rx.stop()
            self._kv_rx = None
        if self._kv_tx is not None:
            self._kv_tx.close()
            self._kv_tx = None
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads.clear()
        if self._sched is not None:
            self._sched.join(timeout=5.0)
            self._sched = None
        self._params = None
        self._decode = None

    @property
    def handoff_port(self) -> Optional[int]:
        """The bound KV_XFER port of a decode-role filter (resolves
        handoff_port:0 to the ephemeral port the OS picked)."""
        return self._kv_rx.bound_port if self._kv_rx is not None else None

    def get_model_info(self):
        # prompt length is per-buffer (dynamic): input derives from caps
        return None, TensorsInfo.make("int32", "1")

    def set_input_info(self, info: TensorsInfo) -> Optional[TensorsInfo]:
        return TensorsInfo.make("int32", "1")

    # -- generation -------------------------------------------------------
    def _check_prompt(self, prompt: np.ndarray, max_len: int) -> None:
        """Fail before dispatch: the jitted cache write would raise an
        opaque XLA shape error (≙ llamacpp context-overflow error)."""
        if prompt.size == 0:
            raise ValueError("llm: empty prompt")
        if prompt.size > max_len:
            raise ValueError(
                f"llm: prompt length {prompt.size} exceeds max_len "
                f"{max_len}; raise custom=max_len:N")

    def _prefill_prompt(self, prompt: np.ndarray, max_len: int):
        """Bucket-pad the prompt and run ONE prefill dispatch into a
        fresh batch-1 cache of ``max_len``; returns (logits, cache).
        Prompts pad to power-of-two buckets so streams of varied lengths
        compile O(log max_len) prefill shapes, not one per length."""
        import jax.numpy as jnp

        bucket = 8
        while bucket < prompt.size:
            bucket *= 2
        bucket = min(bucket, max_len)
        padded = np.zeros(bucket, np.int32)
        padded[:prompt.size] = prompt
        with _obs_spans.region("nns.llm.prefill", "llm", bucket=bucket,
                               tokens=int(prompt.size)):
            cache = self._tfm.init_cache(self._cfg, batch=1,
                                         max_len=max_len)
            logits, cache = self._prefill(
                self._params, cache, jnp.asarray(padded[None, :]),
                jnp.asarray(prompt.size, jnp.int32))
        self.stats.add(prefill_dispatches=1,
                       prefill_computed_tokens=int(prompt.size))
        return logits, cache

    def _sampling(self):
        """(top_k, top_p) from custom properties (llamacpp sampler-chain
        parity: same knobs, same order — nucleus before temperature).
        Parsed once: this sits on the per-token host loop."""
        cached = getattr(self, "_sampling_cache", None)
        if cached is None:
            cached = self._sampling_cache = (
                int(self._opts.get("top_k", "0")),
                float(self._opts.get("top_p", "1.0")))
        return cached

    def _sample_host(self, sub, logits, temperature):
        """One host-loop sampling step, via the SAME in-graph helper the
        scanned chunk body uses, so every path draws identical tokens."""
        return self._tfm.sample_logits(sub[None], logits, temperature,
                                       *self._sampling())[:1]

    def _chunk_fn(self, steps: int, temperature: float):
        """Jitted K-step decode chunk, cached per (steps, sampling)."""
        top_k, top_p = self._sampling()
        key = (steps, float(temperature), top_k, top_p)
        fn = self._chunk_jits.get(key)
        if fn is None:
            import jax
            tfm, cfg = self._tfm, self._cfg
            fn = jax.jit(_obs_spans.named_program(
                "nns_llm_chunk",
                lambda p, c, l, k, a: tfm.decode_chunk_multi(
                    p, c, l, k, a, cfg, steps=steps,
                    temperature=temperature, top_k=top_k, top_p=top_p)))
            self._chunk_jits[key] = fn
        return fn

    def _chunk_fn_paged(self, steps: int, temperature: float):
        """Paged twin of _chunk_fn (decode_chunk_paged over the pool +
        block tables), cached per (steps, sampling)."""
        top_k, top_p = self._sampling()
        key = ("paged", steps, float(temperature), top_k, top_p)
        fn = self._chunk_jits.get(key)
        if fn is None:
            import jax
            tfm, cfg = self._tfm, self._cfg
            max_len = self._batch_max_len
            fn = jax.jit(_obs_spans.named_program(
                "nns_llm_chunk_paged",
                lambda p, pool, tbl, idx, l, k, a: tfm.decode_chunk_paged(
                    p, pool, tbl, idx, l, k, a, cfg, steps=steps,
                    max_len=max_len, temperature=temperature,
                    top_k=top_k, top_p=top_p)))
            self._chunk_jits[key] = fn
        return fn

    def _generate(self, prompt: np.ndarray, emit) -> None:
        import jax
        import jax.numpy as jnp

        prompt = np.asarray(prompt).reshape(-1)
        max_tokens = int(self._opts.get("max_tokens", "16"))
        temperature = float(self._opts.get("temperature", "0"))
        # the DEFAULT max_len derives from the bucket (not the raw
        # prompt length) so the cache shape — and with it the
        # decode-step compilation — is bucket-stable too
        bucket = 8
        while bucket < max(prompt.size, 1):
            bucket *= 2
        max_len = int(self._opts.get("max_len", str(bucket + max_tokens)))
        key = jax.random.PRNGKey(int(self._opts.get("seed", "0")))
        self._check_prompt(prompt, max_len)
        logits, cache = self._prefill_prompt(prompt, max_len)
        pos = prompt.size  # host-side cache index: no per-token device sync
        if self._chunk > 1:
            self._generate_chunked(logits, cache, pos, max_tokens, max_len,
                                   temperature, key, emit)
            return
        for i in range(max_tokens):
            if self._stop.is_set():
                return
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = self._sample_host(sub, logits, temperature)
            else:
                tok = jnp.argmax(logits, -1)
            # per-token emit IS the streaming boundary: materialize via
            # the sanctioned device_get, not an implicit __array__ sync
            emit(jax.device_get(tok).astype(np.int32))
            if i + 1 >= max_tokens or pos >= max_len:
                return  # nothing left to decode: skip the trailing step
            logits, cache = self._decode(self._params, cache,
                                         tok.astype(jnp.int32))
            self.stats.add(decode_dispatches=1, decode_steps=1)
            pos += 1

    def _generate_chunked(self, logits, cache, pos, max_tokens, max_len,
                          temperature, key, emit) -> None:
        """Single-stream chunked decode: [chunk] tokens per dispatch and
        per host fetch. Emits the exact token stream of the per-token
        loop (same key-split order, same capacity cutoff at max_len)."""
        import jax
        import jax.numpy as jnp

        mcache = {"k": cache["k"], "v": cache["v"],
                  "index": jnp.broadcast_to(cache["index"], (1,))}
        keys = key[None]
        active = jnp.ones((1,), bool)
        remaining = max_tokens
        while remaining > 0 and not self._stop.is_set():
            # each scan step samples THEN decodes; decode writes at the
            # stream's cache index, legal while index <= max_len-1
            k = min(self._chunk, remaining, max_len - pos)
            if k <= 0:
                # cache full: the per-token loop still emits one final
                # sampled token before stopping — mirror it, no decode
                if temperature > 0:
                    key2, sub = jax.random.split(keys[0])
                    tok = self._sample_host(sub, logits, temperature)
                else:
                    tok = jnp.argmax(logits, -1)
                emit(jax.device_get(tok).astype(np.int32))
                return
            toks, logits, mcache, keys = self._chunk_fn(k, temperature)(
                self._params, mcache, logits, keys, active)
            self.stats.add(decode_dispatches=1, decode_steps=k)
            toks_host = np.asarray(toks)  # ONE fetch for k tokens
            for j in range(k):
                emit(toks_host[j].astype(np.int32))
            pos += k
            remaining -= k

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        """Sync path: return the whole generation as one int32 tensor."""
        tokens: List[np.ndarray] = []
        self._generate(np.asarray(inputs[0]), tokens.append)
        return [np.concatenate(tokens) if tokens
                else np.zeros((0,), np.int32)]

    def invoke_async(self, inputs: Sequence[Any], ctx: Any = None) -> None:
        """1-in/N-out: one output frame per generated token, each
        dispatched with this invoke's ``ctx``. A prefill-role filter
        dispatches nothing: it ships the prompt's KV to its decode home
        and the decode replica emits the tokens."""
        prompt = np.asarray(inputs[0])
        if self._role == "prefill":
            flat = prompt.reshape(-1).astype(np.int32)
            self._check_prompt(flat, self._batch_max_len)
            t = threading.Thread(target=self._prefill_and_ship,
                                 args=(flat, ctx),
                                 name="llm-prefill-ship", daemon=True)
            self._threads.append(t)
            t.start()
            return
        if self._n_parallel > 1:
            # validate on the CALLER's thread so an oversized prompt is a
            # visible invoke error, not a silent scheduler drop
            flat = prompt.reshape(-1)
            self._check_prompt(flat, self._batch_max_len)
            with self._cond:
                rem = None
                if self._recovered is not None:
                    # resurrection: a re-submitted prompt that matches a
                    # snapshotted stream continues where it stopped —
                    # the emitted tokens (already delivered through the
                    # acked session pre-crash) join the prefill context
                    # and only the undelivered remainder is generated
                    rem, flat = self._adopt_recovered_locked(flat)
                self._enqueue_stream_locked((flat, ctx, rem))
            return

        def run():
            try:
                self._generate(
                    prompt, lambda tok: self._dispatch([tok], ctx))
            except Exception as exc:  # noqa: BLE001 — thread boundary
                logger.exception("llm generation failed")
                self._report_async_error(exc)

        t = threading.Thread(target=run, name="llm-generate", daemon=True)
        self._threads.append(t)
        t.start()

    def _enqueue_stream_locked(self, entry: tuple) -> None:
        """Queue a stream for the scheduler (caller holds _cond).
        Start-check under the lock: two racing submitters must not
        spawn two schedulers splitting one slot pool."""
        self._pending.append(entry)
        self._cond.notify_all()
        if self._sched is None or not self._sched.is_alive():
            self._sched = threading.Thread(
                target=self._sched_loop, name="llm-sched", daemon=True)
            self._sched.start()

    # -- prefill/decode split (role prop + KV handoff) ---------------------
    def _handoff_sender(self):
        with self._cond:
            if self._kv_tx is None:
                target = self._opts.get("handoff", "")
                if not target:
                    raise ValueError(
                        "llm: role:prefill requires custom=handoff:host:port")
                host, _, port = target.rpartition(":")
                from ..edge.kv import KvSender
                self._kv_tx = KvSender(host or "127.0.0.1", int(port),
                                       precision=self._kv_precision,
                                       stats=self.stats)
            return self._kv_tx

    def _prefill_and_ship(self, flat: np.ndarray, ctx: Any) -> None:
        """Prefill-role path: ONE prompt pass, then ship the KV prefix
        + last logits to the decode home over KV_XFER. The trace
        context (minted here when the invoke carried none) rides the
        wire, so prefill -> handoff -> decode renders as one tree."""
        from ..checkpoint.state import token_sha
        try:
            max_tokens = int(self._opts.get("max_tokens", "16"))
            t0 = time.time_ns()
            tctx = _ctx_of(ctx)
            if tctx is None and _obs_spans.ENABLED:
                from ..obs import context as _obs_ctx
                tctx = _obs_ctx.TraceContext(_obs_ctx.next_id(), 0, t0)
            l1, c1 = self._prefill_prompt(flat, self._batch_max_len)
            t = int(flat.size)
            k_np = np.asarray(c1["k"][:, 0, :t])
            v_np = np.asarray(c1["v"][:, 0, :t])
            if tctx is not None:
                _obs_spans.record_span("llm-prefill", "llm", t0,
                                       max(0, time.time_ns() - t0), tctx)
            ack = self._handoff_sender().send(
                token_sha(flat), flat, k_np, v_np,
                np.asarray(l1[0], np.float32), remaining=max_tokens,
                seed=int(self._opts.get("seed", "0")), ctx=tctx)
            self.stats.inc("kv_handoffs_out")
            if not ack.get("adopted"):
                self.stats.inc("kv_handoff_errors")
                logger.error("llm: decode replica refused stream %s",
                             ack.get("sid"))
        except Exception:  # noqa: BLE001 — ship failures must be visible, not fatal
            self.stats.inc("kv_handoff_errors")
            logger.exception("llm: kv handoff failed")

    def _on_kv_handoff(self, d: Dict) -> bool:
        """KvReceiver callback (per-connection listener thread): queue a
        shipped stream for paged admission. The returned flag becomes
        the KV_ACK ``adopted`` receipt — False tells the prefill side
        to try another decode home."""
        if self._stop.is_set() or self._params is None:
            return False
        flat = np.asarray(d["prompt"], np.int32).reshape(-1)
        try:
            self._check_prompt(flat, self._batch_max_len)
        except ValueError:
            logger.exception("llm: rejected KV handoff %s", d.get("sid"))
            return False
        with self._cond:
            rem = int(d.get("remaining", 0)) or None
            if self._recovered is not None:
                # a re-shipped conversation adopts its snapshot: the
                # pre-crash emitted tokens join the context and only
                # the undelivered remainder is generated
                rem2, flat = self._adopt_recovered_locked(flat)
                if rem2 is not None:
                    rem = rem2
            self._enqueue_stream_locked((flat, d.get("sid"), rem, d))
        self.stats.inc("kv_handoffs_in")
        return True

    # -- checkpoint/restore (checkpoint/) ----------------------------------
    def snapshot_state(self, snap_dir) -> Optional[Dict[str, Any]]:
        """Continuous-batching state for a preemption snapshot: per
        stream (queued or mid-generation) the prompt, the tokens already
        emitted, and the remaining budget. The KV cache itself is NOT
        saved — it is recomputed by one prefill over prompt+emitted at
        adoption time (cheaper and version-proof next to dumping a
        device cache). Single-stream mode (n_parallel=1) keeps no
        scheduler state and snapshots nothing."""
        with self._cond:
            pend = [{"prompt": np.asarray(e[0], np.int32).tolist(),
                     "emitted": [], "remaining": e[2]}
                    for e in self._pending]
            act = [{"prompt": s["prompt"].tolist(),
                    "emitted": list(s["emitted"]),
                    "remaining": int(s["remaining"])}
                   for s in (self._streams or [])
                   if s is not None and s["remaining"] > 0]
        if not pend and not act:
            return None
        return {"streams": act + pend}

    def restore_state(self, state, snap_dir) -> None:
        """Stash recovered streams; they are adopted lazily when a
        re-submitted prompt (the client's RESUME-driven resend, or a
        re-shipped KV handoff) matches one of them — see invoke_async
        and _on_kv_handoff."""
        with self._cond:
            self._recovered = state

    def _adopt_recovered_locked(self, flat: np.ndarray):
        """Match an incoming prompt against the recovered streams
        (caller holds _cond). Matching is by content digest
        (checkpoint.state.token_sha — the same digest that names wire
        handoffs), computed once per entry and once for the incoming
        prompt, instead of a full array comparison per entry. On a
        hit: continuation — the pre-crash prompt + already-emitted
        tokens become the prefill context and only the remaining
        budget is generated. Returns (remaining_override,
        prompt_to_queue)."""
        from ..checkpoint.state import token_sha

        entries = self._recovered.get("streams") or []
        sha = token_sha(flat)
        for i, ent in enumerate(entries):
            esha = ent.get("_sha")
            if esha is None:
                esha = ent["_sha"] = token_sha(
                    np.asarray(ent.get("prompt") or [], np.int32))
            if esha == sha:
                entries.pop(i)
                if not entries:
                    self._recovered = None
                emitted = np.asarray(ent.get("emitted") or [], np.int32)
                rem = ent.get("remaining")
                if emitted.size:
                    flat = np.concatenate(
                        [flat.astype(np.int32), emitted])
                return rem, flat
        return None, flat

    # -- continuous-batching scheduler (n_parallel > 1) --------------------
    def _sched_loop(self) -> None:
        """Decode M streams per dispatch. Admission: pending prompts are
        prefilled (one bucketed dispatch each) into free cache slots;
        every active slot then advances one token per SHARED decode
        dispatch, and finished slots free up mid-flight for waiting
        prompts — continuous batching, not static batching."""
        try:
            self._sched_body()
        except Exception as exc:  # noqa: BLE001 — thread boundary
            logger.exception("llm scheduler failed; in-flight streams lost")
            with self._cond:
                lost = sum(s is not None for s in self._streams or ())
                self._streams = None
            if self._backend is not None:
                # the next prompt starts a fresh loop over the SAME
                # pool: the lost streams' blocks must not stay taken
                for slot in range(self._n_parallel):
                    self._backend.free(slot)
            for _ in range(max(1, lost)):
                self._report_async_error(exc)

    def _finish_span(self, s: Dict[str, Any]) -> None:
        """A stream just finished: close its llm-decode span so the
        conversation's trace tree has a terminal node on this replica."""
        tctx = s.get("tctx")
        if tctx is None:
            return
        t0 = s.get("t0") or time.time_ns()
        _obs_spans.record_span("llm-decode", "llm", t0,
                               max(0, time.time_ns() - t0), tctx)

    def _sched_body(self) -> None:
        import jax
        import jax.numpy as jnp

        m = self._n_parallel
        max_tokens = int(self._opts.get("max_tokens", "16"))
        max_len = self._batch_max_len
        temperature = float(self._opts.get("temperature", "0"))
        seed = int(self._opts.get("seed", "0"))
        # the cache layout is a pluggable backend: contiguous per-slot
        # lanes (stream-counted) or the paged block pool
        # (token-budgeted). Admission, sampling, dispatch bookkeeping
        # and snapshots are THIS one loop either way — the parity gate
        # only has to reason about the cache math, not two schedulers.
        backend = (_PagedBackend(self, m, max_len) if self._paged
                   else _ContigBackend(self, m, max_len))
        self._backend = backend
        tok = jnp.zeros((m,), jnp.int32)
        streams: List[Optional[Dict[str, Any]]] = [None] * m
        with self._cond:
            self._streams = streams  # published for snapshot_state
        while not self._stop.is_set():
            # -- admit pending streams into free slots
            with self._cond:
                if all(s is None for s in streams) and not self._pending:
                    with _obs_spans.region("nns.llm.wait", "llm"):
                        while not self._pending \
                                and not self._stop.is_set():
                            self._cond.wait(0.1)
                if self._stop.is_set():
                    return
                admit = []
                for slot in range(m):
                    if streams[slot] is None and self._pending:
                        admit.append((slot, self._pending.pop(0)))
            requeue = []
            for slot, entry in admit:
                prompt, ctx, rem = entry[0], entry[1], entry[2]
                kv = entry[3] if len(entry) > 3 else None
                budget = max_tokens if rem is None else int(rem)
                t_admit = time.time_ns()
                tctx = kv.get("ctx") if kv is not None else _ctx_of(ctx)
                try:
                    # the ring keeps the local admission as the
                    # conversation's ``llm-prefill`` node; a handoff's
                    # context already chains prefill -> kv-handoff, so
                    # its admission hangs off no frame
                    with _obs_spans.region(
                            "nns.llm.admit", "llm",
                            tctx if kv is None else None,
                            name="llm-prefill" if kv is None
                            else "llm-admit",
                            slot=slot, tokens=int(np.size(prompt))):
                        if kv is not None:
                            backend.admit_handoff(slot, prompt, kv, budget)
                        else:
                            self._check_prompt(prompt, max_len)
                            backend.admit(slot, prompt, budget)
                except _PoolFull:
                    # token-budgeted admission: not enough KV blocks
                    # right now — requeue; running streams release
                    # blocks as they finish
                    requeue.append(entry)
                    continue
                except Exception as exc:  # noqa: BLE001 — drop THIS prompt only
                    logger.exception("llm: prompt rejected at admission")
                    self._report_async_error(exc)
                    continue
                # per-stream PRNG key: the sample sequence matches the
                # n_parallel=1 path for the same seed, independent of
                # which other prompts happen to be in flight. rem
                # overrides the budget for a stream adopted from a
                # preemption snapshot (the rest was emitted pre-crash);
                # handoff streams sample with the seed the prefill
                # replica shipped, so the split emits the monolithic
                # token stream.
                streams[slot] = {"ctx": ctx,
                                 "remaining": budget,
                                 "pos": int(prompt.size),
                                 "prompt": np.asarray(prompt,
                                                      np.int32).copy(),
                                 "emitted": [],
                                 "key": jax.random.PRNGKey(
                                     int(kv["seed"]) if kv is not None
                                     else seed),
                                 "tctx": tctx, "t0": t_admit}
            if requeue:
                with self._cond:
                    if all(s is None for s in streams):
                        # nothing is running, so nothing will ever free
                        # blocks: the head request exceeds the whole
                        # pool — drop it loudly instead of deadlocking
                        head = requeue.pop(0)
                        msg = (f"llm: stream of "
                               f"{int(np.asarray(head[0]).size)} tokens "
                               f"needs more KV blocks than pool_blocks="
                               f"{self._pool_mgr.n_blocks} holds; dropped")
                        logger.error(msg)
                        self._report_async_error(_PoolFull(msg))
                    self._pending[:0] = requeue
            active_np = np.array([s is not None for s in streams])
            if not active_np.any():
                continue
            if self._chunk > 1:
                self._sched_chunk(streams, active_np, backend, max_len,
                                  temperature)
                continue
            # -- sample on device, D2H just the M token ids
            if temperature > 0:
                subs = []
                for s in streams:
                    if s is None:
                        subs.append(jax.random.PRNGKey(0))
                        continue
                    s["key"], sub = jax.random.split(s["key"])
                    subs.append(sub)
                tok = self._tfm.sample_logits(
                    jnp.stack(subs), backend.logits, temperature,
                    *self._sampling())
            else:
                tok = jnp.argmax(backend.logits, -1)
            tok = tok.astype(jnp.int32)
            with _obs_spans.region("nns.llm.fetch", "llm"):
                tok_host = jax.device_get(tok)  # ONE fetch for all slots
            with _obs_spans.region("nns.llm.emit", "llm"):
                for slot, s in enumerate(streams):
                    if s is None:
                        continue
                    self._dispatch([tok_host[slot:slot + 1]], s["ctx"])
                    with self._cond:
                        # bookkeeping under _cond: a preemption snapshot
                        # reads (prompt, emitted, remaining) coherently
                        s["emitted"].append(int(tok_host[slot]))
                        s["remaining"] -= 1
                        s["pos"] += 1
                    # pos is one past the next decode's cache-write
                    # position (the write lands at pos-1), so the stream
                    # survives while pos <= max_len — matching the
                    # single-stream loop's emit-then-check ordering
                    if s["remaining"] <= 0 or s["pos"] > max_len:
                        streams[slot] = None
                        # keep the mask current: a lane that just
                        # finished must not keep writing/advancing its
                        # cache in the trailing decode (the decode step
                        # also position-guards at max_len)
                        active_np[slot] = False
                        backend.free(slot)
                        self._finish_span(s)
            if active_np.any():
                with _obs_spans.region("nns.llm.chunk", "llm", steps=1):
                    backend.step(tok, active_np)
                self.stats.add(decode_dispatches=1, decode_steps=1)

    def _sched_chunk(self, streams, active_np, backend, max_len,
                     temperature) -> None:
        """One chunked round of the continuous-batching loop: K
        sample+decode steps in ONE dispatch, K tokens per stream per
        host fetch. K adapts to the deepest stream still running, so a
        stream never emits past its budget; streams that finish
        mid-chunk have their surplus lane tokens discarded (their lanes
        compute garbage either way). New prompts admit between chunks —
        the admission-latency/throughput knob is ``custom=chunk:K``."""
        import jax
        import jax.numpy as jnp

        # emits each stream still owes; K serves the deepest one fully.
        # The +1 is the capacity tail: the final token a lane emits at
        # pos == max_len is sampled in-scan from the last legal decode's
        # logits — the decode that FOLLOWS that sample is position-
        # guarded inside the decode step (pos < max_len), so it cannot
        # clamp a write onto row max_len-1 (the single-stream invariant
        # of _generate_chunked, enforced in-graph here).
        emits_left = [min(s["remaining"], max_len - s["pos"] + 1)
                      if s else 0 for s in streams]
        k = min(self._chunk, max(emits_left))
        if temperature > 0:
            # one cached filler key for idle slots: a fresh eager
            # PRNGKey per slot per round is a dispatch each, eroding
            # the chunking win
            if not hasattr(self, "_idle_key"):
                self._idle_key = jax.random.PRNGKey(0)
            keys = jnp.stack([s["key"] if s else self._idle_key
                              for s in streams])
        else:
            keys = jnp.zeros((len(streams), 2), jnp.uint32)
        with _obs_spans.region("nns.llm.chunk", "llm", steps=k):
            toks, keys = backend.chunk(k, temperature, keys, active_np)
        self.stats.add(decode_dispatches=1, decode_steps=k)
        with _obs_spans.region("nns.llm.fetch", "llm"):
            toks_host = np.asarray(toks)  # [k, M]: ONE fetch for the chunk
        with _obs_spans.region("nns.llm.emit", "llm"):
            for slot, s in enumerate(streams):
                if s is None:
                    continue
                for j in range(min(k, emits_left[slot])):
                    self._dispatch([toks_host[j, slot:slot + 1]], s["ctx"])
                    with self._cond:
                        s["emitted"].append(int(toks_host[j, slot]))
                        s["remaining"] -= 1
                        s["pos"] += 1
                if temperature > 0:
                    s["key"] = keys[slot]
                if s["remaining"] <= 0 or s["pos"] > max_len:
                    streams[slot] = None
                    backend.free(slot)
                    self._finish_span(s)


register_alias("llamacpp", "llm")
register_alias("llama2c", "llm")

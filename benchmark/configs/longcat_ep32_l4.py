"""Program-side entry of configuration ``longcat_ep32_l4``: a
``get_model()`` file for ``tensor_filter framework=jax``. The model is
``models/longcat.py`` at the configuration's sizes, told which of the
router's real experts it holds (rank ``expert_rank`` of
``expert_parallel``); the weights are the benchmark's, made from the
run's seed in bfloat16, so the plain reference shares them and takes
nothing the program made."""


def get_model():
    import math
    import statistics

    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import longcat
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    held = int(s["n_routed_experts"])
    if held * int(s["expert_parallel"]) != int(s["n_routed_experts_total"]):
        raise ValueError("the held experts are not the router's share")
    cfg = longcat.LongCatConfig.from_hf(
        # the sizes keep numbers only: the two mla_scale_* flags and
        # zero_expert_type come from the configuration file itself
        {**ses.config, **s,
         "n_routed_experts": int(s["n_routed_experts_total"])},
        held_first=held * int(s["expert_rank"]), held_count=held,
        dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: longcat.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, 2 * cfg.num_layers
    # the classifier's logits spread by the normal quantile of top /
    # width: the chosen scores then carry about half the softmax's mass
    spread = statistics.NormalDist().inv_cdf(
        1.0 - cfg.moe_topk / cfg.router_width)
    edge = math.exp(spread * spread / 2) / cfg.router_width

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name.endswith("norm") or name == "norm_f":
            return 1.0, 0.02
        if name == "gate":
            return 0.0, spread * d ** -0.5
        if name == "bias":
            return 0.0, 0.1 * edge    # small, not zero: it changes choices
        if name in ("wo", "w2"):    # the projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name in ("embed", "head"):
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info = longcat.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info

"""Program-side entry of configuration ``trinity_mini_pp8_l5``: a
``get_model()`` file for ``tensor_filter framework=jax``. The model is
``models/afmoe.py`` at the configuration's sizes, told which of the
router's experts it holds (rank ``expert_rank`` of ``expert_parallel``:
all of them, the pipeline stage holds its layers whole); the weights
are the benchmark's, made from the run's seed in bfloat16, so the plain
reference shares them and takes nothing the program made."""


def get_model():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import afmoe
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    held, rest = divmod(int(s["num_experts"]), int(s["expert_parallel"]))
    if rest:
        raise ValueError("the experts do not divide over expert_parallel")
    # the sizes keep numbers only: layer_types, mup_enabled, route_norm
    # and score_func come from the configuration file itself
    cfg = afmoe.AfmoeConfig.from_hf(
        {**ses.config, **s}, held_first=held * int(s["expert_rank"]),
        held_count=held, dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: afmoe.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, cfg.num_hidden_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name.endswith("norm") or name == "norm_f":
            return 1.0, 0.02
        if name == "bias":
            return 0.0, 0.02        # small, not zero: it changes choices
        if name in ("wo", "w2"):    # the projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name in ("embed", "head"):
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info = afmoe.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info

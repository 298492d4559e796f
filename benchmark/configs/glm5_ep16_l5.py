"""Program-side entry of configuration ``glm5_ep16_l5``: a ``get_model()``
file for ``tensor_filter framework=jax``. The model is
``models/glm_dsa.py`` at the configuration's sizes, told which of the
router's experts it holds (rank ``expert_rank`` of ``expert_parallel``);
the weights are the benchmark's, made from the run's seed in bfloat16,
so the plain reference shares them and takes nothing the program made."""


def get_model():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import glm_dsa
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    held = int(s["n_routed_experts"])
    if held * int(s["expert_parallel"]) != int(s["n_routed_experts_total"]):
        raise ValueError("the held experts are not the router's share")
    cfg = glm_dsa.GLMDSAConfig.from_hf(
        {**s, "n_routed_experts": int(s["n_routed_experts_total"])},
        held_first=held * int(s["expert_rank"]), held_count=held,
        dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: glm_dsa.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, cfg.num_hidden_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name.endswith("norm") or name == "norm_f" or name == "k_norm_w":
            return 1.0, 0.02
        if name == "k_norm_b":
            return 0.0, 0.02
        if name == "bias":
            return 0.0, 0.02        # small, not zero: it changes choices
        if name in ("wo", "w2"):    # the two projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name in ("embed", "head"):
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info = glm_dsa.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info

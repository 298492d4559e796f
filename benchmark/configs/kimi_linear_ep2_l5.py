"""Program-side entry of configuration ``kimi_linear_ep2_l5``: a
``get_model()`` file for ``tensor_filter framework=jax``. The model is
``models/kimi_linear.py`` at the configuration's sizes, told which of
the router's experts it holds (rank ``expert_rank`` of
``expert_parallel``: half of them); the weights are the benchmark's,
made from the run's seed in bfloat16, so the plain reference shares
them and takes nothing the program made."""


def get_model():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import kimi_linear
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    held = int(s["num_experts"])
    if held * int(s["expert_parallel"]) != int(s["num_experts_total"]):
        raise ValueError("the held experts are not the router's share")
    # the sizes keep numbers only: the nested linear_attn_config and the
    # flags come from the configuration file itself
    cfg = kimi_linear.KimiLinearConfig.from_hf(
        {**ses.config, **s, "num_experts": int(s["num_experts_total"])},
        held_first=held * int(s["expert_rank"]), held_count=held,
        dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: kimi_linear.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, cfg.num_hidden_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name.endswith("norm") or name == "norm_f":
            return 1.0, 0.02
        if name == "bias":
            return 0.0, 0.02        # small, not zero: it changes choices
        if name in ("A_log", "dt_bias"):
            return 0.0, 3.0 ** -0.5     # u uniform in (-1, 1): spread below
        if name in ("wo", "w2"):    # the projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name == "embed":
            # unit rows: at d ** -0.5 the first mixer's output is 15x the
            # embedding, every token's stream is that layer's shared part
            # and a sequence's tokens choose the same experts
            return 0.0, 1.0
        if name == "head":
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in (a convolution's: 4)

    @jax.jit
    def spread(a_log, dt_bias):
        """The gated delta-rule layers' initialisation from two uniform
        draws in (-1, 1): ``A_log = log(U(1, 16))``, ``dt_bias`` the
        inverse softplus of ``exp(U(log 0.001, log 0.1))``."""
        u, w = ((x.astype(jnp.float32) + 1.0) / 2.0 for x in (a_log, dt_bias))
        step = jnp.exp(jnp.log(0.001) + w * jnp.log(100.0))
        return (jnp.log(1.0 + 15.0 * u).astype(a_log.dtype),
                (step + jnp.log(-jnp.expm1(-step))).astype(dt_bias.dtype))

    # the two small leaves alone go through it: the tree is made once
    # and never held twice
    tree = weights.make_tree(shapes, rule, ses.seed)
    for layer in tree["layers"]:
        a = layer["attn"]
        if "A_log" in a:
            a["A_log"], a["dt_bias"] = spread(a["A_log"], a["dt_bias"])
    ses.weights = tree
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info = kimi_linear.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info

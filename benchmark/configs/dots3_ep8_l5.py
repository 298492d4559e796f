"""Program-side entry of configuration ``dots3_ep8_l5``: a ``get_model()``
file for ``tensor_filter framework=jax``. The model is
``models/dots3_note.py`` at the configuration's sizes, told which of the
router's experts it holds (rank ``expert_rank`` of ``expert_parallel``);
the weights are the benchmark's, made from the run's seed in bfloat16,
so the plain reference shares them and takes nothing the program made."""


def get_model():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import dots3_note
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    held = int(s["n_routed_experts"])
    if held * int(s["expert_parallel"]) != int(s["n_routed_experts_total"]):
        raise ValueError("the held experts are not the router's share")
    # the sizes keep numbers only: layer_types, the gate types and the
    # rescale come from the configuration file itself
    router = int(s["n_routed_experts_total"])
    cfg = dots3_note.Dots3NoteConfig.from_hf(
        {**ses.config, **s, "n_routed_experts": router},
        held_first=held * int(s["expert_rank"]), held_count=held,
        dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: dots3_note.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, cfg.num_hidden_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name in ("q_norm", "kv_norm"):
            # the latents' norms about sqrt(rank / d): times the published
            # sqrt(d / rank) the latents come out of unit scale, as a
            # trained model's norms make them; at 1 the rescale makes the
            # seeded attention about six times sharper than any other cell's
            s = (shape[-1] / d) ** 0.5
            return s, 0.02 * s
        if name.endswith("norm") or name == "norm_f" or name == "k_norm_w":
            return 1.0, 0.02
        if name == "k_norm_b":
            return 0.0, 0.02
        if name == "bias":
            # about one gap between the 8th and 9th of 256 sigmoid scores:
            # it changes choices, and each expert's load stays within about
            # a tenth of the mean, as a trained balancing bias keeps it
            return 0.0, 0.005
        if name in ("wo", "w2"):    # the two projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name == "embed":
            # unit rows, as kimi_linear_ep2_l5's: at d ** -0.5 the dense
            # layer alone adds about 13 times the token's own row
            return 0.0, 1.0
        if name == "head":
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info = dots3_note.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info

"""Program-side entry of configuration ``brumby_14b_pp4_l10``: a
``get_model()`` file for ``tensor_filter framework=jax``. The model is
``models/brumby.py`` at the configuration's sizes; the weights are the
benchmark's, made from the run's seed in bfloat16, so the plain
reference shares them and takes nothing the program made. It returns a
fifth item, the retention's state before a document: the filter keeps
that state on the chip from one buffer of the stream to the next."""


def get_model():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import brumby
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    cfg = brumby.BrumbyConfig.from_hf({**ses.config, **s},
                                      dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: brumby.init_params(cfg, jax.random.PRNGKey(0)))
    d, n = cfg.hidden_size, cfg.num_hidden_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name.endswith("norm") or name == "norm_f":
            return 1.0, 0.02
        if name == "bg":
            return 0.0, 3.0 ** -0.5     # u uniform in (-1, 1): spread below
        if name == "wg":                # a tenth of fan-in: the offset leads
            return 0.0, 0.1 * shape[-2] ** -0.5
        if name in ("wo", "w2"):    # the projections back to the stream
            return 0.0, (2 * shape[-2] * n) ** -0.5
        if name == "embed":         # unit rows: a token leads its stream
            return 0.0, 1.0
        if name == "head":
            return 0.0, d ** -0.5
        return 0.0, shape[-2] ** -0.5       # fan_in

    lo, hi = float(s["gate_tau_min"]), float(s["gate_tau_max"])

    @jax.jit
    def spread(u):
        """The gates' offsets from a uniform draw in (-1, 1): ``b =
        logit(exp(-1 / tau))``, ``tau`` log-uniform from ``gate_tau_min``
        to ``gate_tau_max`` tokens."""
        tau = jnp.exp(jnp.log(lo) + (u.astype(jnp.float32) + 1.0) / 2.0
                      * jnp.log(hi / lo))
        # logit(exp(-1 / tau)) = -1 / tau - log(1 - exp(-1 / tau))
        return (-1.0 / tau - jnp.log(-jnp.expm1(-1.0 / tau))).astype(u.dtype)

    tree = weights.make_tree(shapes, rule, ses.seed)
    for layer in tree["layers"]:
        layer["attn"]["bg"] = spread(layer["attn"]["bg"])
    ses.weights = tree
    seq = int(ses.traffic["tokens_per_buffer"])
    apply_fn, in_info, out_info, state = brumby.frame_model(cfg, seq)
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    return apply_fn, ses.weights, in_info, out_info, state

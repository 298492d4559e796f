"""Program-side entry of configuration ``dsllm7b_l12``: a ``get_lm()``
file for ``tensor_filter framework=llm``. The block is
``models/transformer.py``'s (MHA + RoPE + RMSNorm + SwiGLU, untied head)
at DeepSeek-LLM-7B's widths; the weights are the benchmark's, made from
the run's seed in bfloat16, so the plain reference shares them."""


def get_lm():
    import jax
    import jax.numpy as jnp
    from nnstreamer_tpu.models import transformer as tfm
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    if s["num_key_value_heads"] != s["num_attention_heads"]:
        raise ValueError("models/transformer.py has no grouped KV heads")
    cfg = tfm.GPTConfig(
        vocab=s["vocab_size"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], n_layers=s["num_hidden_layers"],
        d_ff=s["intermediate_size"], max_seq=s["max_position_embeddings"],
        rope_theta=float(s["rope_theta"]), dtype=jnp.bfloat16)
    # the program's own init is traced for its tree and shapes, never run
    shapes = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    d, f, n = cfg.d_model, cfg.ff, cfg.n_layers

    def rule(path, shape):
        name = path.rsplit("['", 1)[-1].rstrip("']")
        if name in ("ln1", "ln2", "ln_f"):
            return 1.0, 0.02
        if name == "wo":
            return 0.0, (2 * d * n) ** -0.5
        if name == "w2":
            return 0.0, (2 * f * n) ** -0.5
        return 0.0, d ** -0.5       # embed, head, wq, wk, wv, w1, w3

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    return ses.weights, cfg

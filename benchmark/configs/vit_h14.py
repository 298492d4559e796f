"""Program-side entry of configuration ``vit_h14``: a ``get_model()``
file for ``tensor_filter framework=jax``. The model is the zoo's ``vit``
builder at the configuration's sizes; the weights are the benchmark's,
made from the run's seed, so the plain reference shares them and takes
nothing the program made."""


def get_model():
    import jax
    from nnstreamer_tpu.models import zoo
    from nnstreamer_tpu.tensors.info import TensorsInfo
    from nnsbench import session, weights

    ses = session.current()
    s = ses.sizes
    if s["intermediate_size"] != 4 * s["hidden_size"]:
        raise ValueError("models/vit.py fixes mlp_ratio=4")
    box = {}

    def abstract_params():
        apply_fn, params, _, _ = zoo.build(
            "vit", size=str(s["image_size"]), patch=str(s["patch_size"]),
            d_model=str(s["hidden_size"]), layers=str(s["num_hidden_layers"]),
            heads=str(s["num_attention_heads"]),
            classes=str(s["num_classes"]))
        box["apply"] = apply_fn
        return params

    # the builder's own random init is traced, never run: no second copy
    # of the parameters ever sits on the device
    shapes = jax.eval_shape(abstract_params)

    def rule(path, shape):
        if path.endswith("['kernel']"):
            # flax attention kernels: query/key/value [d, heads, hd],
            # out [heads, hd, d]; Dense and Conv: [..., fan_in, fan_out]
            qkv = any(n in path for n in ("['query']", "['key']",
                                          "['value']"))
            fan_in = shape[0] if qkv else 1
            if not qkv:
                for n in shape[:-1]:
                    fan_in *= n
            return 0.0, fan_in ** -0.5
        if path.endswith("['scale']"):
            return 1.0, 0.02
        return 0.0, 0.02            # biases, pos_embed

    ses.weights = weights.make_tree(shapes, rule, ses.seed)
    apply_fn = box["apply"]
    fault = ses.fault
    if fault is not None:
        apply_fn = fault(apply_fn)
    rows = int(ses.traffic.get("frames_per_buffer", 0))
    hw = s["image_size"]
    dims = f"3:{hw}:{hw}" + (f":{rows}" if rows else "")
    out = str(s["num_classes"]) + (f":{rows}" if rows else "")
    return (apply_fn, ses.weights, TensorsInfo.make("uint8", dims),
            TensorsInfo.make("float32", out))

"""The GPT family: what the harness needs to know of one model family.

A configuration file names its family (`"family": "gpt"`), and the harness
imports `family_<name>` from this directory. A family gives the windows
the program's model at a configuration's sizes, the way from the weights
the reference makes to the program's parameters, the plain reference, and
the work counts. Another family is another such file, with its reference
and its work counts beside it, and no edit here.
"""
from __future__ import annotations

import reference                    # noqa: F401  (the family's reference)
import work                         # noqa: F401  (the family's work counts)


def build_model(cfg, train, dtype=None):
    """The program's own GPT at the configuration's sizes; served models
    are built in `dtype` at once, not in float32 first."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining, GPTModel
    paddle.seed(0)
    d = int(cfg["n_embd"])
    prior = paddle.get_default_dtype()
    if not train:
        paddle.set_default_dtype(dtype)
    try:
        net = GPTForPretraining(GPTModel(
            vocab_size=int(cfg["vocab_size"]), hidden_size=d,
            num_layers=int(cfg["n_layer"]), num_heads=int(cfg["n_head"]),
            intermediate_size=int(cfg.get("n_inner") or 4 * d),
            max_position_embeddings=int(cfg["n_positions"]),
            attn_dropout_prob=float(cfg["attn_pdrop"]),
            hidden_dropout_prob=float(cfg["resid_pdrop"]),
            layer_norm_epsilon=float(cfg["layer_norm_epsilon"])))
    finally:
        paddle.set_default_dtype(prior)
    if train:
        net.train()
    else:
        net.to(dtype=dtype)
        net.eval()
    return net


def program_leaf(name):
    """The program's parameter name -> the reference's leaf name."""
    name = name.replace("gpt.", "", 1)
    table = {"embeddings.word_embeddings.weight": "wte",
             "embeddings.position_embeddings.weight": "wpe",
             "ln_f.weight": "ln_f.w", "ln_f.bias": "ln_f.b"}
    if name in table:
        return table[name]
    _, i, rest = name.split(".", 2)
    rest = rest.replace("attn.qkv_proj", "qkv").replace(
        "attn.out_proj", "out").replace("mlp.", "")
    return "h.%s.%s" % (i, rest.replace(".weight", ".w").replace(
        ".bias", ".b"))


def load_weights(net, weights, keep=False):
    """Give the program the weights made from the seed, in each
    parameter's own type. With keep, the program gets copies (its step
    donates them) and {leaf: array as loaded} is returned."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def unstack(w):
        out = {}
        for k, v in w.items():
            if k.startswith("h."):
                for i in range(v.shape[0]):
                    out["h.%d.%s" % (i, k[2:])] = v[i]
            else:
                out[k] = v
        return out

    leaves = unstack(weights)
    loaded = {}
    for name, p in net.named_parameters():
        leaf = program_leaf(name)
        arr = leaves[leaf]
        if tuple(arr.shape) != tuple(p._data.shape):
            raise ValueError("weight %s: made %s, the program has %s"
                             % (name, arr.shape, p._data.shape))
        arr = arr.astype(p._data.dtype)
        p._data = jnp.array(arr, copy=True) if keep else arr
        if keep:
            loaded[leaf] = arr
    return loaded

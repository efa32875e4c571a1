"""The `mla_moe` family (`"family": "mla_moe"` in a configuration file): what
the harness needs to know of decoders with multi-head LATENT attention over
routed experts (the language model of Kimi-VL-A3B).

The program's model is `paddle_tpu.models.decoder.DecoderLM` built from the
configuration's published keys (`DecoderConfig.from_hf`): on every layer a
query of `qk_nope_head_dim + qk_rope_head_dim` a head, one normed latent of
`kv_lora_rank` a token from which every head's keys and values are
up-projected, a rotary part of `qk_rope_head_dim` that the heads share,
rotary over adjacent pairs; a leading dense SwiGLU layer
(`first_k_dense_replace`, an integer `moe_layer_freq`) and sigmoid-routed
top-k experts with a shared expert on the rest; an untied head. The served
cache keeps one row of `kv_lora_rank + qk_rope_head_dim` numbers a token a
layer and nothing else of the token; prefill expands the latent, decode
absorbs the up-projections. Beside this file: `reference_mla_moe.py` (the
plain float32 reference, which always expands, and the weights from the
seed) and `work_mla_moe.py` (the work counts the per-layer metrics name).

THE SHARE, as in the `mimo_v2` family: `n_routed_experts` counts the experts
HELD here, `experts_held` = [first, count] says which, and `router_experts`
is the width the router keeps; the shared expert is whole on every chip.

Serving only, the model built ABSTRACT and handed the seed's arrays as they
are (`load_weights`). `DecoderConfig` has had its latent branch since the PR
that brought this file; an older program has none and `build_model` raises
at once, before any weight is made.
"""
from __future__ import annotations

import reference_mla_moe as reference   # noqa: F401  (the family's reference)
import work_mla_moe as work             # noqa: F401  (the family's work counts)
# a leaf of the reference is one parameter of the program, named and adopted
# as in the afmoe family: the same `DecoderLM` underneath
from family_afmoe import load_weights, program_leaf  # noqa: F401


def decoder_config(cfg):
    """The program's `DecoderConfig` of a configuration of this family."""
    from paddle_tpu.models.decoder import DecoderConfig
    fields = getattr(DecoderConfig, "__dataclass_fields__", {})
    if "latent_rank" not in fields:
        raise SystemExit(
            "family_mla_moe: this program's decoder block has no latent "
            "attention (paddle_tpu.models.decoder.DecoderConfig lacks "
            "latent_rank): the mla_moe family cannot be built — not run")
    published = dict(cfg, n_routed_experts=int(
        cfg.get("router_experts") or cfg["n_routed_experts"]))
    held = cfg.get("experts_held")
    return DecoderConfig.from_hf(
        published, experts_held=tuple(held) if held else None)


def build_model(cfg, train, dtype=None):
    """The program's decoder at the configuration's sizes, as shapes
    alone: `load_weights` gives it its arrays."""
    from paddle_tpu.models.decoder import DecoderLM
    if train:
        raise NotImplementedError(
            "the mla_moe family is served only: the program has no backward "
            "for its block yet")
    net = DecoderLM(decoder_config(cfg), dtype or "bfloat16", abstract=True)
    net.eval()
    return net

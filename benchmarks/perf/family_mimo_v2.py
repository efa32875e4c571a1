"""The `mimo_v2` family (`"family": "mimo_v2"` in a configuration file): what
the harness needs to know of MiMo-V2-style decoders.

The program's model is `paddle_tpu.models.decoder.DecoderLM` built from the
configuration's published keys (`DecoderConfig.from_hf`): one full layer to
five sliding-window layers, each kind with its own key-value heads, a key
row wider than a value row, rotary on the first third of a head with a base
a kind, a scale on the values, a sink in the window layers' softmax, a
leading dense SwiGLU layer and sigmoid-routed top-k experts with no shared
expert on the rest, an untied head. Beside this file: `reference_mimo_v2.py`
(the plain float32 reference and the weights from the seed) and
`work_mimo_v2.py` (the work counts the per-layer metrics name).

THE SHARE. A configuration of this family gives one chip's share of a
deployment (its `deployment` key): `n_routed_experts` counts the experts
HELD here, `experts_held` = [first, count] says which, and `router_experts`
is the width the router keeps. The program's expert layer is told the same
(`MoEConfig.experts_held`): it scores all `router_experts`, keeps its top-k
and computes what its own experts give.

As for the `afmoe` family: serving only, the model built ABSTRACT and
handed the seed's arrays as they are (`load_weights`), so the weights are on
the device once. `DecoderConfig` has had `experts_held`, and `from_hf` this
configuration's keys, since the PR that brought this file; an older program
has neither and `build_model` raises at once, before any weight is made.
"""
from __future__ import annotations

import reference_mimo_v2 as reference   # noqa: F401  (the family's reference)
import work_mimo_v2 as work             # noqa: F401  (the family's work counts)
# a leaf of the reference is one parameter of the program, named and adopted
# as in the afmoe family: the same `DecoderLM` underneath
from family_afmoe import load_weights, program_leaf  # noqa: F401


def decoder_config(cfg):
    """The program's `DecoderConfig` of a configuration of this family."""
    from paddle_tpu.models.decoder import DecoderConfig
    fields = getattr(DecoderConfig, "__dataclass_fields__", {})
    if "sink_kinds" not in fields:
        raise SystemExit(
            "family_mimo_v2: this program's decoder block has no sink, no "
            "key size apart from its value size and no heads by layer kind "
            "(paddle_tpu.models.decoder.DecoderConfig lacks them): the "
            "mimo_v2 family cannot be built — not run")
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
            "the mimo_v2 family is served only: the program has no backward "
            "for its block yet")
    net = DecoderLM(decoder_config(cfg), dtype or "bfloat16", abstract=True)
    net.eval()
    return net

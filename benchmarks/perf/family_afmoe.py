"""The `afmoe` family (`"family": "afmoe"` in a configuration file): what the
harness needs to know of Trinity-style decoders.

The program's model is `paddle_tpu.models.decoder.DecoderLM` built from the
configuration's published keys (`DecoderConfig.from_hf`): grouped-query
gated attention with QK-norm, sliding-window layers with rotary positions
and full layers without, four RMSNorms a layer, a dense SwiGLU on the
leading layers and sigmoid-routed top-k experts with a shared expert on the
rest, an untied head. Beside this file: `reference_afmoe.py` (the plain
float32 reference and the weights from the seed) and `work_afmoe.py` (the
work counts the per-layer metrics name).

What this family adds to the harness's contract (README.md, "Model
family"): serving only. `build_model` makes the model ABSTRACT — shapes, no
arrays — and `load_weights` hands it the seed's arrays as they are, a leaf
of the reference being one parameter of the program: the 8.5 GB of a
published-width configuration are on the device once, never twice. There is
no training window for this family yet (`train=True` raises): the program
has no backward for it.
"""
from __future__ import annotations

import reference_afmoe as reference     # noqa: F401  (the family's reference)
import work_afmoe as work               # noqa: F401  (the family's work counts)


def build_model(cfg, train, dtype=None):
    """The program's decoder at the configuration's sizes, as shapes
    alone: `load_weights` gives it its arrays."""
    from paddle_tpu.models.decoder import DecoderConfig, DecoderLM
    if train:
        raise NotImplementedError(
            "the afmoe family is served only: the program has no backward "
            "for its block yet")
    net = DecoderLM(DecoderConfig.from_hf(cfg), dtype or "bfloat16",
                    abstract=True)
    net.eval()
    return net


def program_leaf(name):
    """The program's parameter name -> the reference's leaf name."""
    return name.replace("layers.", "l", 1)


def load_weights(net, weights, keep=False):
    """Give the program the weights made from the seed: the very arrays,
    no copy and no cast (a leaf is made in the type it is served in)."""
    if keep:
        raise NotImplementedError("keep= is the training windows'")
    net.load_arrays({name: weights[program_leaf(name)]
                     for name, _ in net.named_parameters()})
    return {}

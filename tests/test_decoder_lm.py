"""The configurable decoder (`models/decoder.py`), its no-drop expert layer
(`incubate/moe.py`), the two-kind paged cache and the grouped-query kernels,
against the plain float32 reference of the `afmoe` family
(`benchmarks/perf/reference_afmoe.py`) on seeded weights: the checks every
family shares (`decoder_conformance.py`) and this family's own.

Size: d 64, 4 query / 2 key-value heads of 16, 8 experts top-2 + 1 shared,
window 8, layers [dense-sliding | sliding, sliding, sliding, full],
float32 on the CPU.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "perf"))

import reference_afmoe as ref                                  # noqa: E402
from decoder_conformance import (                              # noqa: E402
    DecoderConformance, family_fixtures, interpret, kernel_flags, randn)
from paddle_tpu.framework.flags import set_flags               # noqa: E402
from paddle_tpu.incubate import moe as moe_ops                 # noqa: E402
from paddle_tpu.inference.serving.cache import PagedKVCache    # noqa: E402
from paddle_tpu.models import decoder as dec                   # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk                # noqa: E402

CFG = {
    "hidden_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "intermediate_size": 96, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "vocab_size": 300, "max_position_embeddings": 512}
KINDS = ("window",) * 4 + ("full",)


def program(cfg, weights, dtype="float32"):
    net = dec.DecoderLM(dec.DecoderConfig.from_hf(cfg), dtype, abstract=True)
    net.load_arrays({n: weights[n.replace("layers.", "l", 1)]
                     for n, _ in net.named_parameters()})
    net.eval()
    return net


weights, net = family_fixtures(ref, CFG, program)


def _moe_inputs(weights, n=37, layer=2):
    lw = ref.layer_leaves(weights, layer)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 64), jnp.float32)
    return ref.dims(CFG), lw, x


class TestConformance(DecoderConformance):
    ref, CFG, build = ref, CFG, staticmethod(program)
    # a bucket of 16 rows, then 3 slots: 2 experts a token, 4 layers
    ASSIGNMENTS = (16 + 3) * 2 * 4
    KV_BYTES = {"full": 1 * 3 * 2 * 64 * (16 + 16) * 4,
                "window": 4 * 3 * 2 * 8 * (16 + 16) * 4}
    LIVE_ROWS = {"full": 3 + 40 + 64, "window": 3 + 8 + 8}
    # 48 slots x 16 384 positions, 4 key-value heads of 128 in bfloat16,
    # four window layers of 2 048 and one full layer: 2.42 GB
    CELL = ((5, 48, 4, 16384, 128),
            dict(kv_dtype="bfloat16", layer_kinds=KINDS, window=2048),
            [(1, 48, 4, 16384, 128)] * 2 + [(4, 48, 4, 2048, 128)] * 2,
            (1 * 16384 + 4 * 2048) * 48 * 4 * 128 * 2 * 2)
    # the shares of layer 2's 8 experts, each with the shared expert
    SHARES = [(8, held, 2, 64, 32) for held in (1, 2, 4)]
    SHARE_TOL = (1e-6, 1e-6)

    def check_small_caches(self):
        # every layer at full depth would take 8.05 GB
        uniform = 5 * 48 * 4 * 16384 * 128 * 2 * 2
        assert round(uniform / 1e9, 2) == 8.05
        small = PagedKVCache(5, 2, 2, 32, 8, kv_dtype="float32",
                             layer_kinds=KINDS, window=8)
        assert small.nbytes == sum(int(a.nbytes) for a in small.state())
        assert small.nbytes_by_kind() == {"full": 2 * 2 * 2 * 32 * 8 * 4,
                                          "window": 2 * 4 * 2 * 2 * 8 * 8 * 4}
        assert [small.layer_index(i) for i in range(5)] == [
            ("window", 0), ("window", 1), ("window", 2), ("window", 3),
            ("full", 0)]
        assert len(small.state()) == 5
        small.set_state(small.state())
        with pytest.raises(ValueError, match="int8"):
            PagedKVCache(2, 2, 2, 32, 8, kv_dtype="int8",
                         layer_kinds=("window", "full"), window=8)
        with pytest.raises(ValueError, match="layer_kinds"):
            PagedKVCache(2, 2, 2, 32, 8, layer_kinds=("window",), window=8)
        assert len(PagedKVCache(2, 2, 2, 32, 8).state()) == 3

    def expert_layer(self, weights, E, k, d, f):
        m, lw, x = _moe_inputs(weights)
        shared = np.asarray(dec.swiglu(x, lw["s_gate"], lw["s_up"],
                                       lw["s_down"]))
        return m, lw, x, (8, 2, 32, 32, True, 2.826), shared


def test_abstract_model_allocates_nothing_and_adopts_arrays_as_they_are(
        weights):
    net = dec.DecoderLM(dec.DecoderConfig.from_hf(CFG), "float32",
                        abstract=True)
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in net.parameters())
    net.load_arrays({n: weights[n.replace("layers.", "l", 1)]
                     for n, _ in net.named_parameters()})
    assert net.embed._data is weights["embed"]
    assert net.layers[2].e_gate._data is weights["l2.e_gate"]
    with pytest.raises(ValueError, match="parameter head"):
        net.load_arrays(dict(
            {n: weights[n.replace("layers.", "l", 1)]
             for n, _ in net.named_parameters()},
            head=weights["head"][:10]))


@pytest.mark.parametrize("kernels", [False, True])
def test_live_tokens_with_empty_slots_are_the_parent_rules(net, kernels):
    """tests/test_serving.py's hand-driven four slots on the decoder
    family: empty slots between live ones, an arrival mid-flight, a slot
    released and refilled; the rings (8 rows) wrap, `lens mod W` of an
    empty slot is row 0, and the live requests' tokens are the ones they
    get where every slot advances."""
    from test_serving import live_tokens_are_the_parent_rules
    with kernel_flags(kernels):
        live_tokens_are_the_parent_rules(
            lambda cls: cls(net, max_batch=4, max_seq_len=64,
                            prefill_buckets=(8, 16), kv_dtype="float32"),
            CFG["vocab_size"])


# -- (c) routing ------------------------------------------------------------


def test_every_token_to_one_expert_is_served_without_a_drop(weights):
    m, lw, x = _moe_inputs(weights)
    lw = dict(lw, expert_bias=jnp.zeros((8,)).at[jnp.asarray([3, 5])].set(9.))
    chosen, w = moe_ops.sigmoid_topk_route(
        x, lw["router"], lw["expert_bias"], 2, True, 2.826)
    assert set(np.asarray(chosen).ravel()) == {3, 5}
    out, sizes = moe_ops.grouped_experts(x, chosen, w, lw["e_gate"],
                                         lw["e_up"], lw["e_down"])
    assert list(np.asarray(sizes)) == [0, 0, 0, 37, 0, 37, 0, 0]
    want = ref.moe(m, lw, x, fault="no_shared")
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-6


def test_the_bias_chooses_and_the_score_alone_weighs(weights):
    m, lw, x = _moe_inputs(weights, n=600)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, lw["router"], precision=jax.lax.Precision.HIGHEST)))
    bias = np.asarray(lw["expert_bias"])
    chosen, w = moe_ops.sigmoid_topk_route(
        x, lw["router"], lw["expert_bias"], 2, True, 2.826)
    chosen, w = np.asarray(chosen), np.asarray(w)
    by_both = np.argsort(-(s + bias), axis=1)[:, :2]
    by_score = np.argsort(-s, axis=1)[:, :2]
    assert (np.sort(chosen, 1) == np.sort(by_both, 1)).all()
    # the drawn bias is wide enough to change some token's choice
    assert (np.sort(by_both, 1) != np.sort(by_score, 1)).any()
    picked = np.take_along_axis(s, chosen, 1)
    want = picked / picked.sum(1, keepdims=True) * 2.826
    assert np.abs(w - want).max() < 1e-6


@pytest.mark.parametrize("norm,scale", [(True, 2.826), (True, 1.0),
                                        (False, 1.0), (False, 0.5)])
def test_route_norm_and_route_scale(weights, norm, scale):
    m, lw, x = _moe_inputs(weights)
    m = dict(m, route_norm=norm, route_scale=scale)
    want_c, want_w = ref.route(m, lw, x)
    chosen, w = moe_ops.sigmoid_topk_route(
        x, lw["router"], lw["expert_bias"], 2, norm, scale)
    assert (np.asarray(chosen) == np.asarray(want_c)).all()
    assert np.abs(np.asarray(w) - np.asarray(want_w)).max() < 1e-6
    if norm:
        assert np.allclose(np.asarray(w).sum(1), scale, atol=1e-5)


# -- (e) the kernels, in interpret mode, against the einsum ------------------


@pytest.mark.parametrize("window", [0, 8, 11, 20])
def test_band_kernel_skips_blocks_and_masks_the_window(interpret, window):
    B, Hq, Hkv, T, D = 1, 4, 2, 32, 16
    q, k, v = randn(B, Hq, T, D), randn(B, Hkv, T, D), randn(B, Hkv, T, D)
    got = pk.band_flash_attention_or_none(q, k, v, window)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = (j <= i) & ((i - j < window) if window else True)
    want = pk._gqa_oracle(q.reshape(B, Hkv, 2, T, D), k, v, ok)
    assert np.abs(np.asarray(got) - np.asarray(want).reshape(
        B, Hq, T, D)).max() < 1e-5
    # the grid visits the band's blocks alone: 8-row blocks, so a window
    # of 8 spans 2-3 key blocks where the causal square has 4
    n_k = lambda w: min(T // 8, (8 + w - 2) // 8 + 2) if w else T // 8  # noqa
    assert n_k(8) == 3 and n_k(0) == 4
    assert dec.band_attention(q, k, v, window).shape == q.shape


@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("lens", [[0, 17, 200], [63, 64, 31], [15, 16, 47]])
def test_paged_gqa_kernel_on_rows_and_on_a_ring(interpret, ring, lens):
    L, B, H, G, D, R = 2, 3, 2, 2, 16, 64
    q, nk, nv = randn(B, H, G, D), randn(B, H, 1, D), randn(B, H, 1, D + 0)
    kc, vc = randn(L, B, H, R, D), randn(L, B, H, R, D) * 2.0
    lens = jnp.asarray(lens, jnp.int32)
    if ring:
        row, live = lens % R, jnp.minimum(lens + 1, R)
    else:
        row, live = jnp.minimum(lens, R - 1), jnp.minimum(lens + 1, R)
    out, ko, vo = pk.paged_gqa_decode_or_none(q, kc, vc, row, live, nk, nv,
                                              layer=1)
    slots = jnp.arange(B)
    kb = kc.at[1, slots, :, row].set(nk[:, :, 0])
    vb = vc.at[1, slots, :, row].set(nv[:, :, 0])
    ok = (jnp.arange(R)[None, :] < live[:, None])[:, None, None, None]
    want = pk._gqa_oracle(q[:, :, :, None], kb[1], vb[1], ok)[:, :, :, 0]
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5
    # one row a (slot, head) of the named layer changed, nothing else
    assert np.array_equal(np.asarray(ko), np.asarray(kb))
    assert np.array_equal(np.asarray(vo), np.asarray(vb))


def test_kernels_stay_off_without_their_flags_or_shapes():
    q = randn(1, 4, 32, 16)
    set_flags({"FLAGS_use_flash_attention": False})
    try:
        assert pk.band_flash_attention_or_none(
            q, q[:, :2], q[:, :2], 8) is None
    finally:
        set_flags({"FLAGS_use_flash_attention": True})
    # off the TPU the emulator takes whole 8-row blocks up to 64 rows
    for T in (30, 128):
        q = randn(1, 4, T, 16)
        assert pk.band_flash_attention_or_none(
            q, q[:, :2], q[:, :2], 8) is None
    c = randn(1, 1, 2, 64, 16)
    z = jnp.zeros((1,), jnp.int32)
    assert pk.paged_gqa_decode_or_none(
        randn(1, 2, 2, 16), c, c, z, z + 1, randn(1, 2, 1, 16),
        randn(1, 2, 1, 16), layer=0) is None

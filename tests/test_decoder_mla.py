"""Latent attention in the decoder block (`decoder.py`: one normed latent a
token from which every head's keys and values are up-projected, a rotary part
the heads share, rotary over adjacent pairs, the expanded path for a whole
sequence and the absorbed path for one token a slot), the latent cache
(`serving/cache.py`: one row a token a layer, no per-head key or value) and
the decode kernel over it, against the plain float32 reference of the
`mla_moe` family (`benchmarks/perf/reference_mla_moe.py`), which always
expands, on seeded weights: the checks every family shares
(`decoder_conformance.py`) and this family's own.

Size: d 64, 4 heads of 16 + 8 (value 16) over a 32-wide latent and an 8-wide
rotary part, a 16-wide router top-4 of which the experts 4-7 are held, two
shared experts, layers [dense | experts x2], float32 on the CPU.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmarks", "perf"),
           os.path.join(ROOT, "tests", "perf")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import family_mla_moe as fam                                    # noqa: E402
import reference_mla_moe as ref                                 # noqa: E402
from decoder_conformance import (                               # noqa: E402
    TOL, DecoderConformance, family_fixtures, interpret, kernel_flags, randn)
from tiny_mla import TINY_CONFIG, wide_scores                   # noqa: E402
from paddle_tpu.framework.flags import set_flags                # noqa: E402
from paddle_tpu.inference.serving.cache import (                # noqa: E402
    LayerCacheView, PagedKVCache, StackedKV)
from paddle_tpu.models import decoder as dec                    # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk                 # noqa: E402

CFG = dict(TINY_CONFIG, vocab_size=300)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def program(cfg, weights):
    net = fam.build_model(cfg, False, "float32")
    fam.load_weights(net, weights)
    return net


weights, net = family_fixtures(ref, CFG, program)


# -- (a) the configuration's way into the block -----------------------------


def test_the_published_keys_become_the_block(net):
    c = net.cfg
    assert c.layer_kinds == ("latent",) * 3
    assert c.mlp_kinds == ("dense", "moe", "moe")
    assert c.geometry("latent") == (4, 4, 24, 16)
    assert (c.latent_rank, c.latent_rope_dim) == (32, 8)
    assert c.rotary_dim("latent") == 8 and c.theta("latent") == 8e5
    assert all(c.rope_layers)
    assert not (c.qk_norm or c.attn_gate or c.sandwich_norm or c.sink_kinds)
    assert c.moe.num_experts == 16 and c.moe.held == (4, 4)
    assert c.moe.shared_width == 64 and c.moe.route_scale == 2.446
    blk = net.layers[1]
    assert blk.wq._data.shape == (64, 4 * 24)
    assert blk.wkv_a._data.shape == (64, 32 + 8)
    assert blk.kv_norm._data.shape == (32,)
    assert blk.wkv_b._data.shape == (32, 4 * (16 + 16))
    assert blk.wo._data.shape == (4 * 16, 64)
    assert not hasattr(blk, "wk") and not hasattr(blk, "wv")
    assert blk.e_gate._data.shape == (4, 64, 32)
    assert blk.s_gate._data.shape == (64, 64)
    assert net.layers[0].gate._data.shape == (64, 96)
    sv = net.serving()
    assert sv.kv_geometry == {"latent": (32, 8)}
    # 4 of the router's 16 experts held: the held-share grouped product
    assert sv.selfchecks == ("paged_latent", "band_flash_latent",
                             "held_grouped")
    assert (sv.moe_layers, sv.moe_top_k, sv.moe_experts) == (2, 4, 16)
    assert sv.prefix_cache is False


def test_the_catalogs_keys_alone_build_the_published_model():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = [json.loads(l) for l in open(CATALOG)
           if '"Kimi-VL-A3B-Instruct"' in l][0]
    c = dec.DecoderConfig.from_hf(row["config"])
    assert c.layer_kinds == ("latent",) * 27
    assert c.mlp_kinds == ("dense",) + ("moe",) * 26
    assert (c.hidden_size, c.num_heads, c.num_kv_heads) == (2048, 16, 16)
    assert c.geometry("latent") == (16, 16, 192, 128)
    assert (c.latent_rank, c.latent_rope_dim) == (512, 64)
    assert c.rope_theta == 800000.0 and all(c.rope_layers)
    assert c.dense_width == 11264 and c.vocab_size == 163840
    assert c.max_positions == 131072 and c.rms_eps == 1e-5
    m = c.moe
    assert (m.num_experts, m.top_k, m.width, m.shared_width) == (
        64, 6, 1408, 2816)
    assert m.route_norm and m.route_scale == 2.446 and m.held == (0, 64)
    leaves = dec.block_leaves(c, 1, "bfloat16")
    assert leaves["wq"][0] == (2048, 3072)
    assert leaves["wkv_a"][0] == (2048, 576)
    assert leaves["wkv_b"][0] == (512, 4096)
    assert leaves["wo"][0] == (2048, 2048)
    assert leaves["e_gate"][0] == (64, 2048, 1408)


BASE = {"hidden_size": 64, "num_hidden_layers": 6, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
        "n_routed_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "vocab_size": 300,
        "max_position_embeddings": 512}


@pytest.mark.parametrize("freq,first,want", [
    (1, 1, "dmmmmm"), (1, 3, "dddmmm"), (2, 1, "ddmdmd"), (3, 0, "mddmdd"),
    ([0, 1, 1, 0, 1, 1], 4, "dmmdmm")])
def test_an_integer_moe_layer_freq_is_read_with_first_k_dense_replace(
        freq, first, want):
    """An integer frequency f with k leading dense layers puts experts on
    the layers i >= k with i mod f = 0; a list is still one entry a layer
    (and then `first_k_dense_replace` says nothing)."""
    c = dec.DecoderConfig.from_hf(dict(
        BASE, moe_layer_freq=freq, first_k_dense_replace=first))
    assert "".join(k[0] for k in c.mlp_kinds) == want


def test_a_config_with_neither_list_of_kinds_is_all_full_layers():
    c = dec.DecoderConfig.from_hf(dict(BASE, moe_layer_freq=1,
                                       first_k_dense_replace=1))
    assert c.layer_kinds == ("full",) * 6 and all(c.rope_layers)
    # what the modelling code behind `layer_types` does is not assumed
    assert not (c.qk_norm or c.attn_gate or c.sandwich_norm)
    assert c.latent_rank == 0 and c.geometry("full") == (4, 2, 16, 16)


def test_a_missing_head_dim_is_an_error_that_names_the_key():
    cfg = {k: v for k, v in BASE.items() if k != "head_dim"}
    with pytest.raises(KeyError, match="head_dim"):
        dec.DecoderConfig.from_hf(cfg)
    # a latent config states the two qk sizes and needs none
    assert "head_dim" not in CFG
    assert dec.DecoderConfig.from_hf(CFG).head_dim == 24


@pytest.mark.parametrize("key,value,error", [
    ("q_lora_rank", 1536, NotImplementedError),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, NotImplementedError),
    ("scoring_func", "softmax", NotImplementedError),
    ("n_group", 8, NotImplementedError)])
def test_what_the_latent_block_does_not_compute_is_refused(key, value, error):
    with pytest.raises(error):
        dec.DecoderConfig.from_hf(dict(CFG, **{key: value}))


def test_latent_layers_stand_beside_no_other_kind():
    c = dec.DecoderConfig.from_hf(CFG)
    import dataclasses
    with pytest.raises(ValueError, match="beside"):
        dataclasses.replace(c, layer_kinds=("latent", "full", "latent"))
    with pytest.raises(ValueError, match="latent_rank"):
        dataclasses.replace(c, latent_rank=0)
    with pytest.raises(ValueError, match="latent_rank"):
        dataclasses.replace(c, latent_rope_dim=24)


# -- (b) the whole forward pass ---------------------------------------------


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS
                                   if f not in (None, "one_token")])
def test_each_planted_fault_moves_the_reference_logits(monkeypatch, fault):
    """Each part of the mathematics shows in the logits: left out or done
    otherwise, the reference itself leaves the sound one by far more than
    the program does (at scores as wide as the published sizes give)."""
    wide_scores(monkeypatch, ref)
    weights = ref.make_weights(CFG, 5, "float32")
    ids = ref.tokens(4, 1, 40, CFG["vocab_size"])[0]
    want = np.asarray(ref.logits(CFG, weights, ids))
    got = np.asarray(ref.logits(CFG, weights, ids, fault=fault))
    assert np.abs(got - want).max() > 100 * TOL


@pytest.mark.parametrize("quant,least", [("int8", 100), ("bf16", 10)])
def test_a_lower_precision_fails_the_tolerance(weights, quant, least):
    ids = ref.tokens(4, 1, 40, CFG["vocab_size"])[0]
    want = np.asarray(ref.logits(CFG, weights, ids))
    got = np.asarray(ref.logits(CFG, weights, ids, quant=quant))
    assert np.abs(got - want).max() > least * TOL


def test_rotary_turns_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 8), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    got = np.asarray(dec.rotary_pairs(x, pos, 8e5))
    # by hand: the pair (x[2i], x[2i+1]) as a complex number turned by
    # pos * theta^(-2i/d)
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    ang = np.arange(5)[:, None] * 8e5 ** (-np.arange(0, 8, 2) / 8.0)
    w = z * np.exp(1j * ang)
    want = np.stack([w.real, w.imag], -1).reshape(x.shape)
    assert np.abs(got - want).max() < 1e-5
    for b in range(2):       # the reference's own formulation
        r = ref._rotary(jnp.swapaxes(x[b], 0, 1), 8e5)         # [T, H, d]
        assert np.abs(np.swapaxes(got[b], 0, 1) - np.asarray(r)).max() < 1e-5
    # position 0 is not turned; rotate-half over the same dims is another
    # function
    assert np.array_equal(got[:, :, 0], np.asarray(x[:, :, 0]))
    halves = np.asarray(dec.rotary(x, pos, 8e5))
    assert np.abs(halves[:, :, 1:] - got[:, :, 1:]).max() > 0.1


# -- (c) absorbed = expanded, one layer -------------------------------------


@pytest.mark.parametrize("with_kernels", [False, True])
@pytest.mark.parametrize("layer", [0, 2])
def test_the_absorbed_path_gives_the_expanded_paths_attention(
        net, with_kernels, layer):
    """One layer's attention branch over a sequence of 37 tokens: expanded
    in one pass (keys and values of every head made from the latents), and
    absorbed, a token at a time over the cache's rows, the first 20 of them
    inserted as a prompt's rows. The same function of the weights."""
    cfg, p = net.cfg, net.layers[layer].arrays()
    T, n = 37, 20
    h = jax.random.normal(jax.random.PRNGKey(layer), (1, T, 64), jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    with kernel_flags(with_kernels):
        want, rows, none = dec._attention(cfg, layer, p, h, pos)
        assert none is None and rows.shape == (1, T, 32 + 8)
        kv = PagedKVCache(3, 2, None, 64, None, layer_kinds=cfg.layer_kinds,
                          kv_geometry={"latent": (32, 8)})
        carrier = kv.carrier(kv.state())
        carrier.insert([rows[:, :n]] * 3, None, jnp.int32(n), jnp.int32(1))

        @jax.jit            # one token's step: the cache state in and out
        def absorbed(state, hh, pos):
            carrier = kv.carrier(state)
            got, _, _ = dec._attention(cfg, layer, p, hh, pos,
                                       kv.views(carrier)[layer])
            return got, carrier.state()

        state = carrier.state()
        for t in range(n, T):
            hh = jnp.stack([h[0, t] * 0.0, h[0, t]])[:, None]   # slot 0 empty
            got, state = absorbed(state, hh,
                                  jnp.asarray([[0], [t]], jnp.int32))
            assert np.abs(np.asarray(got[1, 0]) - np.asarray(
                want[0, t])).max() < 1e-5, t
            state = kv.carrier(state).state(jnp.asarray([0, t + 1],
                                                        jnp.int32))
        carrier = kv.carrier(state)
        # the cache holds the rows the expanded pass returned, nothing else
        assert np.allclose(carrier.c[layer, 1, :T], rows[0, :, :32],
                           atol=1e-6)
        assert np.allclose(carrier.kr[layer, 1, :, :T], rows[0, :, 32:].T,
                           atol=1e-6)


def _moe_inputs(n=37, d=64, E=16, f=32, k=4, fs=64, seed=3):
    """An UNCUT expert layer of E experts and a shared expert: its leaves,
    tokens and dims."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    lw = {"router": 0.2 * jax.random.normal(ks[0], (d, E)),
          "expert_bias": 0.1 * jax.random.normal(ks[1], (E,)),
          "e_gate": 0.1 * jax.random.normal(ks[2], (E, d, f)),
          "e_up": 0.1 * jax.random.normal(ks[3], (E, d, f)),
          "e_down": 0.1 * jax.random.normal(ks[4], (E, f, d)),
          "s_gate": 0.1 * jax.random.normal(ks[6], (d, fs)),
          "s_up": 0.1 * jax.random.normal(ks[7], (d, fs)),
          "s_down": 0.1 * jax.random.normal(ks[8], (fs, d))}
    x = jax.random.normal(ks[5], (n, d), jnp.float32)
    m = dict(ref.dims(CFG), E=E, held=(0, E), k=k, f=f, d=d, fs=fs)
    return m, lw, x


class TestConformance(DecoderConformance):
    """The prompt's rows come from the expanded prefill, every decoded
    position's from the absorbed path through the latent cache."""
    ref, CFG, build = ref, CFG, staticmethod(program)
    STEP_STATS = (3,)                   # a share: what fell on it too
    SERVED_FAULTS = ("one_token",)
    # a bucket of 16 rows, then 3 slots: 4 experts a token, 2 layers, 4 of
    # the 16 experts held
    ASSIGNMENTS = (16 + 3) * 4 * 2
    HELD = True
    # 40 numbers a token a layer and nothing else of the token
    KV_BYTES = {"latent": 3 * 3 * 64 * (32 + 8) * 4}
    LIVE_ROWS = {"latent": 3 + 40 + 64, "full": 0}
    # 48 slots x 16 384 positions x 9 layers in bfloat16: 512 + 64 numbers
    # a token a layer, the latents by row and the rotary parts by column,
    # and nothing else
    CELL = ((9, 48, None, 16384, None),
            dict(kv_dtype="bfloat16", layer_kinds=("latent",) * 9,
                 kv_geometry={"latent": (512, 64)}),
            [(9, 48, 16384, 512), (9, 48, 64, 16384), (48,)], 8153726976)
    SHARES = [(64, 8, 6, 32, 16),       # eight shares of a 64-wide router
              (16, 2, 4, 64, 32), (16, 4, 4, 64, 32)]
    SHARE_TOL = (1e-5, 2e-5)

    def check_rows(self, ks, vs, b):
        assert vs == [None] * 3
        assert all(k.shape == (1, b, 40) for k in ks)

    def check_prefill_rows(self, kv, ks, vs, n, b):
        for layer in range(3):
            assert np.allclose(kv.c[layer, 1, :n], ks[layer][0, :n, :32],
                               atol=2e-5)
            assert np.allclose(kv.kr[layer, 1, :, :n],
                               ks[layer][0, :n, 32:].T, atol=2e-5)
        assert not np.asarray(kv.c[:, 0]).any()    # other slots untouched
        assert not np.asarray(kv.kr[:, 2]).any()

    def check_paths(self, paths, with_kernels):
        # three layers a trace: two prefill buckets expanded, one decode
        # absorbed
        assert paths["latent_expanded"] == 6
        if with_kernels:     # no layer of the model took the einsum
            assert paths["xla_latent"] == 0 and paths["latent_absorbed"] == 3
            assert paths["band_flash"] == 6
        else:
            assert paths["xla_latent"] == 3 and paths["latent_absorbed"] == 0
        assert paths["xla_paged"] == 0 and paths.get("paged_gqa", 0) == 0

    def check_small_caches(self):
        # per-head keys and values of the same heads (16 x 320) would take
        # 72.5 GB
        assert 8153726976 == 9 * 48 * 16384 * 576 * 2
        assert 9 * 48 * 16384 * 16 * 320 * 2 == 72477573120
        small = PagedKVCache(2, 2, None, 32, None,
                             layer_kinds=("latent",) * 2,
                             kv_geometry={"latent": (12, 4)})
        assert small.geometry == {"latent": (12, 4)}
        assert small._fields == ("c", "kr", "lens")
        assert small.c.shape == (2, 2, 32, 12)
        assert small.kr.shape == (2, 2, 4, 32)
        assert small.k is None and small.wk is None and small.k_cols == ()
        assert small.layer_index(1) == ("latent", 1)
        assert [v.kind for v in small.views(small.carrier(
            small.state()))] == ["latent"] * 2
        assert small.nbytes_by_kind() == {"latent": 2 * 2 * 32 * 16 * 4}
        with pytest.raises(ValueError, match="no prompt head"):
            small.head(0, 4)
        with pytest.raises(ValueError, match="3 arrays"):
            small.set_state((small.c, small.kr))
        with pytest.raises(ValueError, match="dtype"):
            small.set_state(small.c.astype(jnp.bfloat16), small.kr,
                            small.lens)
        with pytest.raises(ValueError, match="latent layers alone"):
            PagedKVCache(2, 2, 2, 32, 8, layer_kinds=("latent", "full"),
                         kv_geometry={"latent": (12, 4)})
        with pytest.raises(ValueError, match="int8"):
            PagedKVCache(1, 2, None, 32, None, kv_dtype="int8",
                         layer_kinds=("latent",),
                         kv_geometry={"latent": (12, 4)})
        with pytest.raises(ValueError, match="no geometry"):
            PagedKVCache(1, 2, None, 32, None, layer_kinds=("latent",))
        # a cache of K and V is what it was, stated by sizes or by geometry
        flat = PagedKVCache(2, 2, 2, 32, 8)
        assert flat._fields == ("k", "v", "lens") and flat.c is None
        same = PagedKVCache(2, 2, None, 32, None,
                            kv_geometry={"full": (2, 8, 8)})
        assert same.k.shape == flat.k.shape == (2, 2, 2, 32, 8)
        assert same.geometry == flat.geometry

    def expert_layer(self, weights, E, k, d, f):
        """An UNCUT layer with a shared expert, which every share computes
        too and a deployment's combine counts ONCE."""
        m, lw, x = _moe_inputs(E=E, k=k, d=d, f=f)
        shared = np.asarray(ref._swiglu(x, lw["s_gate"], lw["s_up"],
                                        lw["s_down"], False))
        assert np.abs(shared).max() > 1e-2
        return m, lw, x, (E, k, f, 64, True, 2.446), shared

    def check_routed_part(self, m, lw, x, routed, whole):
        want = np.asarray(ref.moe(m, lw, x, shared=False))
        assert np.abs(routed - want).max() < 2e-5
        assert np.abs(want).max() > .01


# -- (f) the kernel, in interpret mode, against the einsum -------------------


# one compile a block size, where the kernel's grid steps would otherwise
# dispatch op by op
_latent = jax.jit(pk._paged_latent_decode, static_argnames=(
    "layer", "block_k", "scale", "interpret"))


@pytest.mark.parametrize("block", [16, 32, 64])
@pytest.mark.parametrize("lens", [[0, 17, 200], [63, 64, 31], [15, 16, 47],
                                  [0, 0, 0], [1, 32, 48]])
def test_latent_decode_kernel_against_the_einsum(interpret, lens, block):
    """Ragged lengths over two layers: an empty slot (no grid step, output
    0, rows untouched), append rows that open a block, close one and sit at
    the wall; every row the call did not append comes back unchanged."""
    L, B, H, r, dr, T = 2, 3, 4, 32, 8, 64
    q, new = randn(B, H, r + dr), randn(B, r + dr)
    c, kr = randn(L, B, T, r) * 2.0, randn(L, B, dr, T)
    lens = jnp.asarray(lens, jnp.int32)
    scale = 24.0 ** -0.5
    out, co, kro = _latent(
        q[..., :r], q[..., r:], c, kr, lens, new[:, :r], new[:, r:], layer=1,
        block_k=block, scale=scale, interpret=True)
    live = np.asarray(lens) > 0
    row = jnp.minimum(lens, T - 1)
    slots = jnp.arange(B)
    cb = np.array(c.at[1, slots, row].set(new[:, :r]))
    krb = np.array(kr.at[1, slots, :, row].set(new[:, r:]))
    ok = jnp.arange(T)[None, :] <= row[:, None]
    want = np.array(pk._latent_oracle(q, cb[1], krb[1], ok, scale))
    want[~live] = 0.0
    cb[1][~live] = np.asarray(c)[1][~live]
    krb[1][~live] = np.asarray(kr)[1][~live]
    assert np.abs(np.asarray(out) - want).max() < 1e-5
    assert np.array_equal(np.asarray(co), cb)
    assert np.array_equal(np.asarray(kro), krb)
    # the view's einsum computes the same for the slots that hold a request
    view = LayerCacheView(StackedKV(c=c, kr=kr, lens=lens), 1, "latent")
    set_flags({"FLAGS_paged_flash_interpret": False})
    plain = view.attend(q[:, None], new[:, None, None], None, scale=scale)
    assert np.allclose(np.asarray(plain[:, 0])[live], want[live], atol=1e-5)
    assert np.array_equal(np.asarray(view.kv.c)[:, live], cb[:, live])


def test_the_scale_is_the_callers_and_a_wrong_one_shows(interpret):
    B, H, r, dr, T = 2, 4, 32, 8, 32
    q, new = randn(B, 1, H, r + dr), randn(B, 1, 1, r + dr)
    c, kr = randn(1, B, T, r), randn(1, B, dr, T)
    lens = jnp.asarray([20, 31], jnp.int32)

    def attend(scale):
        view = LayerCacheView(StackedKV(c=c, kr=kr, lens=lens), 0, "latent")
        return np.asarray(view.attend(q, new, None, scale=scale))

    head, row = attend(24.0 ** -0.5), attend(40.0 ** -0.5)
    assert head.shape == (B, 1, H, r)
    assert np.abs(head - row).max() > 1e-2
    set_flags({"FLAGS_paged_flash_interpret": False})
    assert np.abs(attend(24.0 ** -0.5) - head).max() < 1e-5
    # the other kinds' kernels scale by the key size: no other is taken
    full = LayerCacheView(StackedKV(
        randn(1, B, 1, T, 8), randn(1, B, 1, T, 8), lens), 0)
    qf, kf = randn(B, 1, 2, 8), randn(B, 1, 1, 8)
    with pytest.raises(ValueError, match="scale"):
        full.attend(qf, kf, kf, scale=0.5)
    assert full.attend(qf, kf, kf).shape == (B, 1, 2, 8)


def test_the_gate_takes_the_latent_cache_or_leaves_it_to_the_einsum(interpret):
    q, new = randn(2, 4, 40), randn(2, 40)
    c, kr = randn(1, 2, 64, 32), randn(1, 2, 8, 64)
    lens = jnp.asarray([3, 40], jnp.int32)
    kw = dict(layer=0, scale=0.2)
    assert pk.paged_latent_decode_or_none(q, c, kr, lens, new, **kw) \
        is not None
    # rows that are no whole number of write-back groups: the einsum
    assert pk.paged_latent_decode_or_none(
        q, c[:, :, :24], kr[..., :24], lens, new, **kw) is None
    set_flags({"FLAGS_paged_flash_interpret": False})
    assert pk.paged_latent_decode_or_none(q, c, kr, lens, new, **kw) is None
    # a block of cache rows is the grouped-query kernels': 1 MiB of latents
    assert pk._gqa_block(16384, False) == 1024
    assert pk._gqa_block(100, False) is None
    # one query head a key head takes 512 query rows a step of the band
    # kernel; the grouped-query blocks are what they were
    assert pk._band_blocks(15360, False, 0, 1) == (512, 512)
    assert pk._band_blocks(3072, False, 128, 8) == (128, 128)
    assert pk._band_blocks(3072, False, 0, 16) == (64, 512)

"""Latent attention in the decoder block (`decoder.py`: one normed latent a
token from which every head's keys and values are up-projected, a rotary part
the heads share, rotary over adjacent pairs, the expanded path for a whole
sequence and the absorbed path for one token a slot), the latent cache
(`serving/cache.py`: one row a token a layer, no per-head key or value) and
the decode kernel over it, against the plain float32 reference of the
`mla_moe` family (`benchmarks/perf/reference_mla_moe.py`), which always
expands, on seeded weights.

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
from tiny_mla import TINY_CONFIG, wide_scores                   # noqa: E402
from paddle_tpu.framework.flags import set_flags                # noqa: E402
from paddle_tpu.inference.serving import cache as cache_mod     # noqa: E402
from paddle_tpu.inference.serving import engine as engine_mod   # noqa: E402
from paddle_tpu.inference.serving.cache import (                # noqa: E402
    LayerCacheView, PagedKVCache, StackedKV)
from paddle_tpu.inference.serving.engine import GenerationEngine  # noqa: E402
from paddle_tpu.models import decoder as dec                    # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk                 # noqa: E402

CFG = dict(TINY_CONFIG, vocab_size=300)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# float32 on both sides and the same function of the weights: the program
# and the reference differ in the order of summation, and the absorbed path
# from the expanded one in where the up-projections are multiplied in.
# Logits have a standard deviation of 0.16: 2e-5 is a hundred times the 2e-7
# read on the sound program, a thousandth of what bfloat16 gives and far
# under int8 (0.02) or a dropped `kv_norm` (0.01: the tests below).
TOL = 2e-5


def program(cfg, weights):
    net = fam.build_model(cfg, False, "float32")
    fam.load_weights(net, weights)
    return net


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(CFG, 5, "float32")


@pytest.fixture(scope="module")
def net(weights):
    return program(CFG, weights)


@pytest.fixture
def kernels():
    set_flags({"FLAGS_paged_flash_interpret": True})
    yield
    set_flags({"FLAGS_paged_flash_interpret": False})


def _flags(with_kernels):
    set_flags({"FLAGS_paged_flash_interpret": with_kernels,
               "FLAGS_use_flash_attention": with_kernels})


def _flags_back():
    set_flags({"FLAGS_paged_flash_interpret": False,
               "FLAGS_use_flash_attention": True})


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


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_logits_match_the_reference(net, weights, seed):
    ids = ref.tokens(seed, 2, 40, CFG["vocab_size"])
    got = np.asarray(net.run(jnp.asarray(ids, jnp.int32))[0])
    for row in range(2):
        want = np.asarray(ref.logits(CFG, weights, ids[row]))
        assert np.abs(got[row] - want).max() < TOL


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
    _flags(with_kernels)
    try:
        want, rows, none = dec._attention(cfg, layer, p, h, pos)
        assert none is None and rows.shape == (1, T, 32 + 8)
        kv = PagedKVCache(3, 2, None, 64, None, layer_kinds=cfg.layer_kinds,
                          kv_geometry={"latent": (32, 8)})
        carrier = kv.carrier(kv.state())
        carrier.insert([rows[:, :n]] * 3, None, jnp.int32(n), jnp.int32(1))
        for t in range(n, T):
            view = kv.views(carrier)[layer]
            hh = jnp.stack([h[0, t] * 0.0, h[0, t]])[:, None]   # slot 0 empty
            got, _, _ = dec._attention(
                cfg, layer, p, hh, jnp.asarray([[0], [t]], jnp.int32), view)
            assert np.abs(np.asarray(got[1, 0]) - np.asarray(
                want[0, t])).max() < 1e-5, t
            carrier.lens = jnp.asarray([0, t + 1], jnp.int32)
        # the cache holds the rows the expanded pass returned, nothing else
        assert np.allclose(carrier.c[layer, 1, :T], rows[0, :, :32],
                           atol=1e-6)
        assert np.allclose(carrier.kr[layer, 1, :, :T], rows[0, :, 32:].T,
                           atol=1e-6)
    finally:
        _flags_back()


# -- (d) prefill, then decoding through the cache ---------------------------


def _engine(net, **kw):
    kw = dict(dict(max_batch=3, max_seq_len=64, prefill_buckets=(8, 16, 32),
                   kv_dtype="float32"), **kw)
    return GenerationEngine(net, **kw)


@pytest.mark.parametrize("with_kernels", [False, True])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(
        net, weights, with_kernels):
    """Three slots of different lengths in one batch, teacher-forced along
    fixed sequences: the logits of the prompt (expanded prefill) and of
    every decoded position (absorbed, through the latent cache) are the
    reference's full, always-expanded forward pass over the same sequence —
    through the einsum, and through the latent decode kernel and the band
    kernel in interpret mode."""
    _flags(with_kernels)
    try:
        e = _engine(net)
        seqs = ref.tokens(11, 3, 40, CFG["vocab_size"])
        n_prompt = [27, 6, 17]
        want = [np.asarray(ref.logits(CFG, weights, s)) for s in seqs]
        cache = e.kv.state()
        for slot, n in enumerate(n_prompt):
            b = e.bucket_for(n)
            ids = np.zeros((1, b), np.int32)
            ids[0, :n] = seqs[slot, :n]
            logits, ks, vs, _ = net.run(jnp.asarray(ids))
            assert vs == [None] * 3 and ks[0].shape == (1, b, 40)
            assert np.abs(np.asarray(logits)[0, :n]
                          - want[slot][:n]).max() < TOL
            kv = e.kv.carrier(cache)
            kv.insert(ks, vs, jnp.int32(n), jnp.int32(slot))
            cache = kv.state()
        for step in range(40 - max(n_prompt)):
            last = jnp.asarray([[seqs[s, n + step]] for s, n in
                                enumerate(n_prompt)], jnp.int32)
            kv = e.kv.carrier(cache)
            logits, stats = net.step(last, e.kv.views(kv))
            assert stats.shape == (3,)      # a share: what fell on it too
            for s, n in enumerate(n_prompt):
                assert np.abs(np.asarray(logits)[s, 0]
                              - want[s][n + step]).max() < TOL, (s, step)
            cache = kv.state(kv.lens + 1)
    finally:
        _flags_back()


@pytest.mark.parametrize("with_kernels", [False, True])
def test_the_server_path_decodes_the_reference_greedy_tokens(
        net, weights, with_kernels):
    """Through `GenerationEngine.prefill` / `.decode` (jitted, donated):
    greedy tokens equal the reference's argmax along the served sequence;
    ONE decode executable, one prefill executable a bucket."""
    _flags(with_kernels)
    paths0 = dict(pk.attention_path_counts())
    try:
        e = _engine(net, max_seq_len=64, prefill_buckets=(16, 32))
        prompts = ref.tokens(7, 3, 30, CFG["vocab_size"])
        n_prompt = [30, 5, 19]
        seqs = [list(prompts[s, :n]) + [int(e.prefill(s, prompts[s, :n]))]
                for s, n in enumerate(n_prompt)]
        for _ in range(14):
            toks = e.decode()
            for s in range(3):
                seqs[s].append(int(toks[s]))
    finally:
        _flags_back()
    assert e.decode_compiles == 1 and e.prefill_compiles == 2
    paths = {k: v - paths0.get(k, 0)
             for k, v in pk.attention_path_counts().items()}
    # three layers a trace: two prefill buckets expanded, one decode absorbed
    assert paths["latent_expanded"] == 6
    if with_kernels:     # no layer of the model took the einsum
        assert paths["xla_latent"] == 0 and paths["latent_absorbed"] == 3
        assert paths["band_flash"] == 6
    else:
        assert paths["xla_latent"] == 3 and paths["latent_absorbed"] == 0
    assert paths["xla_paged"] == 0 and paths.get("paged_gqa", 0) == 0
    gaps = ref.served_gaps(CFG, weights, [np.asarray(s) for s in seqs],
                           n_prompt)
    assert max(gaps) == 0.0
    assert max(ref.served_gaps(
        CFG, weights, [np.asarray(s) for s in seqs], n_prompt,
        fault="one_token")) > 0.0


@pytest.mark.parametrize("with_kernels", [False, True])
def test_the_run_ahead_loop_gives_the_depth_0_tokens_through_the_latents(
        net, with_kernels):
    from test_serving import run_ahead_matches_depth0
    _flags(with_kernels)
    try:
        b, refills = run_ahead_matches_depth0(_engine(net),
                                              CFG["vocab_size"])
    finally:
        _flags_back()
    assert refills >= 3 and b.steps > 0


def test_counters_of_a_served_latent_model(net):
    e = _engine(net)
    n0, h0 = engine_mod.MOE_ASSIGNMENTS.value, engine_mod.MOE_HERE.value
    c0 = engine_mod.MOE_HERE_PCT.count
    int(e.prefill(0, np.arange(1, 12)))    # observed where it is read
    e.decode()
    # a bucket of 16 rows, then 3 slots: 4 experts a token, 2 layers
    routed = (16 + 3) * 4 * 2
    assert engine_mod.MOE_ASSIGNMENTS.value - n0 == routed
    assert 0 < engine_mod.MOE_HERE.value - h0 < routed  # 4 of 16 are held
    assert engine_mod.MOE_HERE_PCT.count - c0 == 2
    # 40 numbers a token a layer and nothing else of the token
    by_kind = e.kv.nbytes_by_kind()
    assert by_kind == {"latent": 3 * 3 * 64 * (32 + 8) * 4}
    assert cache_mod.KV_BYTES.labels("latent").value == by_kind["latent"]
    assert e.kv.nbytes == by_kind["latent"] + 3 * 4
    l0 = cache_mod.KV_ROWS_LIVE.labels("latent").sum
    f0 = cache_mod.KV_ROWS_LIVE.labels("full").sum
    e.kv.observe_live_rows([3, 40, 100])
    assert cache_mod.KV_ROWS_LIVE.labels("latent").sum - l0 == 3 + 40 + 64
    assert cache_mod.KV_ROWS_LIVE.labels("full").sum == f0


# -- (e) the share of a deployment ------------------------------------------


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


@pytest.mark.parametrize("E,held,k,d,f", [
    (64, 8, 6, 32, 16),         # eight shares of a 64-wide router, top-6
    (16, 2, 4, 64, 32), (16, 4, 4, 64, 32)])
def test_the_shares_with_the_shared_expert_once_add_up_to_the_whole_layer(
        E, held, k, d, f):
    """What the holders of `held` experts each compute of one layer — every
    one of them the shared expert too, which a deployment's combine counts
    ONCE — adds up to the uncut reference's whole layer: the routed parts
    of all the shares, and the shared expert's output one time."""
    m, lw, x = _moe_inputs(E=E, k=k, d=d, f=f)
    whole = np.asarray(ref.moe(m, lw, x))
    shared = np.asarray(ref._swiglu(x, lw["s_gate"], lw["s_up"],
                                    lw["s_down"], False))
    assert np.abs(shared).max() > 1e-2
    total, assigned = np.zeros_like(whole), 0
    for first in range(0, E, held):
        mc = dec.MoEConfig(E, k, f, 64, True, 2.446,
                           experts_held=(first, held))
        part = dict(lw, **{n: lw[n][first:first + held]
                           for n in ("e_gate", "e_up", "e_down")})
        got, sizes = dec.moe_layer(mc, part, x)       # routed part + shared
        assert sizes.shape == (held,)
        assigned += int(np.asarray(sizes).sum())
        want = ref.moe(dict(m, route_scale=2.446), lw, x,
                       experts_held=(first, held))
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
        total += np.asarray(got) - shared             # its routed part
    assert assigned == x.shape[0] * k            # no assignment dropped
    whole = np.asarray(ref.moe(dict(m, route_scale=2.446), lw, x))
    assert np.abs(total + shared - whole).max() < 2e-5
    routed = np.asarray(ref.moe(dict(m, route_scale=2.446), lw, x,
                                shared=False))
    assert np.abs(total - routed).max() < 2e-5 and np.abs(routed).max() > .01


# -- (f) the kernel, in interpret mode, against the einsum -------------------


def _randn(*shape):
    return jnp.asarray(np.random.RandomState(sum(shape)).randn(*shape),
                       jnp.float32)


@pytest.mark.parametrize("block", [16, 32, 64])
@pytest.mark.parametrize("lens", [[0, 17, 200], [63, 64, 31], [15, 16, 47],
                                  [0, 0, 0], [1, 32, 48]])
def test_latent_decode_kernel_against_the_einsum(kernels, lens, block):
    """Ragged lengths over two layers: an empty slot (no grid step, output
    0, rows untouched), append rows that open a block, close one and sit at
    the wall; every row the call did not append comes back unchanged."""
    L, B, H, r, dr, T = 2, 3, 4, 32, 8, 64
    q, new = _randn(B, H, r + dr), _randn(B, r + dr)
    c, kr = _randn(L, B, T, r) * 2.0, _randn(L, B, dr, T)
    lens = jnp.asarray(lens, jnp.int32)
    scale = 24.0 ** -0.5
    out, co, kro = pk._paged_latent_decode(
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


def test_the_scale_is_the_callers_and_a_wrong_one_shows(kernels):
    B, H, r, dr, T = 2, 4, 32, 8, 32
    q, new = _randn(B, 1, H, r + dr), _randn(B, 1, 1, r + dr)
    c, kr = _randn(1, B, T, r), _randn(1, B, dr, T)
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
        _randn(1, B, 1, T, 8), _randn(1, B, 1, T, 8), lens), 0)
    qf, kf = _randn(B, 1, 2, 8), _randn(B, 1, 1, 8)
    with pytest.raises(ValueError, match="scale"):
        full.attend(qf, kf, kf, scale=0.5)
    assert full.attend(qf, kf, kf).shape == (B, 1, 2, 8)


def test_the_gate_takes_the_latent_cache_or_leaves_it_to_the_einsum(kernels):
    q, new = _randn(2, 4, 40), _randn(2, 40)
    c, kr = _randn(1, 2, 64, 32), _randn(1, 2, 8, 64)
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


# -- (g) the latent cache ----------------------------------------------------


def test_cache_bytes_of_the_cell():
    """48 slots x 16 384 positions x 9 layers in bfloat16: 512 + 64 numbers
    a token a layer, the latents by row and the rotary parts by column, and
    nothing else: 8 153 726 976 bytes where per-head keys and values of the
    same heads (16 x 320) would be 72.5 GB."""
    shape = jax.eval_shape(lambda: PagedKVCache(
        9, 48, None, 16384, None, kv_dtype="bfloat16",
        layer_kinds=("latent",) * 9,
        kv_geometry={"latent": (512, 64)}).state())
    assert [a.shape for a in shape] == [
        (9, 48, 16384, 512), (9, 48, 64, 16384), (48,)]
    nbytes = [int(np.prod(a.shape)) * a.dtype.itemsize for a in shape[:2]]
    assert sum(nbytes) == 8153726976 == 9 * 48 * 16384 * 576 * 2
    assert 9 * 48 * 16384 * 16 * 320 * 2 == 72477573120
    small = PagedKVCache(2, 2, None, 32, None, layer_kinds=("latent",) * 2,
                         kv_geometry={"latent": (12, 4)})
    assert small.geometry == {"latent": (12, 4)}
    assert small._fields == ("c", "kr", "lens")
    assert small.c.shape == (2, 2, 32, 12) and small.kr.shape == (2, 2, 4, 32)
    assert small.k is None and small.wk is None and small.k_cols == ()
    assert small.layer_index(1) == ("latent", 1)
    assert [v.kind for v in small.views(small.carrier(small.state()))] == [
        "latent"] * 2
    assert small.nbytes_by_kind() == {"latent": 2 * 2 * 32 * 16 * 4}
    with pytest.raises(ValueError, match="no prompt head"):
        small.head(0, 4)
    with pytest.raises(ValueError, match="3 arrays"):
        small.set_state((small.c, small.kr))
    with pytest.raises(ValueError, match="dtype"):
        small.set_state(small.c.astype(jnp.bfloat16), small.kr, small.lens)
    with pytest.raises(ValueError, match="latent layers alone"):
        PagedKVCache(2, 2, 2, 32, 8, layer_kinds=("latent", "full"),
                     kv_geometry={"latent": (12, 4)})
    with pytest.raises(ValueError, match="int8"):
        PagedKVCache(1, 2, None, 32, None, kv_dtype="int8",
                     layer_kinds=("latent",), kv_geometry={"latent": (12, 4)})
    with pytest.raises(ValueError, match="no geometry"):
        PagedKVCache(1, 2, None, 32, None, layer_kinds=("latent",))
    # a cache of K and V is what it was, stated by sizes or by geometry
    flat = PagedKVCache(2, 2, 2, 32, 8)
    assert flat._fields == ("k", "v", "lens") and flat.c is None
    same = PagedKVCache(2, 2, None, 32, None,
                        kv_geometry={"full": (2, 8, 8)})
    assert same.k.shape == flat.k.shape == (2, 2, 2, 32, 8)
    assert same.geometry == flat.geometry


@pytest.mark.parametrize("n", [5, 8, 19, 27, 32])
def test_a_prefill_leaves_its_latents_where_the_stack_keeps_them(net, n):
    e = _engine(net)
    ids = ref.tokens(n, 1, n, CFG["vocab_size"])[0]
    e.prefill(1, ids)
    b = e.bucket_for(n)
    padded = np.zeros((1, b), np.int32)
    padded[0, :n] = ids
    _, ks, vs, _ = net.run(jnp.asarray(padded))
    assert int(e.kv.lens[1]) == n and list(np.asarray(e.kv.lens)) == [0, n, 0]
    for layer in range(3):
        assert ks[layer].shape == (1, b, 40) and vs[layer] is None
        assert np.allclose(e.kv.c[layer, 1, :n], ks[layer][0, :n, :32],
                           atol=2e-5)
        assert np.allclose(e.kv.kr[layer, 1, :, :n], ks[layer][0, :n, 32:].T,
                           atol=2e-5)
    assert not np.asarray(e.kv.c[:, 0]).any()        # other slots untouched
    assert not np.asarray(e.kv.kr[:, 2]).any()

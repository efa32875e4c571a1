"""The parts of the decoder block that MiMo-V2-style models add (`decoder.py`:
key-value heads and key / value sizes by layer kind, rotary on the first
dims of a head with a base a kind, a scale on the values, a sink in the
window layers' softmax, a held share of the experts), the two-kind cache
with unequal stacks and K kept by column, and the kernels over it, against
the plain float32 reference of the `mimo_v2` family
(`benchmarks/perf/reference_mimo_v2.py`) on seeded weights.

Size: d 64, 8 query heads over 2 (full) / 4 (window) key-value heads, key 24
and value 16 wide, rotary on 8, window 8, 16-wide router top-4 of which the
experts 4-7 are held, layers [dense-full | window x4, full, window], float32
on the CPU.
"""
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

import family_mimo_v2 as fam                                    # noqa: E402
import reference_mimo_v2 as ref                                 # noqa: E402
from tiny_mimo import TINY_CONFIG, wide_scores                  # noqa: E402
from paddle_tpu.framework.flags import set_flags                # noqa: E402
from paddle_tpu.inference.serving import cache as cache_mod     # noqa: E402
from paddle_tpu.inference.serving import engine as engine_mod   # noqa: E402
from paddle_tpu.inference.serving.cache import PagedKVCache     # noqa: E402
from paddle_tpu.inference.serving.engine import GenerationEngine  # noqa: E402
from paddle_tpu.models import decoder as dec                    # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk                 # noqa: E402

CFG = dict(TINY_CONFIG, vocab_size=300)
# float32 on both sides and the same mathematics; they differ in the order
# of summation alone. Logits have a standard deviation of 0.16: 2e-5 is a
# hundred times the 2e-7 read on the sound program and a thousandth of what
# bfloat16 would give.
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


# -- (a) the configuration's way into the block -----------------------------


def test_the_published_keys_become_the_block(net):
    c = net.cfg
    assert c.layer_kinds == ("full", "window", "window", "window", "window",
                             "full", "window")
    assert c.mlp_kinds == ("dense",) + ("moe",) * 6
    assert c.geometry("full") == (8, 2, 24, 16)
    assert c.geometry("window") == (8, 4, 24, 16)
    assert c.rotary_dim("full") == c.rotary_dim("window") == 8
    assert (c.theta("full"), c.theta("window")) == (1e7, 1e4)
    assert all(c.rope_layers) and c.value_scale == 0.707
    assert c.sink_kinds == ("window",)
    assert not (c.qk_norm or c.attn_gate or c.sandwich_norm)
    assert c.moe.num_experts == 16 and c.moe.held == (4, 4)
    assert c.moe.shared_width == 0 and c.moe.route_scale == 1.0
    assert net.layers[1].sink._data.shape == (8,)
    assert not hasattr(net.layers[0], "sink")
    assert net.layers[1].e_gate._data.shape == (4, 64, 32)
    assert net.layers[1].router._data.shape == (64, 16)
    assert net.layers[0].wk._data.shape == (64, 2 * 24)
    assert net.layers[1].wv._data.shape == (64, 4 * 16)
    assert net.layers[1].wo._data.shape == (8 * 16, 64)


def test_the_other_set_of_names_still_gives_its_block():
    """A config that says `layer_types` keeps what its modelling code does
    where no key speaks, and each of those parts has a key."""
    base = {
        "hidden_size": 64, "num_hidden_layers": 2, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "full_attention"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "sliding_window": 8, "intermediate_size": 96, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "num_shared_experts": 1, "vocab_size": 300,
        "max_position_embeddings": 512}
    c = dec.DecoderConfig.from_hf(base)
    assert c.qk_norm and c.attn_gate and c.sandwich_norm
    assert c.rope_layers == (True, False) and not c.sink_kinds
    assert c.geometry("window") == c.geometry("full") == (4, 2, 16, 16)
    assert c.rotary_dim("window") == 16 and c.moe.shared_width == 32
    c = dec.DecoderConfig.from_hf(dict(
        base, qk_norm=False, attention_output_gate=False,
        sandwich_norm=False, rope_layer_kinds=["full", "window"]))
    assert not (c.qk_norm or c.attn_gate or c.sandwich_norm)
    assert c.rope_layers == (True, True)
    for key, value in (("rope_scaling", {"rope_type": "yarn"}),
                       ("score_func", "softmax"), ("n_group", 4)):
        with pytest.raises(NotImplementedError):
            dec.DecoderConfig.from_hf(dict(base, **{key: value}))


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
    assert np.abs(got - want).max() > 10 * TOL


def test_partial_rotary_rotates_the_first_dims_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 24), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    got = dec.rotary(x, pos, 1e4, 8)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))
    assert np.allclose(got[..., :8], dec.rotary(x[..., :8], pos, 1e4))
    assert np.abs(np.asarray(got[:, :, 1:, :8] - x[:, :, 1:, :8])).max() > .1
    want = ref._rotary(jnp.swapaxes(x[0], 0, 1), 1e4, 8)       # [T, H, d]
    assert np.allclose(jnp.swapaxes(got[0], 0, 1), want, atol=1e-6)


# -- (c) prefill, then decoding through the cache ---------------------------


def _engine(net, **kw):
    kw = dict(dict(max_batch=3, max_seq_len=64, prefill_buckets=(8, 16, 32),
                   kv_dtype="float32"), **kw)
    return GenerationEngine(net, **kw)


def _windowed(net, weights, with_kernels):
    """(cfg, net) for a test that may run the kernels: the emulator's decode
    kernel writes back 16-row groups, so with the kernels the window is 16
    rows (the same weights; contexts of 40 still wrap it), else 8."""
    if not with_kernels:
        return CFG, net
    cfg = dict(CFG, sliding_window=16)
    return cfg, program(cfg, weights)


@pytest.mark.parametrize("with_kernels", [False, True])
def test_prefill_then_decode_gives_the_reference_logits_at_every_position(
        net, weights, with_kernels):
    """Three slots of different lengths in one batch, teacher-forced along
    fixed sequences of 4-5 windows, so every ring wraps: the logits of the
    prompt (prefill) and of every decoded position are the reference's full
    forward pass over the same sequence — through the einsum, and through
    the by-column decode kernel and the band kernel in interpret mode."""
    cfg, net = _windowed(net, weights, with_kernels)
    set_flags({"FLAGS_paged_flash_interpret": with_kernels,
               "FLAGS_use_flash_attention": with_kernels})
    try:
        e = _engine(net)
        seqs = ref.tokens(11, 3, 40, CFG["vocab_size"])
        n_prompt = [27, 6, 17]
        want = [np.asarray(ref.logits(cfg, weights, s)) for s in seqs]
        cache = e.kv.state()
        for slot, n in enumerate(n_prompt):
            b = e.bucket_for(n)
            ids = np.zeros((1, b), np.int32)
            ids[0, :n] = seqs[slot, :n]
            logits, ks, vs, _ = net.run(jnp.asarray(ids))
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
        set_flags({"FLAGS_paged_flash_interpret": False,
                   "FLAGS_use_flash_attention": True})


@pytest.mark.parametrize("with_kernels", [False, True])
def test_the_server_path_decodes_the_reference_greedy_tokens(
        net, weights, with_kernels):
    """Through `GenerationEngine.prefill` / `.decode` (jitted, donated):
    greedy tokens equal the reference's argmax along the served sequence;
    ONE decode executable, one prefill executable a bucket."""
    cfg, net = _windowed(net, weights, with_kernels)
    set_flags({"FLAGS_paged_flash_interpret": with_kernels,
               "FLAGS_use_flash_attention": with_kernels})
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
        set_flags({"FLAGS_paged_flash_interpret": False,
                   "FLAGS_use_flash_attention": True})
    assert e.decode_compiles == 1 and e.prefill_compiles == 2
    paths = {k: v - paths0.get(k, 0)
             for k, v in pk.attention_path_counts().items()}
    if with_kernels:     # no layer of the model took the einsum
        assert paths["xla_paged"] == 0 and paths["paged_gqa"] == 7
    else:
        assert paths["xla_paged"] == 7
    gaps = ref.served_gaps(cfg, weights, [np.asarray(s) for s in seqs],
                           n_prompt)
    assert max(gaps) == 0.0
    for fault in ("no_sink", "one_token"):
        assert max(ref.served_gaps(
            cfg, weights, [np.asarray(s) for s in seqs], n_prompt,
            fault=fault)) > 0.0, fault


@pytest.mark.parametrize("with_kernels", [False, True])
def test_the_run_ahead_loop_gives_the_depth_0_tokens_through_the_rings(
        net, weights, with_kernels):
    from test_serving import run_ahead_matches_depth0
    net = _windowed(net, weights, with_kernels)[1]
    set_flags({"FLAGS_paged_flash_interpret": with_kernels,
               "FLAGS_use_flash_attention": with_kernels})
    try:
        b, refills = run_ahead_matches_depth0(_engine(net),
                                              CFG["vocab_size"])
    finally:
        set_flags({"FLAGS_paged_flash_interpret": False,
                   "FLAGS_use_flash_attention": True})
    assert refills >= 3 and b.steps > 0


def test_span_attributes_and_counters_of_a_served_share(net):
    e = _engine(net)
    assert e.span_attrs == {"moe_layers": 6, "window_layers": 5}
    n0, h0 = engine_mod.MOE_ASSIGNMENTS.value, engine_mod.MOE_HERE.value
    c0 = engine_mod.MOE_HERE_PCT.count
    int(e.prefill(0, np.arange(1, 12)))    # observed where it is read
    e.decode()
    # a bucket of 16 rows, then 3 slots: 4 experts a token, 6 layers
    routed = (16 + 3) * 4 * 6
    assert engine_mod.MOE_ASSIGNMENTS.value - n0 == routed
    here = engine_mod.MOE_HERE.value - h0
    assert 0 < here < routed                # 4 of 16 experts are held
    assert engine_mod.MOE_HERE_PCT.count - c0 == 2
    assert 0.0 < engine_mod.MOE_HERE_PCT.mean < 100.0
    # bytes and rows by kind, for stacks that agree on nothing but slots
    by_kind = e.kv.nbytes_by_kind()
    assert by_kind == {"full": 2 * 3 * 2 * 64 * (24 + 16) * 4,
                       "window": 5 * 3 * 4 * 8 * (24 + 16) * 4}
    assert cache_mod.KV_BYTES.labels("full").value == by_kind["full"]
    assert cache_mod.KV_BYTES.labels("window").value == by_kind["window"]
    f0 = cache_mod.KV_ROWS_LIVE.labels("full").sum
    w0 = cache_mod.KV_ROWS_LIVE.labels("window").sum
    e.kv.observe_live_rows([3, 40, 100])
    assert cache_mod.KV_ROWS_LIVE.labels("full").sum - f0 == 3 + 40 + 64
    assert cache_mod.KV_ROWS_LIVE.labels("window").sum - w0 == 3 + 8 + 8


# -- (d) the share of a deployment ------------------------------------------


def _moe_inputs(n=37, d=64, E=16, f=32, k=4, seed=3):
    """An UNCUT expert layer of E experts: its leaves, tokens and dims."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    lw = {"router": 0.2 * jax.random.normal(ks[0], (d, E)),
          "expert_bias": 0.1 * jax.random.normal(ks[1], (E,)),
          "e_gate": 0.1 * jax.random.normal(ks[2], (E, d, f)),
          "e_up": 0.1 * jax.random.normal(ks[3], (E, d, f)),
          "e_down": 0.1 * jax.random.normal(ks[4], (E, f, d))}
    x = jax.random.normal(ks[5], (n, d), jnp.float32)
    m = dict(ref.dims(CFG), E=E, held=(0, E), k=k, f=f, d=d)
    return m, lw, x


@pytest.mark.parametrize("E,held,k,d,f", [
    (256, 16, 8, 32, 16),       # sixteen shares of a 256-wide router
    (16, 2, 4, 64, 32), (16, 4, 4, 64, 32)])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(E, held, k, d,
                                                             f):
    """What holders of `held` experts each compute of one layer — no shared
    expert, so nothing is counted twice — adds up to the uncut reference's
    whole layer; the reference given the same share computes the same
    part; the held counts add up to every assignment."""
    m, lw, x = _moe_inputs(E=E, k=k, d=d, f=f)
    whole = np.asarray(ref.moe(m, lw, x))
    total, assigned = np.zeros_like(whole), 0
    for first in range(0, E, held):
        mc = dec.MoEConfig(E, k, f, 0, True, 1.0, experts_held=(first, held))
        part = dict(lw, **{n: lw[n][first:first + held]
                           for n in ("e_gate", "e_up", "e_down")})
        got, sizes = dec.moe_layer(mc, part, x)
        assert sizes.shape == (held,)
        assigned += int(np.asarray(sizes).sum())
        want = ref.moe(m, lw, x, experts_held=(first, held))
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6
        total += np.asarray(got)
    assert assigned == x.shape[0] * k            # no assignment dropped
    assert np.abs(total - whole).max() < 1e-5
    assert np.abs(whole).max() > 1e-2


def test_route_stats_count_what_fell_on_the_share():
    sizes = [jnp.asarray([0, 3, 2], jnp.int32), jnp.asarray([1, 0, 0])]
    assert list(np.asarray(dec.route_stats(sizes))) == [3, 4]
    assert list(np.asarray(dec.route_stats(sizes, share=True))) == [3, 4, 6]
    assert dec.route_stats([], share=True) is None


# -- (e) the kernels, in interpret mode, against the einsum ------------------


def _randn(*shape):
    return jnp.asarray(np.random.RandomState(sum(shape)).randn(*shape),
                       jnp.float32)


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("window", [0, 8, 11])
def test_band_kernel_at_a_key_size_that_is_not_the_value_size(kernels,
                                                              window, sink):
    B, Hq, Hkv, T, dk, dv = 1, 8, 2, 32, 24, 16
    q, k, v = _randn(B, Hq, T, dk), _randn(B, Hkv, T, dk), \
        _randn(B, Hkv, T, dv)
    b = _randn(Hq) if sink else None
    got = pk.band_flash_attention_or_none(q, k, v, window, b)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = (j <= i) & ((i - j < window) if window else True)
    want = pk._gqa_oracle(q.reshape(B, Hkv, 4, T, dk), k, v, ok, b)
    assert got.shape == (B, Hq, T, dv)
    assert np.abs(np.asarray(got) - np.asarray(want).reshape(
        B, Hq, T, dv)).max() < 1e-5
    set_flags({"FLAGS_use_flash_attention": False})
    try:                                    # the block's own einsum
        plain = dec.band_attention(q, k, v, window, b)
    finally:
        set_flags({"FLAGS_use_flash_attention": True})
    assert np.abs(np.asarray(plain) - np.asarray(got)).max() < 1e-5
    if sink:        # the sink takes weight: every row's output is smaller
        none = pk._gqa_oracle(q.reshape(B, Hkv, 4, T, dk), k, v, ok)
        assert np.abs(np.asarray(none) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("heads", [1, 2])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("lens", [[0, 17, 200], [63, 64, 31], [15, 16, 47]])
def test_by_column_decode_kernel_on_rows_and_on_a_ring(kernels, ring, lens,
                                                       heads, sink):
    L, B, H, G, dk, dv, R = 2, 3, 2, 4, 24, 16, 64
    q, nk, nv = _randn(B, H, G, dk), _randn(B, H, 1, dk), _randn(B, H, 1, dv)
    kc, vc = _randn(L, B, H, dk, R), _randn(L, B, H, R, dv) * 2.0
    b = _randn(H * G) if sink else None
    lens = jnp.asarray(lens, jnp.int32)
    row = lens % R if ring else jnp.minimum(lens, R - 1)
    live = jnp.minimum(lens + 1, R)
    out, ko, vo = pk._paged_kv_decode(
        q, kc, vc, row, live, nk, nv, layer=1, block_k=16, interpret=True,
        sink=b, heads=heads)
    slots = jnp.arange(B)
    kb = kc.at[1, slots, :, :, row].set(nk[:, :, 0])
    vb = vc.at[1, slots, :, row].set(nv[:, :, 0])
    ok = (jnp.arange(R)[None, :] < live[:, None])[:, None, None, None]
    want = pk._gqa_oracle(q[:, :, :, None], jnp.swapaxes(kb[1], 2, 3),
                          vb[1], ok, b)[:, :, :, 0]
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5
    # one row a (slot, head) of the named layer changed, nothing else
    assert np.array_equal(np.asarray(ko), np.asarray(kb))
    assert np.array_equal(np.asarray(vo), np.asarray(vb))


def test_the_gate_sends_a_sink_or_unequal_sizes_to_the_by_column_kernel(
        kernels):
    q, nk, nv = _randn(1, 2, 2, 24), _randn(1, 2, 1, 24), _randn(1, 2, 1, 16)
    z = jnp.zeros((1,), jnp.int32)
    kr, kcol, v = _randn(1, 1, 2, 64, 24), _randn(1, 1, 2, 24, 64), \
        _randn(1, 1, 2, 64, 16)
    # by row, such a layer has no kernel: the caller's einsum
    assert pk.paged_gqa_decode_or_none(q, kr, v, z, z + 1, nk, nv,
                                       layer=0) is None
    assert pk.paged_gqa_decode_or_none(q, kcol, v, z, z + 1, nk, nv, layer=0,
                                       k_cols=True, sink=_randn(4)) \
        is not None
    assert pk._gqa_heads(8, 128, 192, 128, 2) == 8       # a 128-row ring
    assert pk._gqa_heads(4, 1024, 192, 128, 2) == 4      # 1024 full rows
    assert pk._band_blocks(3072, False, 128, 8) == (128, 128)
    assert pk._band_blocks(3072, False, 0, 16) == (64, 512)
    assert pk._band_blocks(4096, False, 2048, 8) == (128, 512)
    assert pk._band_blocks(4096, False, 0, 8) == (128, 512)


# -- (f) the two-kind cache with unequal stacks ------------------------------


def test_cache_bytes_of_the_cell():
    """192 slots x 4096 positions in bfloat16: two full layers of 4 heads
    and five 128-row rings of 8, a key row 192 wide (kept by column) and a
    value row 128: 4.03 + 0.63 = 4.66 GB."""
    kinds = ("full", "window", "window", "window", "window", "full",
             "window")
    geo = {"full": (4, 192, 128), "window": (8, 192, 128)}
    shape = jax.eval_shape(lambda: PagedKVCache(
        7, 192, 4, 4096, 192, kv_dtype="bfloat16", layer_kinds=kinds,
        window=128, kv_geometry=geo).state())
    assert [a.shape for a in shape[:4]] == [
        (2, 192, 4, 192, 4096), (2, 192, 4, 4096, 128),
        (5, 192, 8, 192, 128), (5, 192, 8, 128, 128)]
    nbytes = [int(np.prod(a.shape)) * a.dtype.itemsize for a in shape[:4]]
    assert round(sum(nbytes[:2]) / 1e9, 2) == 4.03
    assert round(sum(nbytes[2:]) / 1e9, 2) == 0.63
    assert round(sum(nbytes) / 1e9, 2) == 4.66
    small = PagedKVCache(3, 2, 2, 32, 8, kv_dtype="float32",
                         layer_kinds=("full", "window", "window"), window=8,
                         kv_geometry={"window": (4, 12, 8)})
    assert small.geometry == {"full": (2, 8, 8), "window": (4, 12, 8)}
    assert small.k_cols == ("window",)
    assert small.k.shape == (1, 2, 2, 32, 8)
    assert small.wk.shape == (2, 2, 4, 12, 8)
    assert small.wv.shape == (2, 2, 4, 8, 8)
    assert small.nbytes_by_kind() == {"full": 2 * 2 * 2 * 32 * 8 * 4,
                                      "window": 2 * 2 * 4 * 8 * 20 * 4}
    with pytest.raises(ValueError, match="no prompt head"):
        small.head(0, 4)
    with pytest.raises(ValueError, match="int8"):
        PagedKVCache(1, 2, 2, 32, 8, kv_dtype="int8",
                     kv_geometry={"full": (2, 12, 8)})
    flat = PagedKVCache(2, 2, 2, 32, 8)
    assert [a.shape for a in flat.head(1, 4)] == [(2, 1, 2, 4, 8)] * 2


@pytest.mark.parametrize("n", [5, 8, 19, 27, 32])
def test_a_prefill_leaves_its_rows_where_each_stack_keeps_them(net, n):
    e = _engine(net)
    ids = ref.tokens(n, 1, n, CFG["vocab_size"])[0]
    e.prefill(1, ids)
    b = e.bucket_for(n)
    padded = np.zeros((1, b), np.int32)
    padded[0, :n] = ids
    _, ks, vs, _ = net.run(jnp.asarray(padded))
    assert int(e.kv.lens[1]) == n
    W = 8
    rings = [i for i, k in enumerate(net.cfg.layer_kinds) if k == "window"]
    for ring_layer, layer in enumerate(rings):
        assert ks[layer].shape == (1, 4, b, 24)
        for p in range(max(0, n - W), n):       # K by column, V by row
            assert np.allclose(e.kv.wk[ring_layer, 1, :, :, p % W],
                               ks[layer][0, :, p], atol=2e-5)
            assert np.allclose(e.kv.wv[ring_layer, 1, :, p % W],
                               vs[layer][0, :, p], atol=2e-5)
    assert ks[5].shape == (1, 2, b, 24) and vs[5].shape == (1, 2, b, 16)
    assert np.allclose(e.kv.k[1, 1, :, :, :n],
                       np.swapaxes(ks[5][0, :, :n], 1, 2), atol=2e-5)
    assert np.allclose(e.kv.v[1, 1, :, :n], vs[5][0, :, :n], atol=2e-5)
    assert not np.asarray(e.kv.wk[:, 0]).any()          # other slots untouched

"""The parts of the decoder block that MiMo-V2-style models add (`decoder.py`:
key-value heads and key / value sizes by layer kind, rotary on the first
dims of a head with a base a kind, a scale on the values, a sink in the
window layers' softmax, a held share of the experts), the two-kind cache
with unequal stacks and K kept by column, and the kernels over it, against
the plain float32 reference of the `mimo_v2` family
(`benchmarks/perf/reference_mimo_v2.py`) on seeded weights: the checks every
family shares (`decoder_conformance.py`) and this family's own.

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
from decoder_conformance import (                               # noqa: E402
    TOL, DecoderConformance, family_fixtures, interpret, randn)
from tiny_mimo import TINY_CONFIG, wide_scores                  # noqa: E402
from paddle_tpu.framework.flags import set_flags                # noqa: E402
from paddle_tpu.inference.serving.cache import PagedKVCache     # noqa: E402
from paddle_tpu.models import decoder as dec                    # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk                 # noqa: E402

CFG = dict(TINY_CONFIG, vocab_size=300)
KINDS = ("full", "window", "window", "window", "window", "full", "window")


def program(cfg, weights):
    net = fam.build_model(cfg, False, "float32")
    fam.load_weights(net, weights)
    return net


weights, net = family_fixtures(ref, CFG, program)


# -- (a) the configuration's way into the block -----------------------------


def test_the_published_keys_become_the_block(net):
    c = net.cfg
    assert c.layer_kinds == KINDS
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


class TestConformance(DecoderConformance):
    ref, CFG, build = ref, CFG, staticmethod(program)
    # the emulator's decode kernel writes back 16-row groups, so with the
    # kernels the window is 16 rows (the same weights; contexts of 40 still
    # wrap it), else 8
    KERNEL_CFG = {"sliding_window": 16}
    STEP_STATS = (3,)                   # a share: what fell on it too
    SERVED_FAULTS = ("no_sink", "one_token")
    # a bucket of 16 rows, then 3 slots: 4 experts a token, 6 layers, 4 of
    # the 16 experts held
    ASSIGNMENTS = (16 + 3) * 4 * 6
    HELD = True
    # stacks that agree on nothing but slots
    KV_BYTES = {"full": 2 * 3 * 2 * 64 * (24 + 16) * 4,
                "window": 5 * 3 * 4 * 8 * (24 + 16) * 4}
    LIVE_ROWS = {"full": 3 + 40 + 64, "window": 3 + 8 + 8}
    # 192 slots x 4096 positions in bfloat16: two full layers of 4 heads
    # and five 128-row rings of 8, a key row 192 wide (kept by column) and
    # a value row 128: 4.03 + 0.63 = 4.66 GB
    CELL = ((7, 192, 4, 4096, 192),
            dict(kv_dtype="bfloat16", layer_kinds=KINDS, window=128,
                 kv_geometry={"full": (4, 192, 128), "window": (8, 192, 128)}),
            [(2, 192, 4, 192, 4096), (2, 192, 4, 4096, 128),
             (5, 192, 8, 192, 128), (5, 192, 8, 128, 128)],
            (2 * 4096 * 4 + 5 * 128 * 8) * 192 * (192 + 128) * 2)
    SHARES = [(256, 16, 8, 32, 16),     # sixteen shares of a 256-wide router
              (16, 2, 4, 64, 32), (16, 4, 4, 64, 32)]
    SHARE_TOL = (1e-6, 1e-5)

    def check_rows(self, ks, vs, b):
        heads = {"full": 2, "window": 4}
        for k, v, kind in zip(ks, vs, KINDS):
            assert k.shape == (1, heads[kind], b, 24)
            assert v.shape == (1, heads[kind], b, 16)

    def check_paths(self, paths, with_kernels):
        if with_kernels:     # no layer of the model took the einsum
            assert paths["xla_paged"] == 0 and paths["paged_kv_work"] == 7
        else:
            assert paths["xla_paged"] == 7

    def check_small_caches(self):
        small = PagedKVCache(3, 2, 2, 32, 8, kv_dtype="float32",
                             layer_kinds=("full", "window", "window"),
                             window=8, kv_geometry={"window": (4, 12, 8)})
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

    def expert_layer(self, weights, E, k, d, f):
        """An UNCUT layer, no shared expert: nothing is counted twice."""
        m, lw, x = _moe_inputs(E=E, k=k, d=d, f=f)
        return m, lw, x, (E, k, f, 0, True, 1.0), 0.0

    def check_routed_part(self, m, lw, x, routed, whole):
        assert np.abs(whole).max() > 1e-2


def test_route_stats_count_what_fell_on_the_share():
    sizes = [jnp.asarray([0, 3, 2], jnp.int32), jnp.asarray([1, 0, 0])]
    assert list(np.asarray(dec.route_stats(sizes))) == [3, 4]
    assert list(np.asarray(dec.route_stats(sizes, share=True))) == [3, 4, 6]
    assert dec.route_stats([], share=True) is None


# -- (e) the kernels, in interpret mode, against the einsum ------------------


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("window", [0, 8, 11])
def test_band_kernel_at_a_key_size_that_is_not_the_value_size(
        interpret, window, sink):
    B, Hq, Hkv, T, dk, dv = 1, 8, 2, 32, 24, 16
    q, k, v = randn(B, Hq, T, dk), randn(B, Hkv, T, dk), \
        randn(B, Hkv, T, dv)
    b = randn(Hq) if sink else None
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


# one compile a (heads, sink), where the kernel's grid steps would otherwise
# dispatch op by op
_by_column = jax.jit(pk._paged_kv_decode, static_argnames=(
    "layer", "block_k", "interpret", "heads"))


@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("heads", [1, 2])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("lens", [
    [0, 17, 200], [63, 64, 31], [15, 16, 47],
    [2, 62, 5],          # one slot far longer than the others
    [31, 1, 0],          # live rows ending on a block; one row; an empty slot
])
def test_by_column_decode_kernel_on_rows_and_on_a_ring(interpret, ring, lens,
                                                        heads, sink):
    L, B, H, G, dk, dv, R = 2, 3, 2, 4, 24, 16, 64
    q, nk, nv = randn(B, H, G, dk), randn(B, H, 1, dk), randn(B, H, 1, dv)
    kc, vc = randn(L, B, H, dk, R), randn(L, B, H, R, dv) * 2.0
    b = randn(H * G) if sink else None
    lens = jnp.asarray(lens, jnp.int32)
    row = lens % R if ring else jnp.minimum(lens, R - 1)
    live = jnp.minimum(lens + 1, R)
    out, ko, vo = _by_column(
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


@pytest.mark.parametrize("live,R,block_k", [
    ([1, 64, 17], 64, 16),      # one row; a full slot; a block and a row
    ([3, 64, 1, 2], 64, 16),    # one slot far longer than the others
    ([16, 32, 33], 64, 16),     # live rows ending on a block, and one past
    ([128, 5, 128], 128, 128),  # a ring of one block: one entry a slot
])
def test_the_by_column_work_list_holds_each_slots_live_blocks(live, R,
                                                              block_k):
    # no entry for a block past a slot's live rows: the static grid's dead
    # steps cannot come back unnoticed
    slot, blk, n = pk._live_work(jnp.asarray(live, jnp.int32), R, block_k)
    assert slot.shape == blk.shape == (len(live) * R // block_k,)
    assert int(n) == sum((nl - 1) // block_k + 1 for nl in live)
    want = [(b, j) for b, nl in enumerate(live)
            for j in range((nl - 1) // block_k + 1)]
    assert list(zip(np.asarray(slot)[:int(n)].tolist(),
                    np.asarray(blk)[:int(n)].tolist())) == want


def test_the_by_column_grid_is_the_work_list():
    # longgen's full layer as the benchmark serves it: the lowered call's
    # grid is (head groups, the work list's length), not (slots, head
    # groups, blocks)
    B, H, G, dk, dv, R = 192, 4, 16, 192, 128, 4096
    blk = pk._gqa_cols_block(R, H, dk, dv, 2, interpret=False)
    bf = jnp.bfloat16
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    closed = jax.make_jaxpr(lambda *a: pk._paged_kv_decode(
        *a, layer=1, block_k=blk, interpret=False))(
        jax.ShapeDtypeStruct((B, H, G, dk), bf),
        jax.ShapeDtypeStruct((2, B, H, dk, R), bf),
        jax.ShapeDtypeStruct((2, B, H, R, dv), bf), i32, i32,
        jax.ShapeDtypeStruct((B, H, 1, dk), bf),
        jax.ShapeDtypeStruct((B, H, 1, dv), bf))
    (call,) = [e for e in closed.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    assert gm.num_dynamic_grid_bounds == 1 and len(gm.grid) == 2
    assert gm.grid[0] == H // pk._gqa_heads(H, blk, dk, dv, 2)


def test_the_gate_sends_a_sink_or_unequal_sizes_to_the_by_column_kernel(
        interpret):
    q, nk, nv = randn(1, 2, 2, 24), randn(1, 2, 1, 24), randn(1, 2, 1, 16)
    z = jnp.zeros((1,), jnp.int32)
    kr, kcol, v = randn(1, 1, 2, 64, 24), randn(1, 1, 2, 24, 64), \
        randn(1, 1, 2, 64, 16)
    # by row, such a layer has no kernel: the caller's einsum
    assert pk.paged_gqa_decode_or_none(q, kr, v, z, z + 1, nk, nv,
                                       layer=0) is None
    assert pk.paged_gqa_decode_or_none(q, kcol, v, z, z + 1, nk, nv, layer=0,
                                       k_cols=True, sink=randn(4)) \
        is not None
    assert pk._gqa_heads(8, 128, 192, 128, 2) == 8       # a 128-row ring
    assert pk._gqa_heads(4, 512, 192, 128, 2) == 4       # 512 full rows
    # the block: the fewest rows whose step holds 1 MiB, a ring whole
    assert pk._gqa_cols_block(4096, 4, 192, 128, 2, False) == 512
    assert pk._gqa_cols_block(128, 8, 192, 128, 2, False) == 128
    assert pk._gqa_cols_block(100, 8, 192, 128, 2, False) is None
    assert pk._band_blocks(3072, False, 128, 8) == (128, 128)
    assert pk._band_blocks(3072, False, 0, 16) == (64, 512)
    assert pk._band_blocks(4096, False, 2048, 8) == (128, 512)
    assert pk._band_blocks(4096, False, 0, 8) == (128, 512)

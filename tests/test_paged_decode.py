"""Megakernel tier (ISSUE 15): fused paged-decode attention and the
decoder-block tail fusion, checked in interpret mode against einsum /
composed-XLA oracles.

Three layers of evidence:

  * kernel-level — `_paged_decode` on one layer of a STACKED cache vs a
    numpy oracle that replays the exact serving semantics (append the
    new token at position lens[b], dequantize the int8 window, attend
    over pos <= lens[b]), across dtype (f32 / bf16 / int8-cache),
    ragged lens including idle slots, NaN garbage in the unwritten
    tail, and the full-slot clamp; every row the call did not append —
    other layers, the dead tail — comes back bit-identical (the cache
    is aliased to the output and updated in place);
  * dispatch/engine-level — the gate chain (shape, emulator flag,
    interpret caps), probe-failure capture (journal event + counter + fallback),
    the compile-once contract, prefix-hit suffix admission through the
    fused path, token parity against the einsum engine, and
    the decode step's jaxpr: no restack of the cache on either path,
    every kernel call aliased;
  * block-fusion level — the (y, z) pair primitive and the
    FLAGS_fused_block decoder-layer wiring vs the unfused model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.ops import pallas_kernels as pk

jax.config.update("jax_platforms", "cpu")

VOCAB = 64


def _quantize_np(x):
    """quantize_kv's rule in numpy: symmetric absmax int8 per row."""
    amax = np.abs(x).astype(np.float32).max(-1)
    scale = np.maximum(amax, 1e-8) / np.float32(127.0)
    q = np.clip(np.round(x.astype(np.float32) / scale[..., None]),
                -127.0, 127.0).astype(np.int8)
    return q, scale.astype(np.float32)


def _oracle(q, kc, vc, lens, nk, nv, ks=None, vs=None):
    """Numpy replay of the megakernel contract. Returns
    (out, kc', vc', ks', vs') with the new token appended at lens[b] — of
    the slots that hold a request: lens[b] == 0 is an EMPTY slot, whose
    output is 0 and whose rows stay as they are."""
    q = np.asarray(q, np.float32)
    B, H, _, D = q.shape
    kc, vc = np.array(kc), np.array(vc)
    quant = ks is not None
    if quant:
        ks, vs = np.array(ks), np.array(vs)
        nkq, nks = _quantize_np(np.asarray(nk))
        nvq, nvs = _quantize_np(np.asarray(nv))
    out = np.zeros((B, H, 1, D), np.float32)
    for b in range(B):
        ln = int(lens[b])
        if ln == 0:
            continue
        if quant:
            kc[b, :, ln] = nkq[b, :, 0]
            vc[b, :, ln] = nvq[b, :, 0]
            ks[b, :, ln] = nks[b, :, 0]
            vs[b, :, ln] = nvs[b, :, 0]
            kw = kc[b, :, :ln + 1].astype(np.float32) \
                * ks[b, :, :ln + 1, None]
            vw = vc[b, :, :ln + 1].astype(np.float32) \
                * vs[b, :, :ln + 1, None]
        else:
            kc[b, :, ln] = np.asarray(nk)[b, :, 0].astype(kc.dtype)
            vc[b, :, ln] = np.asarray(nv)[b, :, 0].astype(vc.dtype)
            kw = kc[b, :, :ln + 1].astype(np.float32)
            vw = vc[b, :, :ln + 1].astype(np.float32)
        s = np.einsum("hd,hkd->hk", q[b, :, 0] * D ** -0.5, kw)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out[b, :, 0] = np.einsum("hk,hkd->hd", p, vw)
    return out, kc, vc, (ks if quant else None), (vs if quant else None)


LAYER = 1      # the layer the kernel tests call; its neighbours must not move


def _mk(B=2, H=2, T=96, D=16, lens=(5, 40), dtype=jnp.float32,
        quantized=False, nan_tail=True, seed=0, L=2):
    """Inputs over a stacked cache [L, B, H, T, D] with the tail PAST lens
    left as NaN garbage in every layer — the hostile shape a cache can
    hold (rows past a slot's length are whatever was there before)."""
    rs = np.random.RandomState(seed)
    lens = np.asarray(lens, np.int32)
    q = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    nk = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    nv = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    kf = rs.randn(L, B, H, T, D)
    vf = rs.randn(L, B, H, T, D)
    if quantized:
        kc, ks = _quantize_np(kf)
        vc, vs = _quantize_np(vf)
        if nan_tail:     # scales past lens are garbage; payload is int8
            for b in range(B):
                ks[:, b, :, lens[b]:] = np.nan
                vs[:, b, :, lens[b]:] = np.nan
        return (q, jnp.asarray(kc), jnp.asarray(vc),
                jnp.asarray(lens), nk, nv,
                jnp.asarray(ks), jnp.asarray(vs))
    if nan_tail:
        for b in range(B):
            kf[:, b, :, lens[b]:] = np.nan
            vf[:, b, :, lens[b]:] = np.nan
    return (q, jnp.asarray(kf, dtype), jnp.asarray(vf, dtype),
            jnp.asarray(lens), nk, nv, None, None)


def _layer_args(args, layer=LAYER):
    """The oracle's view: the named layer cut out of the stacked args."""
    q, kc, vc, lens, nk, nv, ks, vs = args
    cut = lambda a: None if a is None else np.asarray(a)[layer]  # noqa: E731
    return (q, cut(kc), cut(vc), lens, nk, nv, cut(ks), cut(vs))


def _run(args, T=96, layer=LAYER):
    q, kc = args[0], args[1]
    assert kc.shape[3] == T
    blk = pk._paged_block(T, q.shape[1], q.shape[3], kc.dtype,
                          interpret=True)
    return pk._paged_decode(*args, layer=layer, block_k=blk,
                            interpret=True)


def _assert_rest_untouched(got, before, lens, layer=LAYER):
    """Bit for bit (NaN payloads included): all of `got` equals `before`
    but the appended rows (layer, b, :, lens[b]) — other layers, earlier
    rows and the dead tail past each slot's length alike."""
    got, before = np.array(got), np.array(before)
    assert got.dtype == before.dtype and got.shape == before.shape
    for b in range(lens.shape[0]):
        got[layer, b, :, lens[b]] = 0
        before[layer, b, :, lens[b]] = 0
    assert got.tobytes() == before.tobytes()


def _check(args, atol, T=96, layer=LAYER):
    out = _run(args, T=T, layer=layer)
    ref = _oracle(*_layer_args(args, layer))
    lens = np.asarray(args[3])
    np.testing.assert_allclose(np.asarray(out[0], np.float32), ref[0],
                               atol=atol, rtol=atol)
    for got, want, name in ((out[1], ref[1], "k"), (out[2], ref[2], "v")):
        got, want = np.asarray(got)[layer], np.asarray(want)
        for b in range(lens.shape[0]):     # live region incl. the append
            np.testing.assert_allclose(
                got[b, :, :lens[b] + 1].astype(np.float32),
                want[b, :, :lens[b] + 1].astype(np.float32),
                atol=atol, rtol=atol, err_msg=name)
    if args[6] is not None:
        for got, want in ((out[3], ref[3]), (out[4], ref[4])):
            got, want = np.asarray(got)[layer], np.asarray(want)
            for b in range(lens.shape[0]):
                np.testing.assert_allclose(got[b, :, :lens[b] + 1],
                                           want[b, :, :lens[b] + 1],
                                           atol=2e-7, rtol=2e-5)
    for got, before in zip(out[1:], (args[1], args[2], args[6], args[7])):
        if before is not None:
            _assert_rest_untouched(got, before, lens, layer)


class TestPagedDecodeKernel:
    def test_f32_multiblock_vs_oracle(self):
        _check(_mk(lens=(5, 40)), atol=1e-5)

    def test_bf16_cache(self):
        _check(_mk(lens=(17, 63), dtype=jnp.bfloat16), atol=2e-2)

    def test_int8_cache_fused_dequant(self):
        _check(_mk(lens=(5, 40), quantized=True), atol=1e-4)

    def test_ragged_lens_with_idle_slots(self):
        # an empty slot (lens=0) is given no grid step: its output is 0,
        # no row of it is written, and the garbage it holds reaches nothing
        _check(_mk(B=4, lens=(0, 1, 33, 95)), atol=1e-5)

    def test_int8_idle_and_full_slots(self):
        _check(_mk(B=4, lens=(0, 2, 64, 95), quantized=True), atol=1e-4)

    def test_full_slot_clamp(self):
        # lens == T-1: append lands in the last position of the last
        # block; the clamped index map must not read past the cache
        _check(_mk(lens=(95, 95)), atol=1e-5)

    def test_sequential_decode_crosses_blocks(self):
        # grow one slot across a block boundary (32-wide blocks), cache
        # threaded kernel-to-kernel, vs the oracle at every step
        T, D = 96, 16
        args = list(_mk(B=1, H=2, T=T, D=D, lens=(30,)))
        ref = [np.array(a) if a is not None else None
               for a in _layer_args(args)]
        rs = np.random.RandomState(9)
        for step in range(6):
            out = _run(tuple(args), T=T)
            want = _oracle(*ref)
            np.testing.assert_allclose(np.asarray(out[0], np.float32),
                                       want[0], atol=1e-5, rtol=1e-5)
            ln = int(np.asarray(args[3])[0]) + 1
            args[1], args[2] = out[1], out[2]
            ref[1], ref[2] = want[1], want[2]
            args[3] = jnp.asarray([ln], jnp.int32)
            ref[3] = np.asarray([ln], np.int32)
            nk = rs.randn(1, 2, 1, D)
            nv = rs.randn(1, 2, 1, D)
            args[4], args[5] = jnp.asarray(nk, jnp.float32), \
                jnp.asarray(nv, jnp.float32)
            ref[4], ref[5] = nk, nv

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    def test_layer_call_touches_only_its_appended_rows(self, quantized):
        # L = 3, called for layer 1: layers 0 and 2 and every row of
        # layer 1 other than the appended one come back bit-identical
        # (scales too), and the appended rows are the new token
        args = _mk(B=3, lens=(0, 31, 64), L=3, quantized=quantized)
        out = _run(args, layer=1)
        ref = _oracle(*_layer_args(args, 1))
        lens = np.asarray(args[3])
        pairs = [(out[1], args[1], ref[1]), (out[2], args[2], ref[2])]
        if quantized:
            pairs += [(out[3], args[6], ref[3]), (out[4], args[7], ref[4])]
        for got, before, want in pairs:
            _assert_rest_untouched(got, before, lens, layer=1)
            for layer in (0, 2):
                assert np.asarray(got)[layer].tobytes() == \
                    np.asarray(before)[layer].tobytes()
            for b in range(3):     # the oracle's scale may sit an ulp off
                np.testing.assert_allclose(
                    np.asarray(got)[1, b, :, lens[b]],
                    np.asarray(want)[b, :, lens[b]], rtol=2e-5, atol=0)

    # many heads a grid step, H not a power of two; 32-row blocks at T = 96
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    @pytest.mark.parametrize("lens", [
        (0, 31, 32, 95),      # empty; one short of a block edge; on it; wall
        (0, 95, 0, 64),       # idle slots beside full ones
        (33, 63, 64, 65),     # round the second edge
    ], ids=["edges", "idle-beside-full", "second-edge"])
    def test_all_heads_of_a_slot_in_one_block(self, lens, quantized):
        # output against the einsum oracle; every row the call did not
        # append bit-identical in all three layers (`_check` compares the
        # whole stacked arrays)
        args = _mk(B=4, H=12, lens=lens, L=3, quantized=quantized)
        assert pk._paged_heads(12, 32, 16, 1 if quantized else 4) == 12
        _check(args, atol=1e-4 if quantized else 1e-5)

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    def test_heads_cut_to_a_budget_walk_two_head_groups(
            self, quantized, monkeypatch):
        # a budget that holds 6 of the 12 heads: grid (2, n), the work
        # list walked once a head group
        itemsize = 1 if quantized else 4
        monkeypatch.setattr(pk, "_PAGED_BLOCK_BYTES", 6 * 32 * 16 * itemsize)
        assert pk._paged_heads(12, 32, 16, itemsize) == 6
        _check(_mk(B=3, H=12, lens=(0, 40, 95), quantized=quantized),
               atol=1e-4 if quantized else 1e-5)

    @pytest.mark.parametrize("lens,n", [
        ((0, 0, 0), 0),                    # no step for an empty slot
        ((0, 31, 0, 64, 0), 1 + 3),        # empty slots between live ones
        ((31, 32, 95), 1 + 2 + 3),
        ((200, 95, 94), 3 + 3 + 3),        # past the wall: clamped
    ])
    def test_work_list_holds_the_live_blocks_in_slot_order(self, lens, n):
        slot, blk, got = pk._paged_work(jnp.asarray(lens, jnp.int32), 96, 32)
        assert int(got) == n and slot.shape == blk.shape == (3 * len(lens),)
        want = [(b, j) for b, ln in enumerate(lens) if ln
                for j in range(min(ln, 95) // 32 + 1)]
        assert list(zip(np.asarray(slot)[:n].tolist(),
                        np.asarray(blk)[:n].tolist())) == want

    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["float", "int8"])
    @pytest.mark.parametrize("lens", [(0, 0, 0), (0, 40, 0), (95, 0, 1)],
                             ids=["all-empty", "one-live", "one-empty"])
    def test_an_empty_slot_is_left_as_it_is_and_given_zeros(self, lens,
                                                            quantized):
        # a grid of no steps runs; an empty slot's output rows are exact
        # zeros whatever garbage its rows hold, and every array of the
        # cache comes back with that slot bit-identical in every layer
        args = _mk(B=3, lens=lens, L=3, quantized=quantized)
        assert int(pk._paged_work(args[3], 96, 32)[2]) == sum(
            min(n, 95) // 32 + 1 for n in lens if n)
        out = _run(args)
        _check(args, atol=1e-4 if quantized else 1e-5)
        for b, n in enumerate(lens):
            if n:
                assert np.abs(np.asarray(out[0])[b]).max() > 0
                continue
            assert not np.asarray(out[0])[b].any()
            for got, before in zip(out[1:], (args[1], args[2], args[6],
                                             args[7])):
                if before is not None:
                    assert np.asarray(got)[:, b].tobytes() == \
                        np.asarray(before)[:, b].tobytes()

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_grid_of_the_served_shape_is_the_work_list(self, kv_dtype):
        # GPT-3 XL as the benchmark serves it: the lowered call's grid is
        # (head groups = 1, the work list's length), which is at most
        # B * T // block_k — the (B, H, T // 128) = 3 072-step grid cannot
        # come back unnoticed
        L, B, H, T, D = 2, 24, 16, 1024, 128
        quantized = kv_dtype == "int8"
        blk = pk._paged_block(T, H, D, kv_dtype, interpret=False)
        tok = jax.ShapeDtypeStruct((B, H, 1, D), jnp.bfloat16)
        cache = jax.ShapeDtypeStruct((L, B, H, T, D), jnp.dtype(kv_dtype))
        scale = jax.ShapeDtypeStruct((L, B, H, T), jnp.float32) \
            if quantized else None
        closed = jax.make_jaxpr(lambda *a: pk._paged_decode(
            *a, layer=1, block_k=blk, interpret=False))(
            tok, cache, cache, jax.ShapeDtypeStruct((B,), jnp.int32), tok,
            tok, scale, scale)
        (call,) = [e for e in closed.jaxpr.eqns
                   if e.primitive.name == "pallas_call"]
        gm = call.params["grid_mapping"]
        assert gm.num_dynamic_grid_bounds == 1 and len(gm.grid) == 2
        assert gm.grid[0] == H // pk._paged_heads(
            H, blk, D, jnp.dtype(kv_dtype).itemsize) == 1
        # the dynamic bound is the work list's length: the sum of the
        # slots' block counts, B * T // block_k when every slot is full
        for lens, n in ((np.zeros(B), 0), (np.full(B, T), B * T // blk),
                        (np.ones(B), B), (np.arange(B) * 40, None)):
            slot, _, got = pk._paged_work(jnp.asarray(lens, jnp.int32), T,
                                          blk)
            assert slot.shape == (B * T // blk,)
            assert np.count_nonzero(lens) <= int(got) <= B * T // blk <= 192
            assert n is None or int(got) == n

    @pytest.mark.parametrize("args,want", [
        # the emulator: the largest block that divides T
        ((2048, 2, 16, "float32", True), 128),
        ((96, 2, 16, "float32", True), 32),
        ((64, 12, 16, "int8", True), 64),
        ((7, 2, 16, "float32", True), None),
        # compiled for the TPU: every head of a slot within 512 KB a
        # block — GPT-3 XL 128 rows in bf16 and 256 in int8, gpt2-small
        # 256 and 512 — or the whole of a short cache
        ((1024, 16, 128, "bfloat16", False), 128),
        ((1024, 16, 128, "int8", False), 256),
        ((1024, 12, 64, "bfloat16", False), 256),
        ((2048, 12, 64, "int8", False), 512),
        ((2048, 2, 64, "float32", False), 1024),
        ((64, 4, 64, "bfloat16", False), 64),
        ((192, 4, 64, "bfloat16", False), None),
        ((24, 4, 64, "bfloat16", False), None),
        # float32 at GPT-3 XL's width: no block holds 16 heads, so the
        # smallest, and the heads are cut
        ((1024, 16, 128, "float32", False), 128),
    ])
    def test_paged_block_chooser(self, args, want):
        assert pk._paged_block(*args) == want

    @pytest.mark.parametrize("args,want", [
        ((16, 128, 128, 2), 16), ((16, 256, 128, 1), 16),
        ((16, 128, 128, 4), 8), ((12, 256, 64, 2), 12),
        ((12, 1024, 128, 4), 1), ((12, 128, 128, 4), 6),
    ])
    def test_paged_heads_chooser(self, args, want):
        assert pk._paged_heads(*args) == want


class TestDispatchGate:
    @pytest.fixture
    def interp_on(self):
        saved = get_flags("paged_flash_interpret")
        set_flags({"paged_flash_interpret": True})
        yield
        set_flags(saved)

    def test_interpret_dispatch_fires(self, interp_on):
        q, kc, vc, lens, nk, nv, _, _ = _mk(nan_tail=False)
        before = pk.attention_path_counts()["paged_flash"]
        out = pk.paged_decode_attention_or_none(q, kc, vc, lens, nk, nv,
                                                layer=LAYER)
        assert out is not None
        assert pk.attention_path_counts()["paged_flash"] == before + 1

    def test_emulator_flag_off_on_the_cpu_returns_none(self, interp_on):
        set_flags({"paged_flash_interpret": False})
        q, kc, vc, lens, nk, nv, _, _ = _mk(nan_tail=False)
        assert pk.paged_decode_attention_or_none(
            q, kc, vc, lens, nk, nv, layer=LAYER) is None

    def test_interpret_caps_reject_big_shapes(self, interp_on):
        q, kc, vc, lens, nk, nv, _, _ = _mk(B=16, H=8, T=64, D=16,
                                            lens=(1,) * 16,
                                            nan_tail=False)
        assert pk.paged_decode_attention_or_none(
            q, kc, vc, lens, nk, nv, layer=LAYER) is None     # B*H = 128 > 64

    def test_odd_head_dim_rejected(self, interp_on):
        q, kc, vc, lens, nk, nv, _, _ = _mk(D=12, nan_tail=False)
        assert pk.paged_decode_attention_or_none(
            q, kc, vc, lens, nk, nv, layer=LAYER) is None     # D % 8 != 0


#: what `LayerCacheView.attend` can be handed: (query heads a key-value
#: head, kind of layer, cache type, lengths before the token). 64 rows a
#: full layer (one slot at the wall), a ring of 16 every slot has wrapped.
ATTEND_CASES = {
    "gpt_float": (1, "full", "float32", (0, 17, 64)),
    "gpt_int8": (1, "full", "int8", (0, 17, 64)),
    "grouped_full": (2, "full", "float32", (0, 17, 64)),
    "grouped_ring_wrapped": (2, "window", "float32", (16, 37, 95)),
}


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["einsum", "emulated_kernel"])
@pytest.mark.parametrize("case", list(ATTEND_CASES))
def test_attend_is_the_one_decode_attention_over_the_cache(case, kernel):
    """One new token a slot through `LayerCacheView.attend`, on the
    einsum and on the emulated kernel it chooses, against the plain
    grouped-query oracle over the layer as it must be afterwards; the
    carrier holds that layer, and no other row of the cache moved."""
    from paddle_tpu.inference.serving.cache import (
        PagedKVCache, dequantize_kv)
    G, kind, kv_dtype, lens = ATTEND_CASES[case]
    B, H, D, T, W = 3, 2, 16, 64, 16
    ring, quantized = kind == "window", kv_dtype == "int8"
    cache = PagedKVCache(
        3 if ring else 2, B, H, T, D, kv_dtype=kv_dtype,
        layer_kinds=("window", "full", "window") if ring else None,
        window=W if ring else None)
    rs = np.random.RandomState(3)
    state = []
    for a in cache.state()[:-1]:
        x = rs.randn(*a.shape)
        state.append(jnp.asarray(_quantize_np(x)[0] if a.dtype == jnp.int8
                                 else np.abs(x) / 127 if a.ndim == 4 else x,
                                 a.dtype))
    lens = jnp.asarray(lens, jnp.int32)
    kv = cache.carrier(state + [lens])
    view = cache.views(kv)[-1]         # the second layer of its kind
    assert (view.kind, view.layer) == (kind, 1)
    names = ("wk", "wv") if ring else ("k", "v")
    was = {n: np.array(getattr(kv, n)) for n in names}
    if quantized:
        was.update(k_scale=np.array(kv.k_scale), v_scale=np.array(kv.v_scale))
    q, nk, nv = (jnp.asarray(rs.randn(B, H, n, D), jnp.float32)
                 for n in (G, 1, 1))
    before = pk.attention_path_counts()
    saved = get_flags("paged_flash_interpret")
    set_flags({"paged_flash_interpret": kernel})
    try:
        out = view.attend(q, nk, nv)
    finally:
        set_flags(saved)
    path = ("xla_paged" if not kernel
            else "paged_gqa" if G > 1 or ring else "paged_flash")
    after = pk.attention_path_counts()
    assert {p for p in after if after[p] != before.get(p, 0)} == {path}

    # the layer as it must be afterwards, in float. An empty slot (lens
    # == 0) is one row of work to every path but the work-list kernel,
    # which leaves it as it is and gives it 0
    rows = W if ring else T
    lens = np.asarray(lens)
    row = lens % rows if ring else np.minimum(lens, rows - 1)
    live = np.minimum(lens + 1, rows)
    held = lens > 0 if path == "paged_flash" else np.ones(B, bool)
    slots, row = np.arange(B)[held], row[held]
    want = []
    for name, new in zip(names, (nk, nv)):
        new = np.asarray(new)[:, :, 0]
        if quantized:
            new, new_sc = _quantize_np(new)
            sc = was[name + "_scale"]
            sc[1, slots, :, row] = new_sc[held]
            np.testing.assert_allclose(
                np.asarray(getattr(kv, name + "_scale")), sc, rtol=2e-5)
        was[name][1, slots, :, row] = new[held]
        assert np.array_equal(np.asarray(getattr(kv, name)), was[name])
        want.append(np.asarray(dequantize_kv(was[name][1], sc[1]))
                    if quantized else was[name][1])
    ok = (np.arange(rows)[None, :] < live[:, None])[:, None, None, None]
    ref = pk._gqa_oracle(q[:, :, :, None], want[0], want[1],
                         jnp.asarray(ok))[:, :, :, 0]
    ref = np.where(held[:, None, None, None], np.asarray(ref), 0.0)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), ref,
                               atol=1e-4 if quantized else 1e-5)
    if ring:                # the other kind's stack is the array it was
        assert kv.k is state[0] and kv.v is state[1]


def _tiny(**kw):
    from paddle_tpu.models import gpt_tiny
    m = gpt_tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                 num_heads=4, intermediate_size=64,
                 max_position_embeddings=64, **kw)
    m.eval()
    return m


class TestEngineFusedPath:
    @pytest.fixture
    def interp_on(self):
        saved = get_flags("paged_flash_interpret")
        set_flags({"paged_flash_interpret": True})
        yield
        set_flags(saved)

    def _greedy(self, model, kv_dtype, steps=20):
        from paddle_tpu.inference.serving import GenerationEngine
        eng = GenerationEngine(model, max_batch=2, max_seq_len=32,
                               prefill_buckets=(8,), kv_dtype=kv_dtype)
        rs = np.random.RandomState(4)
        toks = [[int(eng.prefill(s, rs.randint(1, VOCAB, (5,)).tolist()))]
                for s in range(2)]
        for _ in range(steps - 1):
            out = eng.decode()
            for s in range(2):
                toks[s].append(int(out[s]))
        return toks, eng

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_parity_and_compile_once(self, interp_on, kv_dtype):
        import paddle_tpu as paddle
        paddle.seed(0)
        model = _tiny()
        before = pk.attention_path_counts()
        fused_toks, fused_eng = self._greedy(model, kv_dtype)
        after = pk.attention_path_counts()
        assert after["paged_flash"] > before["paged_flash"]
        assert after["xla_paged"] == before["xla_paged"]
        assert fused_eng.decode_compiles == 1

        set_flags({"paged_flash_interpret": False})
        plain_toks, plain_eng = self._greedy(model, kv_dtype)
        assert pk.attention_path_counts()["paged_flash"] == \
            after["paged_flash"]
        assert plain_eng.decode_compiles == 1
        assert fused_toks == plain_toks

    def test_prefix_hit_suffix_admission(self, interp_on):
        # a prefix-cache HIT admits via the suffix-prefill path; the
        # following decode steps must still ride the fused kernel and
        # match the unfused engine token-for-token
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import (ContinuousBatcher,
                                                  GenerationEngine,
                                                  Request)
        paddle.seed(0)
        model = _tiny()
        rs = np.random.RandomState(8)
        head = rs.randint(1, VOCAB, (16,))
        reqs = [np.concatenate([head, rs.randint(1, VOCAB, (3,))]),
                np.concatenate([head, rs.randint(1, VOCAB, (4,))])]

        def serve():
            eng = GenerationEngine(model, max_batch=2, max_seq_len=32,
                                   prefill_buckets=(8, 16, 24),
                                   prefix_cache_bytes=16 << 20)
            b = ContinuousBatcher(eng)
            out = []
            for p in reqs:
                r = Request(prompt=p.copy(), max_new_tokens=5)
                b.submit(r)
                b.run_until_idle()
                out.append((list(r.tokens), r.prefix_len))
            return out, eng

        before = pk.attention_path_counts()
        fused, feng = serve()
        after = pk.attention_path_counts()
        assert after["paged_flash"] > before["paged_flash"]
        assert after["xla_paged"] == before["xla_paged"]
        assert fused[1][1] > 0          # second request was a prefix HIT
        assert feng.decode_compiles == 1

        set_flags({"paged_flash_interpret": False})
        plain, _ = serve()
        assert [t for t, _ in fused] == [t for t, _ in plain]

    @staticmethod
    def _decode_eqns(kv_dtype):
        """(every equation of `_decode_fn`'s jaxpr, sub-jaxprs included;
        the shapes of the engine's stacked cache arrays)."""
        import paddle_tpu as paddle
        from paddle_tpu.framework.random import RNG
        from paddle_tpu.inference.serving import GenerationEngine
        paddle.seed(0)
        eng = GenerationEngine(_tiny(), max_batch=2, max_seq_len=32,
                               prefill_buckets=(8,), kv_dtype=kv_dtype)
        closed = jax.make_jaxpr(eng._decode_fn)(
            [p._data for p in eng._weights],
            [b._data for b in eng._buffers], RNG.key, eng.kv.state(),
            eng._last, jnp.ones((eng.max_batch,), jnp.bool_))

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from walk(sub)

        shapes = {tuple(a.shape) for a in eng.kv.state()[:-1]}
        return list(walk(closed.jaxpr)), shapes

    @staticmethod
    def _assert_no_restack(eqns, shapes):
        # jnp.stack of the per-layer results is a `concatenate` whose
        # result has the stacked cache's shape
        for eqn in eqns:
            if eqn.primitive.name == "concatenate":
                assert tuple(eqn.outvars[0].aval.shape) not in shapes, eqn

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_decode_jaxpr_aliases_cache_and_never_restacks(
            self, interp_on, kv_dtype):
        eqns, shapes = self._decode_eqns(kv_dtype)
        self._assert_no_restack(eqns, shapes)
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(calls) == 2                      # one a layer
        # the kernel sees the scales as [L, B, H, 1, T]: the heads lead
        shapes |= {s[:3] + (1, s[3]) for s in shapes if len(s) == 4}
        for eqn in calls:
            aliases = dict(eqn.params["input_output_aliases"])
            # the grid's dynamic bound leads the equation's operands and
            # is not counted by the alias indices
            n_dyn = eqn.params["grid_mapping"].num_dynamic_grid_bounds
            invars = eqn.invars[n_dyn:]
            cache_ops = [i for i, v in enumerate(invars)
                         if tuple(v.aval.shape) in shapes]
            assert len(cache_ops) == (4 if kv_dtype == "int8" else 2)
            for i in cache_ops:
                assert i in aliases, (i, aliases)
                out = eqn.outvars[aliases[i]].aval
                assert (out.shape, out.dtype) == (
                    invars[i].aval.shape, invars[i].aval.dtype)

    @pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
    def test_einsum_fallback_jaxpr_never_restacks(self, kv_dtype):
        eqns, shapes = self._decode_eqns(kv_dtype)
        assert not [e for e in eqns if e.primitive.name == "pallas_call"]
        self._assert_no_restack(eqns, shapes)

    def test_cpu_default_takes_einsum_fallback(self):
        # without FLAGS_paged_flash_interpret the CPU engine must land
        # on the einsum path counter, never the kernel
        import paddle_tpu as paddle
        paddle.seed(0)
        before = pk.attention_path_counts()
        toks, eng = self._greedy(_tiny(), "float32", steps=4)
        after = pk.attention_path_counts()
        assert after["xla_paged"] > before["xla_paged"]
        assert after["paged_flash"] == before["paged_flash"]
        assert eng.decode_compiles == 1


class TestFusedBlock:
    def test_pair_api_parity_and_grads(self):
        import paddle_tpu as paddle
        import paddle_tpu.incubate.nn.functional as IF
        import paddle_tpu.nn.functional as F
        paddle.seed(0)
        B, T, E = 2, 8, 64
        x = paddle.randn([B, T, E])
        res = paddle.randn([B, T, E])
        gamma = paddle.ones([E])
        beta = paddle.zeros([E])
        for t in (x, res, gamma, beta):
            t.stop_gradient = False
        y, z = IF.fused_bias_dropout_residual_ln_pair(
            x, res, None, gamma, beta, 0.0, 1e-5, True)
        zr = res + x
        yr = F.layer_norm(zr, (E,), gamma, beta, 1e-5)
        np.testing.assert_allclose(z.numpy(), zr.numpy(), atol=1e-6,
                                   rtol=1e-6)
        np.testing.assert_allclose(y.numpy(), yr.numpy(), atol=1e-5,
                                   rtol=1e-5)
        (y.sum() + z.sum()).backward()
        gx = x.grad.numpy().copy()
        for t in (x, res, gamma, beta):
            t.clear_gradient()
        (yr.sum() + zr.sum()).backward()
        np.testing.assert_allclose(gx, x.grad.numpy(), atol=1e-4,
                                   rtol=1e-4)

    @pytest.fixture
    def fused_block(self):
        saved = get_flags("fused_block")
        set_flags({"fused_block": True})
        yield
        set_flags(saved)

    def test_decoder_layer_eval_parity(self, fused_block):
        import paddle_tpu as paddle
        paddle.seed(0)
        model = _tiny()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, VOCAB, (2, 12)))
        set_flags({"fused_block": False})
        ref = model(ids).numpy()
        set_flags({"fused_block": True})
        out = model(ids).numpy()
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_decoder_layer_train_grads(self, fused_block):
        # p=0 dropouts make fused and unfused training steps comparable
        import paddle_tpu as paddle
        paddle.seed(0)
        model = _tiny(attn_dropout_prob=0.0, hidden_dropout_prob=0.0)
        model.train()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, VOCAB, (2, 12)))

        def grads():
            model.clear_gradients()
            loss = (model(ids) ** 2).mean()
            loss.backward()
            return {n: p.grad.numpy().copy()
                    for n, p in model.named_parameters()
                    if p.grad is not None}

        set_flags({"fused_block": False})
        ref = grads()
        set_flags({"fused_block": True})
        got = grads()
        assert set(got) == set(ref) and got
        for n in ref:
            np.testing.assert_allclose(got[n], ref[n], atol=2e-5,
                                       rtol=2e-4, err_msg=n)

"""Static-shape paged KV cache + shared-prefix reuse for the serving engine.

The training-era decode path (`GPTForPretraining.generate`) grows its
KV cache by `concat` every token, so each step has a NEW shape — an
un-jittable host loop that retraces per token. Here the cache is
preallocated at engine construction:

    k: [n_layers, max_batch, n_heads, max_seq_len, key size]
    v: [n_layers, max_batch, n_heads, max_seq_len, value size]
    lens: int32 [max_batch]   (tokens already resident per slot;
                               0 = the slot holds no request)

and every update is a `jax.lax.dynamic_update_slice` at a traced
(slot, length) index — all dynamism lives in INDICES, never in shapes
(the DeepCompile framing: the decode step is one fixed compiled
program). A slot is freed by its `lens` going to 0 (the decode step that
no longer counts it live does that, engine.py) and refilled by the next
prefill overwriting it; no deallocation, no shape change, no recompile.
`lens == 0` is the ONE encoding of an empty slot — a prompt has at least
one token — and an empty slot costs a decode step nothing it can avoid:
the work-list kernel lists no block of it, the other paths clamp to one row.

Two throughput multipliers live here (ROADMAP item 3c):

  * **int8 quantized KV** (`kv_dtype="int8"`): k/v are stored as int8
    with a float32 scale per (layer, slot, head, token) — the
    symmetric absmax scheme the TPU paged-attention kernels use
    (int8 payload + scales side-buffer, dequantized next to the
    matmul). Bytes/slot roughly halve vs bf16, so `max_batch` doubles
    under the same HBM budget; the accuracy contract (greedy token
    parity vs the float cache) is `tests/test_serving.py::TestInt8KV`'s.
  * **`PrefixCache`**: LRU store of bucket-aligned prompt-prefix K/V
    keyed on the token ids themselves. Requests sharing a system
    prompt skip recomputing it — the engine copies the cached K/V into
    the slot and prefills only the suffix.

This module is the one place that knows the cache's layout: which arrays
a state holds and in what order (`_state_fields`), where a ring keeps a
position, how a prompt's K/V enters a slot (`StackedKV.insert`: split by
kind, ring rows, int8 quantise, a stored head verbatim), what a stored
head is (`PagedKVCache.head`, `head_kv`) and the attention of one new
token over a layer (`LayerCacheView.attend`). The engine threads the
state through its executables and a model hands `attend` its q, k, v;
neither names an array of the cache.

`LayerCacheView` is what a model's attention is handed for one layer
inside a traced decode step: a layer index into ONE `StackedKV` carrier
that all the step's views share. `attend` appends the step's K/V row to
its layer of the stacked buffers, in place (a paged kernel aliases the
cache to its output; the einsum fallback scatters one row a slot), and
REPLACES the carrier's arrays with the updated ones. After the model has
run, the carrier's arrays are the cache state the jitted function
returns: nothing is sliced out of the stacked cache and nothing is
stacked back. The carrier is a plain python holder of traced arrays
scoped to one trace — nothing escapes it.

Two kinds of layer live side by side in one manager (`layer_kinds`): a
"full" layer keeps every row up to `max_seq_len`; a "window" layer (sliding
-window attention) keeps a ring of `window` rows, position p at row
`p mod window`, so its bytes do not grow with the context. Each kind is
one stack: full `k/v [L_f, B, H_kv, max_seq_len, hd]`, window `wk/wv
[L_w, B, H_kv, window, hd]`; a model with no window layer has no window
stack and its state is the three (or five) arrays it always was.

The two stacks need not agree on anything but the slots: each kind has its
own number of key-value heads, and K and V each their own row size
(`PagedKVCache(kv_geometry=)`: a key row 192 wide beside a value row of
128, four heads on the full layers and eight on the rings). Nothing below
assumes that a K array and a V array have one shape, or that the full
stack's heads are the rings'. A kind whose key size is not its value size
keeps its K BY COLUMN, `[L, B, H_kv, key size, rows]`: a 192-wide bfloat16
row is no whole number of the chip's 128-lane tiles, XLA therefore stores
`[.., rows, 192]` with the rows minor, and a kernel that asks for it
row-major gets the whole stack copied in and out of every call
(`ops/pallas_kernels._paged_kv_decode`). `StackedKV.k_cols` names those
kinds; `insert`, `attend` and the einsum fallback are the only readers.

A third kind, "latent" (multi-head latent attention, every layer of such a
model): a token's row is ONE array of numbers a layer, (normed latent |
rotary part), that is key and value at once for every query head — no
per-head key, no value. Its stack is two arrays of the slot-by-
`max_seq_len` layout, `c [L, B, max_seq_len, latent size]` BY ROW and `kr
[L, B, rotary size, max_seq_len]` BY COLUMN, together exactly latent +
rotary numbers a token: a 512-wide bfloat16 row is four whole lane tiles
and stays row-major, where one 576-wide row (four tiles and a half) would
be padded to 640 or stored rows-minor and copied round every kernel call,
as the 192-wide key was; the 64-wide rotary part by column has the
positions along the lanes, nothing padded. `insert` takes a prompt's
rows as `serving().prefill` returns them, `attend` the absorbed query
(`kv_geometry={"latent": (latent size, rotary size)}`); such a cache has
no other kind of layer, no int8 rows and no stored head yet.
"""
from __future__ import annotations

import math
import os
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

from ...observability import metrics

__all__ = ["LayerCacheView", "PagedKVCache", "PrefixCache", "StackedKV",
           "bucket_for", "dequantize_kv", "head_kv", "quantize_kv"]

PREFIX_HITS = metrics.counter(
    "pt_prefix_cache_hits_total",
    "Admissions that reused a cached shared-prefix K/V")
PREFIX_MISSES = metrics.counter(
    "pt_prefix_cache_misses_total",
    "Admissions that found no cached prefix and prefilled from scratch")
PREFIX_EVICTIONS = metrics.counter(
    "pt_prefix_cache_evictions_total",
    "Prefix entries evicted by the LRU byte budget")
PREFIX_BYTES = metrics.gauge(
    "pt_prefix_cache_bytes",
    "Bytes of K/V (+scales) currently held by the prefix cache")

KV_BYTES = metrics.gauge(
    "pt_kv_bytes", "Bytes the paged KV cache reserves, by kind of layer",
    labelnames=("kind",))
KV_ROWS_LIVE = metrics.histogram(
    "pt_kv_rows_live",
    "Cache rows the live requests hold in one layer of a kind, one "
    "observation a decode step", labelnames=("kind",),
    buckets=metrics.exponential_buckets(64, 2, 16))
KV_ROWS_GIVEN = metrics.histogram(
    "pt_kv_rows_given",
    "Cache rows of a full layer a decode step is given to sweep: the "
    "device's own sum of the slots' lengths at the step's start, one "
    "observation a decode step. Over pt_kv_rows_live{kind=full} it is the "
    "share of the sweep that belongs to a request",
    buckets=metrics.exponential_buckets(64, 2, 16))

# env knob: default byte budget for each engine's PrefixCache; 0 disables
PREFIX_CACHE_BYTES_ENV = "PADDLE_TPU_PREFIX_CACHE_BYTES"
_PREFIX_CACHE_DEFAULT = 256 << 20


def _state_fields(quantized, ring, latent=False):
    """The arrays of a cache state, in the order the jitted steps thread
    them (their parameter numbers): the scales MUST travel with the
    values they decode, and a model with no window layer has the three
    (or five) arrays it always had; a latent cache its two."""
    if latent:
        return "c", "kr", "lens"
    if quantized:
        return "k", "v", "k_scale", "v_scale", "lens"
    if ring:
        return "k", "v", "wk", "wv", "lens"
    return "k", "v", "lens"


class StackedKV:
    """The stacked cache arrays of one traced step.

    k/v: [n_full_layers, B, n_heads, max_seq_len, key | value size]
    (traced); lens: int32 [B], each slot's length BEFORE this step's
    token. For a quantized cache k/v are int8 and k_scale/v_scale carry
    the float32 per-(layer, slot, head, token) scales [n_layers, B,
    n_heads, max_seq_len] (None otherwise). wk/wv: the window layers'
    rings [n_window_layers, B, ring heads, window, key | value size], None
    when the model has none; `kinds` then says which of the model's layers
    they are. A latent cache has c [n_layers, B, max_seq_len, latent size]
    and kr [n_layers, B, rotary size, max_seq_len] in their place.
    `insert` and each layer's `attend` replace the arrays with their
    updated ones."""

    __slots__ = ("k", "v", "lens", "k_scale", "v_scale", "wk", "wv", "kinds",
                 "k_cols", "c", "kr")

    def __init__(self, k=None, v=None, lens=None, k_scale=None, v_scale=None,
                 wk=None, wv=None, kinds=None, k_cols=(), c=None, kr=None):
        self.k = k
        self.v = v
        self.c = c
        self.kr = kr
        self.lens = lens
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.wk = wk
        self.wv = wv
        self.kinds = kinds
        self.k_cols = frozenset(k_cols)   # kinds whose K is [.., dk, rows]

    def state(self, lens=None) -> Tuple:
        """The flat tuple `PagedKVCache.carrier` took apart, holding the
        arrays as they are now, with `lens` for the lengths if given."""
        if lens is not None:
            self.lens = lens
        return tuple(getattr(self, f) for f in _state_fields(
            self.k_scale is not None, self.wk is not None,
            self.c is not None))

    def insert(self, ks, vs, true_len, slot, offset=0, prefix=None):
        """A prompt enters `slot`: ks/vs, a layer's fresh float K/V
        [1, H, T', size] each as `serving().prefill` returns them (H and
        the two sizes its kind's), are
        written from row `offset` of the slot (quantized first when the
        cache is int8), behind a stored head (`PagedKVCache.head`) put
        back VERBATIM at row 0 when `prefix` is given, and the slot's
        length becomes `true_len`. A window layer keeps the prompt's
        last `window` rows, position p at row p mod window. A latent
        cache is given ks alone, a layer's rows [1, T', latent + rotary
        size]: the latents go into `c` by row, the rotary parts into `kr`
        by column. Runs inside a trace."""
        import jax
        import jax.numpy as jnp
        kinds = self.kinds or ("full",) * len(ks)
        upd = jax.lax.dynamic_update_slice
        if self.c is not None:
            with jax.named_scope("insert_kv"):
                rows = jnp.stack(ks)                       # [L,1,T',r+dr]
                r = self.c.shape[-1]
                s, z = slot.astype(jnp.int32), jnp.int32(0)
                o = jnp.int32(offset)
                self.c = upd(self.c, rows[..., :r].astype(self.c.dtype),
                             (z, s, o, z))
                self.kr = upd(self.kr, jnp.swapaxes(
                    rows[..., r:], 2, 3).astype(self.kr.dtype), (z, s, z, o))
                self.lens = upd(self.lens, jnp.reshape(true_len, (1,)), (s,))
            return
        # the scope travels in the HLO's `op_name` metadata (HLO text,
        # xprof's op profile) whatever XLA fuses the insert into; the
        # fusions' instruction names do not change
        with jax.named_scope("insert_kv"):
            full = [i for i, kind in enumerate(kinds) if kind == "full"]
            ring = [i for i, kind in enumerate(kinds) if kind == "window"]
            fk = jnp.stack([ks[i] for i in full])      # [L_f,1,H,T',hd]
            fv = jnp.stack([vs[i] for i in full])
            if ring:
                wk = jnp.stack([ks[i] for i in ring])
                wv = jnp.stack([vs[i] for i in ring])
                W, tb = self.wv.shape[3], wk.shape[3]
                if tb > W:
                    r = jnp.arange(W, dtype=jnp.int32)
                    src = jnp.clip(r + W * ((true_len - 1 - r) // W),
                                   0, tb - 1)
                    wk = jnp.take(wk, src, axis=3)
                    wv = jnp.take(wv, src, axis=3)
            s, z = slot.astype(jnp.int32), jnp.int32(0)
            o = jnp.int32(offset)
            at_k = (z, s, z, o, z)
            if "full" in self.k_cols:       # K by column: rows are last
                fk, at_k = jnp.swapaxes(fk, 3, 4), (z, s, z, z, o)
            if ring and "window" in self.k_cols:
                wk = jnp.swapaxes(wk, 3, 4)
            if self.k_scale is not None:
                fk, k_sc = quantize_kv(fk)
                fv, v_sc = quantize_kv(fv)
                if prefix is not None:
                    self.k_scale = upd(self.k_scale, prefix[2], (z, s, z, z))
                    self.v_scale = upd(self.v_scale, prefix[3], (z, s, z, z))
                self.k_scale = upd(self.k_scale, k_sc, (z, s, z, o))
                self.v_scale = upd(self.v_scale, v_sc, (z, s, z, o))
            if prefix is not None:
                self.k = upd(self.k, prefix[0].astype(self.k.dtype),
                             (z, s, z, z, z))
                self.v = upd(self.v, prefix[1].astype(self.v.dtype),
                             (z, s, z, z, z))
            self.k = upd(self.k, fk.astype(self.k.dtype), at_k)
            self.v = upd(self.v, fv.astype(self.v.dtype), (z, s, z, o, z))
            self.lens = upd(self.lens, jnp.reshape(true_len, (1,)), (s,))
            if ring:
                self.wk = upd(self.wk, wk.astype(self.wk.dtype),
                              (z, s, z, z, z))
                self.wv = upd(self.wv, wv.astype(self.wv.dtype),
                              (z, s, z, z, z))


class LayerCacheView:
    """One layer of the paged cache during a traced step: `layer` (a
    static int) into the `StackedKV` carrier `kv` that every view of the
    step shares. `kind`: "full" (rows of `kv.k/v`), "window" (the ring
    `kv.wk/wv`) or "latent" (rows of `kv.c/kr`); `layer` counts within the
    stack of its kind. A model's attention hands `attend` the step's q, k,
    v."""

    __slots__ = ("kv", "layer", "kind")

    def __init__(self, kv, layer, kind="full"):
        self.kv = kv
        self.layer = int(layer)
        self.kind = kind

    @property
    def lens(self):
        return self.kv.lens

    def attend(self, q, k, v, sink=None, scale=None):
        """One new token a slot against this layer of the cache: q
        [B, H_kv, G, dk] (G query heads share a key-value head; GPT has
        G = 1), k [B, H_kv, 1, dk], v [B, H_kv, 1, dv]; arrays in, array
        [B, H_kv, G, dv] out. `sink` (float32 [H_kv * G], or None) is one
        more logit a query head in the softmax's denominator, which takes
        no value. A full layer appends at row `lens` (a slot that hit the
        wall rewrites its last row) and attends rows <= lens (an EMPTY
        slot, lens == 0, is at most that one row of work, its output
        belongs to no one, and the work-list kernel gives it 0); a window
        layer appends at `lens mod W` of its ring and attends the ring's
        live rows, which are in no order and need none under a softmax.
        The carrier's arrays are replaced by the updated ones.

        The kernel is chosen from what can be seen here: G = 1 on a full
        layer with K and V rows of one size and no sink takes the
        work-list kernel (int8 rows too), anything else the grouped-query
        kernel. Where the gate answers None (the CPU
        without FLAGS_paged_flash_interpret, an ineligible shape), one
        einsum over all the layer's rows, counter
        pt_attn_path_total{path=xla_paged}. Either way shapes never
        depend on traced values: decode compiles once.

        A LATENT layer is given its absorbed query q [B, 1, H, latent +
        rotary size], the new token's row k [B, 1, 1, latent + rotary
        size] and no v: every head attends the slot's rows, each key (all
        of it) and value (its latent part) at once, under the softmax
        `scale` its caller states — the row is wider than the head the
        scale belongs to; -> [B, 1, H, latent size]. The kernel
        `paged_latent_decode` (path latent_absorbed), else the einsum
        (path xla_latent). The other kinds' kernels scale by the key size:
        for them `scale` stays None."""
        import jax.numpy as jnp
        from ...ops import pallas_kernels as pk
        kv, layer = self.kv, self.layer
        if self.kind == "latent":
            fused = pk.paged_latent_decode_or_none(
                q[:, 0], kv.c, kv.kr, kv.lens, k[:, 0, 0], layer=layer,
                scale=scale)
            if fused is None:
                fused = self._latent_einsum(q[:, 0], k[:, 0, 0], scale)
            out, kv.c, kv.kr = fused
            return out[:, None]
        if scale is not None:
            raise ValueError("the kernels of a %s layer scale by its key "
                             "size: no other scale is taken" % self.kind)
        ring = self.kind == "window"
        kc, vc = (kv.wk, kv.wv) if ring else (kv.k, kv.v)
        k_cols = self.kind in kv.k_cols
        rows, lens = vc.shape[3], kv.lens
        work_list = q.shape[2] == 1 and not ring and sink is None \
            and k.shape[-1] == v.shape[-1]
        if work_list:
            fused = pk.paged_decode_attention_or_none(
                q, kc, vc, lens, k, v, kv.k_scale, kv.v_scale, layer=layer)
            if fused is not None:
                out, kv.k, kv.v, kv.k_scale, kv.v_scale = fused
                return out.astype(q.dtype)
        row = lens % rows if ring else jnp.minimum(lens, rows - 1)
        live = jnp.minimum(lens + 1, rows)
        fused = None if work_list else pk.paged_gqa_decode_or_none(
            q, kc, vc, row, live, k, v, layer=layer, sink=sink, ring=ring,
            k_cols=k_cols)
        if fused is None:
            fused = self._einsum(q, k, v, kc, vc, row, live, sink, k_cols,
                                 1.0 / math.sqrt(q.shape[-1]))
        out, kc, vc = fused
        if ring:
            kv.wk, kv.wv = kc, vc
        else:
            kv.k, kv.v = kc, vc
        return out

    def _latent_einsum(self, q, new, scale):
        """`attend` of a latent layer where no kernel runs: q [B, H, r +
        dr], the new row [B, r + dr]; scatter it, then one masked float32
        einsum over every row of the layer. -> (out [B, H, r], c', kr')."""
        import jax
        import jax.numpy as jnp
        from ...ops import pallas_kernels as pk
        pk._note_attn_path("xla_latent")
        kv, layer = self.kv, self.layer
        r, rows = kv.c.shape[-1], kv.c.shape[2]
        slots = jnp.arange(q.shape[0])
        row = jnp.minimum(kv.lens, rows - 1)
        c = kv.c.at[layer, slots, row].set(new[:, :r].astype(kv.c.dtype))
        kr = kv.kr.at[layer, slots, :, row].set(
            new[:, r:].astype(kv.kr.dtype))
        cf, qf = c[layer].astype(jnp.float32), q.astype(jnp.float32)
        scores = (jnp.einsum("bhr,btr->bht", qf[..., :r], cf)
                  + jnp.einsum("bhd,bdt->bht", qf[..., r:],
                               kr[layer].astype(jnp.float32))) * scale
        ok = jnp.arange(rows)[None, :] <= row[:, None]          # [B, rows]
        probs = jax.nn.softmax(
            jnp.where(ok[:, None, :], scores, jnp.float32(-1e30)), axis=-1)
        return jnp.einsum("bht,btr->bhr", probs, cf).astype(q.dtype), c, kr

    def _einsum(self, q, k, v, kc, vc, row, live, sink, k_cols, scale):
        """`attend` where no kernel runs: scatter the new row (and its
        scales), then one masked float32 einsum over every row of the
        layer, the scores times `scale` (the sink, where there is one, a
        last column of the scores that the value product leaves out). ->
        (out, kc', vc'); updated scales go to the carrier."""
        import jax
        import jax.numpy as jnp
        from ...ops import pallas_kernels as pk
        pk._note_attn_path("xla_paged")
        kv, layer = self.kv, self.layer
        slots = jnp.arange(row.shape[0])

        def append(buf, new):
            """buf[layer, b, :, row[b]] = new[b, :, 0] for every slot b."""
            return buf.at[layer, slots, :, row].set(
                new[:, :, 0].astype(buf.dtype))

        quantized = kc.dtype == jnp.int8
        if quantized:
            k, k_sc = quantize_kv(k)       # int8 [B,H,1,hd] + f32 [B,H,1]
            v, v_sc = quantize_kv(v)
            kv.k_scale = append(kv.k_scale, k_sc)
            kv.v_scale = append(kv.v_scale, v_sc)
        if k_cols:           # kc[layer, b, :, :, row[b]] = k[b, :, 0]
            kc = kc.at[layer, slots, :, :, row].set(
                k[:, :, 0].astype(kc.dtype))
            kf = jnp.swapaxes(kc[layer], 2, 3).astype(jnp.float32)
        else:
            kc = append(kc, k)
            kf = kc[layer].astype(jnp.float32)
        vc = append(vc, v)
        vf = vc[layer].astype(jnp.float32)
        if quantized:
            kf = kf * kv.k_scale[layer][..., None]
            vf = vf * kv.v_scale[layer][..., None]
        scores = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                            kf) * scale
        ok = jnp.arange(vc.shape[3])[None, :] < live[:, None]   # [B, rows]
        scores = jnp.where(ok[:, None, None, :], scores, jnp.float32(-1e30))
        if sink is not None:
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(1, q.shape[1], -1, 1),
                scores.shape[:-1] + (1,))], -1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :vc.shape[3]]
        out = jnp.einsum("bhgk,bhkd->bhgd", probs, vf)
        return out.astype(q.dtype), kc, vc


def bucket_for(length: int, buckets: Sequence[int]) -> int:
    """Smallest configured prefill bucket that fits `length` tokens.

    Mixed request lengths collapse onto <= len(buckets) compiled prefill
    executables; a prompt longer than the largest bucket is a caller
    error (raise, don't silently truncate someone's context)."""
    for b in buckets:
        if length <= b:
            return int(b)
    raise ValueError(
        "prompt of %d tokens exceeds the largest prefill bucket %d; "
        "configure larger prefill_buckets (each must stay <= max_seq_len)"
        % (length, max(buckets)))


def quantize_kv(x, eps=1e-8):
    """Symmetric absmax int8 quantization over the last (head_dim) axis.

    Returns (int8 values, float32 scales) with scales shaped like `x`
    minus its last axis — one scale per (…, token). The zero-row guard
    keeps idle-slot garbage finite (scale floor -> dequant of a zero
    row is exactly zero)."""
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = (jnp.maximum(amax, eps) / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype="float32"):
    """Inverse of `quantize_kv`: int8 values × per-token scales."""
    import jax.numpy as jnp
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


class PagedKVCache:
    """Host-side handle on the preallocated cache state.

    Owns the device buffers between steps; the engine threads them
    through its jitted prefill/decode executables (donated, so XLA
    updates them in place in HBM instead of double-buffering).

    `kv_dtype="int8"` stores k/v as int8 plus float32 `k_scale`/
    `v_scale` side-buffers of shape [n_layers, max_batch, n_heads,
    max_seq_len] — ~0.53x the bytes of bf16 at head_dim 64, which is
    the whole point: more decode slots per HBM byte.

    `layer_kinds` ("full" | "window" a layer; default all full) with
    `window` splits the layers into the two stacks of the module
    docstring; `n_heads` is the number of key-value heads and `head_dim`
    the size of a key row and of a value row, of both kinds — unless
    `kv_geometry` says otherwise: {kind: (key-value heads, key size, value
    size)} for the kinds whose stack differs (`geometry` holds the answer
    for both), and then `n_heads` and `head_dim` may be None. A "latent"
    kind (every layer, or none) has no heads and no value: its geometry
    is (latent size, rotary size), its stack `c` and `kr` of the module
    docstring."""

    def __init__(self, n_layers: int, max_batch: int, n_heads: Optional[int],
                 max_seq_len: int, head_dim: Optional[int],
                 kv_dtype="float32",
                 layer_kinds: Optional[Sequence[str]] = None,
                 window: Optional[int] = None, kv_geometry=None):
        import jax.numpy as jnp
        self.n_layers = int(n_layers)
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.geometry = {} if n_heads is None else {
            kind: (int(n_heads), int(head_dim), int(head_dim))
            for kind in ("full", "window")}
        for kind, g in (kv_geometry or {}).items():
            self.geometry[kind] = tuple(int(x) for x in g)
        # a kind of K/V stack that is not stated is like the one that is
        stated = [g for k, g in self.geometry.items() if k != "latent"]
        for kind in ("full", "window") if stated else ():
            self.geometry.setdefault(kind, stated[0])
        self.kv_dtype = str(kv_dtype)
        self.quantized = self.kv_dtype == "int8"
        self.layer_kinds = tuple(layer_kinds or ("full",) * self.n_layers)
        if len(self.layer_kinds) != self.n_layers \
                or set(self.layer_kinds) - {"full", "window", "latent"}:
            raise ValueError("layer_kinds must name each of the %d layers "
                             "\"full\", \"window\" or \"latent\""
                             % self.n_layers)
        if set(self.layer_kinds) - set(self.geometry):
            raise ValueError("no geometry for the layers of kind %s"
                             % sorted(set(self.layer_kinds)
                                      - set(self.geometry)))
        self.lens = jnp.zeros((self.max_batch,), jnp.int32)
        self.k = self.v = self.wk = self.wv = self.c = self.kr = None
        self.k_scale = self.v_scale = None
        self.window, self.k_cols = 0, ()
        if "latent" in self.layer_kinds:
            if set(self.layer_kinds) != {"latent"} or self.quantized:
                raise ValueError("a latent cache has latent layers alone "
                                 "and no int8 rows yet")
            r, dr = self.geometry["latent"]
            lead = (self.n_layers, self.max_batch)
            self.c = jnp.zeros(lead + (self.max_seq_len, r), self.kv_dtype)
            self.kr = jnp.zeros(lead + (dr, self.max_seq_len), self.kv_dtype)
        else:
            self._kv_stacks(window)
        self._fields = _state_fields(self.quantized, self.wk is not None,
                                     self.c is not None)
        for kind, n in self.nbytes_by_kind().items():
            KV_BYTES.labels(kind).set(n)

    def _kv_stacks(self, window):
        """The full layers' K and V (and scales) and the window layers'
        rings of `window` rows."""
        import jax.numpy as jnp
        n_window = self.layer_kinds.count("window")
        # a ring never needs more rows than a slot can hold
        self.window = min(int(window or 0), self.max_seq_len)
        if n_window and self.window < 1:
            raise ValueError("window layers need a window")
        if n_window and self.quantized:
            raise ValueError("an int8 cache has no window layers yet")
        store = jnp.int8 if self.quantized else self.kv_dtype

        # a key row that is not a value row's size is kept by column
        # (the module docstring says why)
        self.k_cols = tuple(kind for kind, (_, dk, dv)
                            in sorted(self.geometry.items()) if dk != dv)
        if self.k_cols and self.quantized:
            raise ValueError("an int8 cache keeps no K by column yet")

        def stack(kind, layers, rows):
            heads, dk, dv = self.geometry[kind]
            lead = (layers, self.max_batch, heads)
            k_shape = (dk, rows) if kind in self.k_cols else (rows, dk)
            return jnp.zeros(lead + k_shape, store), \
                jnp.zeros(lead + (rows, dv), store)

        self.k, self.v = stack("full", self.n_layers - n_window,
                               self.max_seq_len)
        if self.quantized:
            self.k_scale = jnp.zeros(self.k.shape[:-1], jnp.float32)
            self.v_scale = jnp.zeros(self.v.shape[:-1], jnp.float32)
        if n_window:
            self.wk, self.wv = stack("window", n_window, self.window)

    def layer_index(self, layer: int) -> Tuple[str, int]:
        """(kind, index within that kind's stack) of a model layer."""
        kind = self.layer_kinds[layer]
        return kind, self.layer_kinds[:layer].count(kind)

    def nbytes_by_kind(self) -> dict:
        """Bytes reserved a kind: each stack's own arrays, so unequal
        heads and K and V rows of different sizes count as they are."""
        if self.c is not None:
            return {"latent": int(self.c.nbytes) + int(self.kr.nbytes)}
        full = int(self.k.nbytes) + int(self.v.nbytes)
        if self.quantized:
            full += int(self.k_scale.nbytes) + int(self.v_scale.nbytes)
        ring = 0 if self.wk is None else \
            int(self.wk.nbytes) + int(self.wv.nbytes)
        return {"full": full, "window": ring}

    @property
    def nbytes(self) -> int:
        return sum(self.nbytes_by_kind().values()) + int(self.lens.nbytes)

    def observe_live_rows(self, lengths) -> None:
        """One observation a kind of `pt_kv_rows_live`: the rows that
        requests of these context lengths hold in one layer of it. A row
        is one position's K and V over the kind's heads (`geometry`): its
        bytes are the kind's own, not one size for both stacks."""
        kind = "latent" if self.c is not None else "full"
        KV_ROWS_LIVE.labels(kind).observe(
            float(sum(min(n, self.max_seq_len) for n in lengths)))
        if self.wk is not None:
            KV_ROWS_LIVE.labels("window").observe(
                float(sum(min(n, self.window) for n in lengths)))

    def state(self) -> Tuple:
        """Flat state tuple the jitted steps thread (and donate), in
        `_state_fields`' order."""
        return tuple(getattr(self, f) for f in self._fields)

    def _checked(self, state) -> Tuple:
        """`state` as a tuple, if it is a state of this cache: its
        arity, and the type its values are stored in."""
        state = tuple(state)
        if len(state) != len(self._fields):
            raise ValueError(
                "a state of this cache has %d arrays for kv_dtype=%s, got "
                "%d (a quantized cache's scales must round-trip with it)"
                % (len(self._fields), self.kv_dtype, len(state)))
        store = getattr(self, self._fields[0]).dtype
        for name, arr in zip(self._fields, state[:2]):
            if str(arr.dtype) != str(store):
                raise ValueError(
                    "state %s dtype %s does not match this cache's "
                    "kv_dtype=%s storage (%s); rebuild the cache instead "
                    "of mixing quantized and float states"
                    % (name, arr.dtype, self.kv_dtype, store))
        return state

    def carrier(self, state) -> StackedKV:
        """A state tuple (this cache's own, or the traced one a jitted
        step received) as one `StackedKV`."""
        return StackedKV(kinds=self.layer_kinds, k_cols=self.k_cols,
                         **dict(zip(self._fields, self._checked(state))))

    def set_state(self, *state) -> None:
        if len(state) == 1 and isinstance(state[0], (tuple, list)):
            state = state[0]
        for f, arr in zip(self._fields, self._checked(state)):
            setattr(self, f, arr)

    def views(self, carrier):
        """A `LayerCacheView` a model layer, all on `carrier`."""
        views = []
        for i in range(self.n_layers):
            kind, index = self.layer_index(i)
            views.append(LayerCacheView(carrier, index, kind))
        return views

    def head(self, slot: int, n: int):
        """The first `n` rows of `slot` in every FULL layer, as new device
        buffers (a later donation of the cache cannot invalidate them):
        k [n_full_layers, 1, heads, n, key size] and v [.., value size] of
        the full stack's geometry, then the two scales when quantized.
        What `StackedKV.insert(prefix=)` puts back and `head_kv` reads. A
        cache with rings has no reusable head: position p of a ring is
        gone once p + window is written."""
        if self.wk is not None:
            raise ValueError("a cache with window layers keeps no prompt "
                             "head: its rings have overwritten it")
        if self.c is not None:
            raise ValueError("a latent cache keeps no prompt head yet: no "
                             "prefill attends stored latents")
        s = int(slot)
        arrays = [self.k[:, s:s + 1, :, :n, :], self.v[:, s:s + 1, :, :n, :]]
        if self.quantized:
            arrays += [self.k_scale[:, s:s + 1, :, :n],
                       self.v_scale[:, s:s + 1, :, :n]]
        return arrays


def head_kv(head):
    """(k, v) in float of a stored head (`PagedKVCache.head`): as they
    are, or dequantized when the head carries its scales."""
    if len(head) == 2:
        return tuple(head)
    k, v, k_scale, v_scale = head
    return dequantize_kv(k, k_scale), dequantize_kv(v, v_scale)


def prefix_cache_budget(explicit: Optional[int] = None) -> int:
    """Resolve the prefix-cache byte budget: explicit arg beats the
    PADDLE_TPU_PREFIX_CACHE_BYTES env, which beats the 256 MiB default.
    <= 0 disables reuse entirely."""
    if explicit is not None:
        return int(explicit)
    try:
        return int(os.environ.get(PREFIX_CACHE_BYTES_ENV,
                                  _PREFIX_CACHE_DEFAULT))
    except ValueError:
        return _PREFIX_CACHE_DEFAULT


class PrefixCache:
    """LRU map from bucket-aligned token-id prefixes to their K/V.

    Keys are the prompt's first `p` token ids (p a configured prefill
    bucket — bucket alignment keeps the engine's insert executables
    compile-once-per-bucket); values are the device arrays the engine
    stored after a cold prefill: (k, v) of shape
    [n_layers, 1, n_heads, p, head_dim] plus (k_scale, v_scale) when
    the paged cache is quantized — a quantized prefix is re-inserted
    verbatim, never re-quantized, so a hit adds zero extra rounding
    error over the cold path.

    Eviction is LRU under `max_bytes` (`PADDLE_TPU_PREFIX_CACHE_BYTES`):
    system prompts are few and hot, one-off prompt heads are many and
    cold, which is exactly the access pattern LRU wins on."""

    def __init__(self, max_bytes: int, buckets: Sequence[int]):
        self.max_bytes = int(max_bytes)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self._entries: "OrderedDict[Tuple[int, ...], Tuple]" = OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _nbytes(arrays) -> int:
        return sum(int(a.nbytes) for a in arrays)

    def lookup(self, prompt) -> Tuple[int, Optional[Tuple]]:
        """(prefix_len, arrays) for the LONGEST cached prefix of
        `prompt`, or (0, None). Only proper prefixes qualify (p <
        len(prompt)): a hit must leave >= 1 suffix token to prefill,
        because the first generated token comes out of the suffix pass.
        A prompt sharing tokens with a cached entry but not on a bucket
        boundary simply misses — alignment is what keeps the insert
        executables static-shaped."""
        n = len(prompt)
        for p in reversed(self.buckets):
            if p >= n:
                continue
            key = tuple(int(t) for t in prompt[:p])
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                PREFIX_HITS.inc()
                return p, entry
        self.misses += 1
        PREFIX_MISSES.inc()
        return 0, None

    def store(self, key_tokens, arrays) -> bool:
        """Admit a prefix (device arrays) under the LRU byte budget.
        Refreshes recency on re-store of an existing key. Returns
        whether the entry is resident afterwards."""
        key = tuple(int(t) for t in key_tokens)
        if key in self._entries:
            self._entries.move_to_end(key)
            return True
        size = self._nbytes(arrays)
        if size > self.max_bytes:
            return False             # bigger than the whole budget
        while self.bytes + size > self.max_bytes and self._entries:
            _, old = self._entries.popitem(last=False)
            self.bytes -= self._nbytes(old)
            self.evictions += 1
            PREFIX_EVICTIONS.inc()
        self._entries[key] = tuple(arrays)
        self.bytes += size
        PREFIX_BYTES.set(self.bytes)
        return True

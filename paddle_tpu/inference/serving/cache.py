"""Static-shape paged KV cache + shared-prefix reuse for the serving engine.

The training-era decode path (`GPTForPretraining.generate`) grows its
KV cache by `concat` every token, so each step has a NEW shape — an
un-jittable host loop that retraces per token. Here the cache is
preallocated at engine construction:

    k/v: [n_layers, max_batch, n_heads, max_seq_len, head_dim]
    lens: int32 [max_batch]   (tokens already resident per slot)

and every update is a `jax.lax.dynamic_update_slice` at a traced
(slot, length) index — all dynamism lives in INDICES, never in shapes
(the DeepCompile framing: the decode step is one fixed compiled
program). A slot is "freed" by simply overwriting it on the next
prefill; no deallocation, no shape change, no recompile.

Two throughput multipliers live here (ROADMAP item 3c):

  * **int8 quantized KV** (`kv_dtype="int8"`): k/v are stored as int8
    with a float32 scale per (layer, slot, head, token) — the
    symmetric absmax scheme the TPU paged-attention kernels use
    (int8 payload + scales side-buffer, dequantized next to the
    matmul). Bytes/slot roughly halve vs bf16, so `max_batch` doubles
    under the same HBM budget; the accuracy contract (greedy token
    parity vs the float cache) is gated in `inference_bench.py`.
  * **`PrefixCache`**: LRU store of bucket-aligned prompt-prefix K/V
    keyed on the token ids themselves. Requests sharing a system
    prompt skip recomputing it — the engine copies the cached K/V into
    the slot and prefills only the suffix.

`LayerCacheView` is what `GPTAttention` is handed for one layer inside a
traced decode step: a layer index into ONE `StackedKV` carrier that all
the step's views share. The attention layer appends the step's K/V row
to its layer of the stacked buffers, in place (the paged kernel aliases
the cache to its output; the einsum fallback scatters one row a slot),
and REPLACES the carrier's arrays with the updated ones. After the model
has run, the carrier's arrays are the cache state the jitted function
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
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import Optional, Sequence, Tuple

from ...observability import metrics

__all__ = ["LayerCacheView", "PagedKVCache", "PrefixCache", "StackedKV",
           "bucket_for", "dequantize_kv", "quantize_kv"]

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

# env knob: default byte budget for each engine's PrefixCache; 0 disables
PREFIX_CACHE_BYTES_ENV = "PADDLE_TPU_PREFIX_CACHE_BYTES"
_PREFIX_CACHE_DEFAULT = 256 << 20


class StackedKV:
    """The stacked cache arrays of one traced decode step.

    k/v: [n_layers, B, n_heads, max_seq_len, head_dim] (traced); lens:
    int32 [B], each slot's length BEFORE this step's token. For a
    quantized cache k/v are int8 and k_scale/v_scale carry the float32
    per-(layer, slot, head, token) scales [n_layers, B, n_heads,
    max_seq_len] (None otherwise). wk/wv: the window layers' rings
    [n_window_layers, B, n_heads, window, head_dim], None when the model
    has none. Each layer's attention replaces the arrays with its
    updated ones."""

    __slots__ = ("k", "v", "lens", "k_scale", "v_scale", "wk", "wv")

    def __init__(self, k, v, lens, k_scale=None, v_scale=None, wk=None,
                 wv=None):
        self.k = k
        self.v = v
        self.lens = lens
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.wk = wk
        self.wv = wv


class LayerCacheView:
    """One layer of the paged cache during a traced step: `layer` (a
    static int) into the `StackedKV` carrier `kv` that every view of the
    step shares. `GPTAttention.forward` detects this type (duck-typed on
    `.lens`), writes the incoming K/V at `(layer, slot, :, lens[slot])`
    (quantizing on append), attends that layer over positions `<= lens`,
    and stores the updated stacked buffers back on the carrier.

    `windows`: optional static tuple of attend-window lengths (the
    engine passes its prefill buckets + max_seq_len, sorted). The
    einsum fallback in models/gpt.py uses it to `lax.switch` onto the
    smallest window covering max(lens)+1 instead of attending (and,
    for int8, dequantizing) the full T_max buffer every step. None →
    full-depth attention (legacy callers). Shapes stay static either
    way — the traced lens picks a branch, never a shape.

    `kind`: "full" (rows of `kv.k/v`) or "window" (the ring `kv.wk/wv`);
    `layer` counts within the stack of its kind."""

    __slots__ = ("kv", "layer", "windows", "kind")

    def __init__(self, kv, layer, windows=None, kind="full"):
        self.kv = kv
        self.layer = int(layer)
        self.windows = windows
        self.kind = kind

    @property
    def lens(self):
        return self.kv.lens


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
    docstring; `n_heads` is the number of key-value heads."""

    def __init__(self, n_layers: int, max_batch: int, n_heads: int,
                 max_seq_len: int, head_dim: int, kv_dtype="float32",
                 layer_kinds: Optional[Sequence[str]] = None,
                 window: Optional[int] = None):
        import jax.numpy as jnp
        self.n_layers = int(n_layers)
        self.max_batch = int(max_batch)
        self.n_heads = int(n_heads)
        self.max_seq_len = int(max_seq_len)
        self.head_dim = int(head_dim)
        self.kv_dtype = str(kv_dtype)
        self.quantized = self.kv_dtype == "int8"
        self.layer_kinds = tuple(layer_kinds or ("full",) * self.n_layers)
        if len(self.layer_kinds) != self.n_layers \
                or set(self.layer_kinds) - {"full", "window"}:
            raise ValueError("layer_kinds must name each of the %d layers "
                             "\"full\" or \"window\"" % self.n_layers)
        n_window = self.layer_kinds.count("window")
        # a ring never needs more rows than a slot can hold
        self.window = min(int(window or 0), self.max_seq_len)
        if n_window and self.window < 1:
            raise ValueError("window layers need a window")
        if n_window and self.quantized:
            raise ValueError("an int8 cache has no window layers yet")
        shape = (self.n_layers - n_window, self.max_batch, self.n_heads,
                 self.max_seq_len, self.head_dim)
        store = jnp.int8 if self.quantized else self.kv_dtype
        self.k = jnp.zeros(shape, store)
        self.v = jnp.zeros(shape, store)
        self.lens = jnp.zeros((self.max_batch,), jnp.int32)
        if self.quantized:
            self.k_scale = jnp.zeros(shape[:-1], jnp.float32)
            self.v_scale = jnp.zeros(shape[:-1], jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        if n_window:
            ring = (n_window,) + shape[1:3] + (self.window, self.head_dim)
            self.wk = jnp.zeros(ring, store)
            self.wv = jnp.zeros(ring, store)
        else:
            self.wk = self.wv = None
        for kind, n in self.nbytes_by_kind().items():
            KV_BYTES.labels(kind).set(n)

    def layer_index(self, layer: int) -> Tuple[str, int]:
        """(kind, index within that kind's stack) of a model layer."""
        kind = self.layer_kinds[layer]
        return kind, self.layer_kinds[:layer].count(kind)

    def nbytes_by_kind(self) -> dict:
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
        requests of these context lengths hold in one layer of it."""
        KV_ROWS_LIVE.labels("full").observe(
            float(sum(min(n, self.max_seq_len) for n in lengths)))
        if self.wk is not None:
            KV_ROWS_LIVE.labels("window").observe(
                float(sum(min(n, self.window) for n in lengths)))

    def state(self) -> Tuple:
        """Flat state tuple the jitted steps thread (and donate).

        Float: (k, v, lens). Quantized: (k, v, k_scale, v_scale, lens)
        — the scales MUST travel with the values they decode. With
        window layers: (k, v, wk, wv, lens)."""
        if self.quantized:
            return self.k, self.v, self.k_scale, self.v_scale, self.lens
        if self.wk is not None:
            return self.k, self.v, self.wk, self.wv, self.lens
        return self.k, self.v, self.lens

    def set_state(self, *state) -> None:
        want = 5 if self.quantized or self.wk is not None else 3
        if len(state) == 1 and isinstance(state[0], (tuple, list)):
            state = tuple(state[0])
        if len(state) != want:
            raise ValueError(
                "set_state expects %d arrays for kv_dtype=%s, got %d "
                "(a quantized cache's scales must round-trip with it)"
                % (want, self.kv_dtype, len(state)))
        k, v = state[0], state[1]
        for name, arr, ref in (("k", k, self.k), ("v", v, self.v)):
            if str(arr.dtype) != str(ref.dtype):
                raise ValueError(
                    "set_state %s dtype %s does not match this cache's "
                    "kv_dtype=%s storage (%s); rebuild the cache instead "
                    "of mixing quantized and float states"
                    % (name, arr.dtype, self.kv_dtype, ref.dtype))
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale, self.lens = state
        elif self.wk is not None:
            self.k, self.v, self.wk, self.wv, self.lens = state
        else:
            self.k, self.v, self.lens = state


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

"""Jitted generation engine: bucketed prefill + compile-once decode.

The serving-side replacement for `GPTForPretraining.generate()`'s eager
loop. Three executable families cover all of decoding:

  * prefill(bucket): one compile per configured prompt-length bucket.
    The prompt is right-padded to the bucket on the host (exact under
    causal attention — pad columns sit to the right of every real
    query position), runs through the legacy concat-cache path as a
    single forward, and the resulting per-layer K/V is inserted into
    the paged cache at the slot index INSIDE the same executable, so
    admission costs one dispatch and no extra compiles.
  * suffix-prefill(prefix_len, bucket): the shared-prefix fast path.
    When the `PrefixCache` holds K/V for the prompt's head (a shared
    system prompt), only the suffix runs through the model — the
    cached prefix K/V enters as a regular argument, is concatenated as
    a legacy cache (bottom-right-causal suffix attention in gpt.py),
    and both halves are inserted into the slot inside the executable.
    One compile per observed (prefix bucket, suffix bucket) pair;
    TTFT on a hit is suffix-length cost.
  * decode: ONE compile, ever. All requests, all tokens, all slots run
    the same [max_batch, 1] program; per-slot progress lives in the
    `lens` index vector (cache.py), never in shapes.

With `kv_dtype="int8"` the quantize-on-append folds into the SAME
executables: prefill/suffix quantize the freshly-computed K/V before
the slot insert, decode quantizes the step's K/V inside
`_paged_decode_attention` and dequantizes next to the matmul. The
cache state a jitted step threads is then the 5-tuple
(k, v, k_scale, v_scale, lens) instead of (k, v, lens) — shapes still
never change, so decode still compiles exactly once. A cached prefix
is re-inserted VERBATIM (int8 payload + its original scales), never
dequantized-and-requantized, so a prefix hit is bit-identical to the
cold path's cache contents.

All executables are wrapped in `StepTelemetry`
("serve_prefill"/"serve_suffix"/"serve_decode") so
`pt_jit_retraces_total` accounts the compile-once contract, and the
engine additionally counts REAL jax traces (the python body runs once
per trace) in `prefill_compiles`/`suffix_prefill_compiles`/
`decode_compiles` — the numbers the tests and the SERVING_SMOKE gate
assert on, immune to the telemetry kill-switch.

The engine reads no attribute of any particular model. It calls
`model.serving()` and asks the answer for what it needs:

  n_layers, kv_heads, head_dim     the cache's geometry
  layer_kinds, window              "full" | "window" a layer (cache.py)
  max_positions                    the longest context the model places
  prefix_cache                     whether a stored prompt head can be
                                   re-inserted (not into a ring)
  selfchecks                       the Pallas self-checks its kernels need
  moe_layers, moe_top_k, moe_experts
                                   expert layers; a step of such a model
                                   sends its routing statistics (int32
                                   [2]) back WITH its tokens
  prefill(ids, true_len[, prefix]) -> (logits [1, 1, V] at the last real
                                   row, [k a layer], [v a layer], stats)
  decode(last, views)              -> (logits [B, 1, V], stats), the
                                   views' carrier left holding the
                                   updated cache

`models/gpt.py` and `models/decoder.py` each answer it; the executables
of a GPT are what they were before the question was asked.

Weights are functionalized exactly like jit/engine.py's eval step:
parameter `_data` is swapped for traced inputs during the trace and
restored in `finally`; at dispatch time weights pass as arguments, so
many engines (server workers) can share one loaded model read-only.
Cache buffers are donated — XLA updates the paged KV in place in HBM.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ...framework import state
from ...framework.random import RNG
from ...observability import memprof, metrics, spans, tracing
from . import cache as cache_mod

__all__ = ["GenerationEngine"]

PREFILL_BUCKET_HITS = metrics.counter(
    "pt_serve_prefill_bucket_total",
    "Prefills served per prompt-length bucket", labelnames=("bucket",))

MOE_TOUCHED = metrics.histogram(
    "pt_moe_experts_touched",
    "Experts that got at least one assignment, mean over the expert "
    "layers; one observation a decode step and a prefill",
    buckets=metrics.exponential_buckets(1, 2, 12))
MOE_LOAD = metrics.histogram(
    "pt_moe_load_max_over_mean",
    "The fullest expert's assignments over the mean expert's, mean over "
    "the expert layers; one observation a decode step and a prefill",
    buckets=metrics.exponential_buckets(1, 1.5, 16))
MOE_ASSIGNMENTS = metrics.counter(
    "pt_moe_assignments_total",
    "Token-expert assignments the expert layers computed (padding rows "
    "and idle slots included: the device computes them)")

# Trace-time weight swapping mutates shared Layer state (`p._data`); one
# process-wide lock serializes dispatches so server workers sharing a
# model can never interleave a trace with another engine's dispatch.
_DISPATCH_LOCK = threading.Lock()


class GenerationEngine:
    """Greedy decoding over a static-shape paged KV cache.

    Host API (used by the scheduler):
      prefill(slot, prompt) -> first generated token (admits a request)
      decode() -> np.int32[max_batch], next token for every slot

    Inactive slots keep decoding garbage into their (clamped) tail —
    that is by design: masking slots out would put batch composition
    into the compiled program's shape. The scheduler simply ignores
    tokens from slots it has not admitted.

    `kv_dtype="int8"` swaps the paged cache for the quantized layout
    (~0.53x bf16 bytes at head_dim 64 — see cache.py); `prefix_cache`
    is the shared-prefix store (None disables reuse; byte budget from
    the `prefix_cache_bytes` arg or PADDLE_TPU_PREFIX_CACHE_BYTES).
    After every `prefill()` the engine leaves `admit_info`
    (prefix_len/bucket of THAT admission) for the scheduler's
    `serve_admit` journal event.
    """

    def __init__(self, model, max_batch=4, max_seq_len=128,
                 prefill_buckets=(32, 64, 128), pad_id=0,
                 kv_dtype="float32", prefix_cache_bytes=None):
        import jax
        import jax.numpy as jnp
        from ...jit import compile_cache
        from ...ops.pallas_kernels import pallas_selfcheck
        compile_cache.configure()
        if not callable(getattr(model, "serving", None)):
            raise TypeError(
                "GenerationEngine expects a model that answers serving() "
                "(GPTForPretraining, GPTModel, DecoderLM); got %r"
                % type(model).__name__)
        self._sv = sv = model.serving()
        # the self-checks of the kernels THIS model's steps run
        pallas_selfcheck(needs_prng=False,
                         needs_paged="paged" in sv.selfchecks,
                         needs=sv.selfchecks)
        model.eval()
        self.model = model
        self._n_layers = sv.n_layers
        self._n_heads = sv.kv_heads
        self._head_dim = sv.head_dim
        self._max_pos = sv.max_positions
        self.span_attrs = {
            "moe_layers": sv.moe_layers,
            "window_layers": tuple(sv.layer_kinds).count("window")}

        buckets = sorted(set(int(b) for b in prefill_buckets))
        if not buckets or buckets[0] < 1:
            raise ValueError("prefill_buckets must be positive ints")
        if max_seq_len > self._max_pos:
            raise ValueError(
                "max_seq_len %d exceeds the model's position table (%d)"
                % (max_seq_len, self._max_pos))
        if buckets[-1] > max_seq_len:
            raise ValueError(
                "largest prefill bucket %d exceeds max_seq_len %d"
                % (buckets[-1], max_seq_len))
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.buckets = tuple(buckets)
        self.pad_id = int(pad_id)
        self.bucket_hits = {b: 0 for b in self.buckets}

        from ...jit.engine import _collect_train_state
        params, frozen, buffers, _ = _collect_train_state(model, None)
        self._weights = params + frozen
        self._buffers = buffers
        self._mutable = self._weights + buffers

        self.kv = cache_mod.PagedKVCache(
            self._n_layers, self.max_batch, self._n_heads,
            self.max_seq_len, self._head_dim, kv_dtype=kv_dtype,
            layer_kinds=sv.layer_kinds, window=sv.window)
        self._layer_index = [self.kv.layer_index(i)
                             for i in range(self._n_layers)]
        self._last = jnp.zeros((self.max_batch, 1), jnp.int32)
        # static attend windows for the einsum decode fallback: the
        # prefill buckets + full depth, so short conversations pay for
        # their bucket, not for max_seq_len (models/gpt.py lax.switch)
        self._decode_windows = tuple(sorted(
            set(self.buckets) | {self.max_seq_len}))

        budget = cache_mod.prefix_cache_budget(prefix_cache_bytes) \
            if sv.prefix_cache else 0
        self.prefix_cache = (cache_mod.PrefixCache(budget, self.buckets)
                             if budget > 0 else None)
        self.admit_info = {"prefix_len": 0, "bucket": 0}

        self._traces = {"prefill": 0, "decode": 0, "suffix": 0}
        # instant the previous program's result reached the host; None
        # before the first program and across an idle wait
        self._fetched_ts = None
        self._prefill_tel = tracing.StepTelemetry("serve_prefill")
        self._suffix_tel = tracing.StepTelemetry("serve_suffix")
        self._decode_tel = tracing.StepTelemetry("serve_decode")
        self._jit_prefill = jax.jit(self._prefill_fn, donate_argnums=(3, 4))
        self._jit_decode = jax.jit(self._decode_fn, donate_argnums=(3, 4))
        # one jit object; jax retraces per (prefix_len, suffix bucket)
        # shape pair — counted in _traces["suffix"], never in "prefill"
        self._jit_suffix = jax.jit(self._suffix_fn, donate_argnums=(3, 4))

    # -- cache-state plumbing ----------------------------------------------

    def _carrier(self, cache):
        """The flat state tuple a jitted step received (see
        PagedKVCache.state) as one StackedKV."""
        if self.kv.quantized:
            kc, vc, ksc, vsc, lens = cache
            return cache_mod.StackedKV(kc, vc, lens, ksc, vsc)
        if self.kv.wk is not None:
            kc, vc, wk, wv, lens = cache
            return cache_mod.StackedKV(kc, vc, lens, wk=wk, wv=wv)
        kc, vc, lens = cache
        return cache_mod.StackedKV(kc, vc, lens)

    def _state_of(self, kv, lens):
        if self.kv.quantized:
            return kv.k, kv.v, kv.k_scale, kv.v_scale, lens
        if self.kv.wk is not None:
            return kv.k, kv.v, kv.wk, kv.wv, lens
        return kv.k, kv.v, lens

    def _split_kinds(self, ks, vs, tl):
        """A layer's fresh K/V [1, H, Tb, hd] each -> (full layers
        stacked [L_f, 1, H, Tb, hd] x 2, ring rows of the window layers
        [L_w, 1, H, min(Tb, W), hd] x 2 or None). A prompt longer than
        the window leaves its last W rows, position p at row p mod W."""
        import jax.numpy as jnp
        kinds = self.kv.layer_kinds
        full = [i for i, k in enumerate(kinds) if k == "full"]
        ring = [i for i, k in enumerate(kinds) if k == "window"]
        fk = jnp.stack([ks[i] for i in full])
        fv = jnp.stack([vs[i] for i in full])
        if not ring:
            return fk, fv, None
        wk = jnp.stack([ks[i] for i in ring])
        wv = jnp.stack([vs[i] for i in ring])
        W, tb = self.kv.window, wk.shape[3]
        if tb > W:
            r = jnp.arange(W, dtype=jnp.int32)
            src = jnp.clip(r + W * ((tl - 1 - r) // W), 0, tb - 1)
            wk, wv = jnp.take(wk, src, axis=3), jnp.take(wv, src, axis=3)
        return fk, fv, (wk, wv)

    def _insert_kv(self, cache, ks, vs, tl, slot, offset=0,
                   prefix=None, ring=None):
        """Write freshly-computed float K/V [L,1,nh,T',hd] (quantizing
        first when the cache is int8) into `cache` at (slot, offset),
        optionally preceded by a VERBATIM stored prefix at offset 0,
        and set the slot's length to `tl`. `ring`: the window layers'
        rows (`_split_kinds`), written from row 0 of the slot's rings.
        Runs inside a trace."""
        import jax
        import jax.numpy as jnp
        # the scope travels in the HLO's `op_name` metadata (HLO text,
        # xprof's op profile) whatever XLA fuses the insert into; the
        # fusions' instruction names do not change
        with jax.named_scope("insert_kv"):
            kv = self._carrier(cache)
            kc, vc, ksc, vsc, lens = kv.k, kv.v, kv.k_scale, kv.v_scale, \
                kv.lens
            s, z = slot.astype(jnp.int32), jnp.int32(0)
            o = jnp.int32(offset)
            if self.kv.quantized:
                ks, ks_sc = cache_mod.quantize_kv(ks)
                vs, vs_sc = cache_mod.quantize_kv(vs)
                if prefix is not None:
                    pk, pv, pks, pvs = prefix
                    ksc = jax.lax.dynamic_update_slice(ksc, pks, (z, s, z, z))
                    vsc = jax.lax.dynamic_update_slice(vsc, pvs, (z, s, z, z))
                ksc = jax.lax.dynamic_update_slice(ksc, ks_sc, (z, s, z, o))
                vsc = jax.lax.dynamic_update_slice(vsc, vs_sc, (z, s, z, o))
            elif prefix is not None:
                pk, pv = prefix
            if prefix is not None:
                kc = jax.lax.dynamic_update_slice(
                    kc, pk.astype(kc.dtype), (z, s, z, z, z))
                vc = jax.lax.dynamic_update_slice(
                    vc, pv.astype(vc.dtype), (z, s, z, z, z))
            kc = jax.lax.dynamic_update_slice(
                kc, ks.astype(kc.dtype), (z, s, z, o, z))
            vc = jax.lax.dynamic_update_slice(
                vc, vs.astype(vc.dtype), (z, s, z, o, z))
            lens = jax.lax.dynamic_update_slice(
                lens, jnp.reshape(tl, (1,)), (s,))
            kv.k, kv.v, kv.k_scale, kv.v_scale = kc, vc, ksc, vsc
            if ring is not None:
                kv.wk = jax.lax.dynamic_update_slice(
                    kv.wk, ring[0].astype(kv.wk.dtype), (z, s, z, z, z))
                kv.wv = jax.lax.dynamic_update_slice(
                    kv.wv, ring[1].astype(kv.wv.dtype), (z, s, z, z, z))
            return self._state_of(kv, lens)

    # -- traced bodies ----------------------------------------------------

    def _prefill_fn(self, arrs, buf_arrs, key, cache, last,
                    ids, true_len, slot):
        import jax
        import jax.numpy as jnp
        self._traces["prefill"] += 1
        saved = [m._data for m in self._mutable]
        saved_key = RNG.key
        try:
            for m, a in zip(self._weights, arrs):
                m._data = a
            for b, a in zip(self._buffers, buf_arrs):
                b._data = a
            RNG.key = key
            tl = true_len.astype(jnp.int32)
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                logits, ks, vs, stats = self._sv.prefill(ids, tl)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ks, vs, ring = self._split_kinds(ks, vs, tl)  # [L,1,nh,Tb,hd]
            cache = self._insert_kv(cache, ks, vs, tl, slot, ring=ring)
            s, z = slot.astype(jnp.int32), jnp.int32(0)
            last = jax.lax.dynamic_update_slice(last, tok, (s, z))
            return (cache, last, tok, RNG.key) + self._packed(tok, stats)
        finally:
            for m, a in zip(self._mutable, saved):
                m._data = a
            RNG.key = saved_key

    def _suffix_fn(self, arrs, buf_arrs, key, cache, last, prefix,
                   ids, true_len, slot):
        """Prefix-hit admission: run ONLY the suffix tokens through the
        model, attending over the cached prefix K/V (legacy concat path;
        gpt.py applies the bottom-right causal mask), then insert
        prefix-verbatim + fresh-suffix into the slot. `prefix` is NOT
        donated — it stays resident in the PrefixCache for the next hit.
        prefix_len is static (baked from the prefix arrays' shape), so
        each (prefix bucket, suffix bucket) pair is its own executable.
        """
        import jax
        import jax.numpy as jnp
        self._traces["suffix"] += 1
        p = int(prefix[0].shape[3])
        saved = [m._data for m in self._mutable]
        saved_key = RNG.key
        try:
            for m, a in zip(self._weights, arrs):
                m._data = a
            for b, a in zip(self._buffers, buf_arrs):
                b._data = a
            RNG.key = key
            if self.kv.quantized:
                pk, pv, pks, pvs = prefix
                pkf = cache_mod.dequantize_kv(pk, pks)
                pvf = cache_mod.dequantize_kv(pv, pvs)
            else:
                pk, pv = prefix
                pkf, pvf = pk, pv
            tl = true_len.astype(jnp.int32)
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                # the model runs ONLY the suffix (its true last row sits
                # at total_len - prefix_len - 1) over the stored prefix
                logits, ks, vs, _ = self._sv.prefill(
                    ids, tl - jnp.int32(p), prefix=(pkf, pvf))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # ks/vs are prefix+suffix concats; keep only the fresh suffix —
            # the stored prefix is re-inserted untouched (for int8 that
            # means NO dequantize->requantize round trip on a hit)
            ks = jnp.stack([k[:, :, p:, :] for k in ks])
            vs = jnp.stack([v[:, :, p:, :] for v in vs])
            cache = self._insert_kv(cache, ks, vs, tl, slot,
                                    offset=p, prefix=prefix)
            s, z = slot.astype(jnp.int32), jnp.int32(0)
            last = jax.lax.dynamic_update_slice(last, tok, (s, z))
            return cache, last, tok, RNG.key
        finally:
            for m, a in zip(self._mutable, saved):
                m._data = a
            RNG.key = saved_key

    def _decode_fn(self, arrs, buf_arrs, key, cache, last):
        import jax.numpy as jnp
        self._traces["decode"] += 1
        saved = [m._data for m in self._mutable]
        saved_key = RNG.key
        try:
            for m, a in zip(self._weights, arrs):
                m._data = a
            for b, a in zip(self._buffers, buf_arrs):
                b._data = a
            RNG.key = key
            # one carrier of the stacked arrays; each layer's attention
            # appends its row in place and leaves the updated arrays here
            kv = self._carrier(cache)
            views = [cache_mod.LayerCacheView(
                        kv, i, windows=self._decode_windows, kind=kind)
                     for kind, i in self._layer_index]
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                logits, stats = self._sv.decode(last, views)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lens = jnp.minimum(kv.lens + 1, jnp.int32(self.max_seq_len))
            return (self._state_of(kv, lens), tok, RNG.key) \
                + self._packed(tok, stats)
        finally:
            for m, a in zip(self._mutable, saved):
                m._data = a
            RNG.key = saved_key

    @staticmethod
    def _packed(tok, stats):
        """A step with routing statistics sends them back in the one
        array its tokens come back in: int32 [n_tokens + 2]."""
        import jax.numpy as jnp
        if stats is None:
            return ()
        return (jnp.concatenate([tok.reshape(-1), stats]),)

    def _observe_moe(self, stats, n_rows):
        sv = self._sv
        layers = float(sv.moe_layers)
        MOE_TOUCHED.observe(stats[0] / layers)
        MOE_LOAD.observe((stats[1] / layers)
                         / (n_rows * sv.moe_top_k / float(sv.moe_experts)))
        MOE_ASSIGNMENTS.inc(n_rows * sv.moe_top_k * layers)

    # -- host API ---------------------------------------------------------

    def bucket_for(self, length: int) -> int:
        return cache_mod.bucket_for(length, self.buckets)

    def _suffix_bucket(self, suffix_len: int, prefix_len: int):
        """Smallest bucket holding the suffix such that prefix+bucket
        still fits the cache time axis; None -> fall back to a cold
        prefill (the hit would overflow the slot)."""
        for b in self.buckets:
            if b >= suffix_len and prefix_len + b <= self.max_seq_len:
                return b
        return None

    def prefill(self, slot: int, prompt) -> int:
        """Admit a prompt into `slot`; returns its first generated token.

        Consults the PrefixCache first: on a hit only the suffix runs
        through the model; on a miss the full bucketed prefill runs and
        the prompt's largest bucket-aligned head is stored for the next
        request that shares it. `admit_info` is left describing this
        admission (reused prefix_len + dispatched bucket)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n < 1:
            raise ValueError("empty prompt")
        if not 0 <= slot < self.max_batch:
            raise ValueError("slot %d out of range" % slot)
        reused, entry, sb = 0, None, None
        if self.prefix_cache is not None:
            reused, entry = self.prefix_cache.lookup(prompt)
            if entry is not None:
                sb = self._suffix_bucket(n - reused, reused)
                if sb is None:
                    reused, entry = 0, None
        if entry is not None:
            tok = self._suffix_prefill(slot, prompt, n, reused, entry, sb)
            self.admit_info = {"prefix_len": reused, "bucket": sb}
            return tok
        b = self.bucket_for(n)
        padded = np.full((1, b), self.pad_id, np.int32)
        padded[0, :n] = prompt
        self.bucket_hits[b] += 1
        PREFILL_BUCKET_HITS.labels(str(b)).inc()
        with _DISPATCH_LOCK:
            try:
                with self._prefill_tel.step(("prefill", b)):
                    kvstate, last, tok, key, *packed = self._jit_prefill(
                        [p._data for p in self._weights],
                        [bf._data for bf in self._buffers], RNG.key,
                        self.kv.state(), self._last,
                        padded, np.int32(n), np.int32(slot))
            except Exception as e:
                if memprof.is_oom(e):
                    memprof.on_oom("serve_prefill", e)
                raise
            self._enqueued("host_gap_prefill")
            RNG.key = key
            self.kv.set_state(kvstate)
            self._last = last
            if self.prefix_cache is not None:
                self._store_prefix(prompt, n, slot)
        self.admit_info = {"prefix_len": 0, "bucket": b}
        if packed:
            out = self._fetch(packed[0])
            self._observe_moe(out[1:], b)
            return int(out[0])
        return int(self._fetch(tok)[0, 0])

    def _suffix_prefill(self, slot, prompt, n, p, entry, sb) -> int:
        padded = np.full((1, sb), self.pad_id, np.int32)
        padded[0, :n - p] = prompt[p:]
        self.bucket_hits[sb] += 1
        PREFILL_BUCKET_HITS.labels(str(sb)).inc()
        with _DISPATCH_LOCK:
            try:
                with self._suffix_tel.step(("suffix", p, sb)):
                    kvstate, last, tok, key = self._jit_suffix(
                        [w._data for w in self._weights],
                        [bf._data for bf in self._buffers], RNG.key,
                        self.kv.state(), self._last, entry,
                        padded, np.int32(n), np.int32(slot))
            except Exception as e:
                if memprof.is_oom(e):
                    memprof.on_oom("serve_suffix", e)
                raise
            self._enqueued("host_gap_prefill")
            RNG.key = key
            self.kv.set_state(kvstate)
            self._last = last
        return int(self._fetch(tok)[0, 0])

    def _store_prefix(self, prompt, n: int, slot: int) -> None:
        """Harvest the slot's freshly-prefilled K/V head (largest bucket
        <= prompt length) and admit it to the PrefixCache. The slices
        materialize NEW device buffers, so later donations of the paged
        cache can't invalidate a stored prefix. Called under the
        dispatch lock, right after set_state."""
        p_store = 0
        for b in self.buckets:
            if b <= n:
                p_store = b
        if not p_store:
            return
        s = int(slot)
        arrays = [self.kv.k[:, s:s + 1, :, :p_store, :],
                  self.kv.v[:, s:s + 1, :, :p_store, :]]
        if self.kv.quantized:
            arrays += [self.kv.k_scale[:, s:s + 1, :, :p_store],
                       self.kv.v_scale[:, s:s + 1, :, :p_store]]
        self.prefix_cache.store(prompt[:p_store], arrays)

    def decode(self) -> np.ndarray:
        """One decode step for the whole batch; next token per slot."""
        with _DISPATCH_LOCK:
            try:
                with self._decode_tel.step("decode"):
                    kvstate, tok, key, *packed = self._jit_decode(
                        [p._data for p in self._weights],
                        [bf._data for bf in self._buffers], RNG.key,
                        self.kv.state(), self._last)
            except Exception as e:
                if memprof.is_oom(e):
                    memprof.on_oom("serve_decode", e)
                raise
            self._enqueued("host_gap_decode")
            RNG.key = key
            self.kv.set_state(kvstate)
            self._last = tok
        if packed:
            out = self._fetch(packed[0])
            self._observe_moe(out[self.max_batch:], self.max_batch)
            return out[:self.max_batch]
        return self._fetch(tok).reshape(-1)

    # -- the host's side of the gap between two programs -------------------

    def _enqueued(self, gap: str) -> None:
        """The next program's enqueue has returned: the host's Python
        since the previous program's tokens were fetched is one `gap`
        span. The device waits longer than this: its gap also holds the
        launch after the enqueue and the tokens' way back, which lie
        under `fetch`."""
        t0 = self._fetched_ts
        if t0 is not None:
            spans.record(gap, (time.perf_counter() - t0) * 1e3, t0=t0)

    def _fetch(self, tok) -> np.ndarray:
        """Block for a program's tokens (the `fetch` span, a child of
        `decode_step` or `prefill`); the next host gap starts here."""
        with spans.span("fetch") as sp:
            out = np.asarray(tok)
            self._fetched_ts = now = time.perf_counter()
            sp.close(now)
        return out

    def note_idle(self) -> None:
        """The caller waited for a request: the gap up to the next
        program is not host work and is not recorded."""
        self._fetched_ts = None

    # -- compile-once contract accounting ---------------------------------

    @property
    def prefill_compiles(self) -> int:
        """Actual jax traces of the cold-prefill body (<= n buckets)."""
        return self._traces["prefill"]

    @property
    def suffix_prefill_compiles(self) -> int:
        """Actual jax traces of the suffix body (<= observed
        (prefix, suffix-bucket) pairs; separate from prefill_compiles
        so the prefill<=n_buckets gate stays exact)."""
        return self._traces["suffix"]

    @property
    def decode_compiles(self) -> int:
        """Actual jax traces of the decode body (must stay == 1)."""
        return self._traces["decode"]

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
from ...framework.tensor import Tensor
from ...observability import memprof, metrics, spans, tracing
from . import cache as cache_mod

__all__ = ["GenerationEngine"]

PREFILL_BUCKET_HITS = metrics.counter(
    "pt_serve_prefill_bucket_total",
    "Prefills served per prompt-length bucket", labelnames=("bucket",))

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
        # needs_paged: the decode step runs the paged-decode kernel
        pallas_selfcheck(needs_prng=False, needs_paged=True)

        gpt = getattr(model, "gpt", model)
        if not hasattr(gpt, "layers") or not hasattr(gpt, "embeddings"):
            raise TypeError(
                "GenerationEngine expects a GPTForPretraining (or GPTModel);"
                " got %r" % type(model).__name__)
        model.eval()
        self.model = model
        self._gpt = gpt
        self._n_layers = len(gpt.layers)
        attn = gpt.layers[0].attn
        self._n_heads = attn.num_heads
        self._head_dim = attn.head_dim
        self._hidden = gpt.hidden_size
        self._max_pos = gpt.embeddings.position_embeddings.weight.shape[0]

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
            self.max_seq_len, self._head_dim, kv_dtype=kv_dtype)
        self._last = jnp.zeros((self.max_batch, 1), jnp.int32)
        # static attend windows for the einsum decode fallback: the
        # prefill buckets + full depth, so short conversations pay for
        # their bucket, not for max_seq_len (models/gpt.py lax.switch)
        self._decode_windows = tuple(sorted(
            set(self.buckets) | {self.max_seq_len}))

        budget = cache_mod.prefix_cache_budget(prefix_cache_bytes)
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

    def _split_cache(self, cache):
        """(k, v, k_scale|None, v_scale|None, lens) from the flat state
        tuple a jitted step received (see PagedKVCache.state)."""
        if self.kv.quantized:
            kc, vc, ksc, vsc, lens = cache
            return kc, vc, ksc, vsc, lens
        kc, vc, lens = cache
        return kc, vc, None, None, lens

    def _join_cache(self, kc, vc, ksc, vsc, lens):
        if self.kv.quantized:
            return kc, vc, ksc, vsc, lens
        return kc, vc, lens

    def _insert_kv(self, cache, ks, vs, tl, slot, offset=0,
                   prefix=None):
        """Write freshly-computed float K/V [L,1,nh,T',hd] (quantizing
        first when the cache is int8) into `cache` at (slot, offset),
        optionally preceded by a VERBATIM stored prefix at offset 0,
        and set the slot's length to `tl`. Runs inside a trace."""
        import jax
        import jax.numpy as jnp
        # the scope travels in the HLO's `op_name` metadata (HLO text,
        # xprof's op profile) whatever XLA fuses the insert into; the
        # fusions' instruction names do not change
        with jax.named_scope("insert_kv"):
            kc, vc, ksc, vsc, lens = self._split_cache(cache)
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
            return self._join_cache(kc, vc, ksc, vsc, lens)

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
            gpt = self._gpt
            zero = [(Tensor(jnp.zeros((1, self._n_heads, 0, self._head_dim),
                                      jnp.float32), _internal=True),) * 2
                    for _ in range(self._n_layers)]
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                hidden, kvs = gpt(Tensor(ids, _internal=True), None, zero)
                from ...models.gpt import _lm_logits
                tl = true_len.astype(jnp.int32)
                h_last = jax.lax.dynamic_slice(
                    hidden._data,
                    (jnp.int32(0), tl - 1, jnp.int32(0)),
                    (1, 1, self._hidden))
                logits = _lm_logits(
                    Tensor(h_last, _internal=True),
                    gpt.embeddings.word_embeddings.weight)
            tok = jnp.argmax(logits._data, axis=-1).astype(jnp.int32)
            ks = jnp.stack([c[0]._data for c in kvs])   # [L,1,nh,Tb,hd]
            vs = jnp.stack([c[1]._data for c in kvs])
            cache = self._insert_kv(cache, ks, vs, tl, slot)
            s, z = slot.astype(jnp.int32), jnp.int32(0)
            last = jax.lax.dynamic_update_slice(last, tok, (s, z))
            return cache, last, tok, RNG.key
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
            gpt = self._gpt
            if self.kv.quantized:
                pk, pv, pks, pvs = prefix
                pkf = cache_mod.dequantize_kv(pk, pks)
                pvf = cache_mod.dequantize_kv(pv, pvs)
            else:
                pk, pv = prefix
                pkf, pvf = pk, pv
            legacy = [(Tensor(pkf[i], _internal=True),
                       Tensor(pvf[i], _internal=True))
                      for i in range(self._n_layers)]
            sb = int(ids.shape[1])
            pos = jnp.arange(sb, dtype=jnp.int32) + jnp.int32(p)
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                hidden, kvs = gpt(Tensor(ids, _internal=True),
                                  Tensor(pos, _internal=True), legacy)
                from ...models.gpt import _lm_logits
                tl = true_len.astype(jnp.int32)
                # hidden covers ONLY the suffix: its true last row sits
                # at (total_len - prefix_len) - 1
                h_last = jax.lax.dynamic_slice(
                    hidden._data,
                    (jnp.int32(0), tl - jnp.int32(p) - 1, jnp.int32(0)),
                    (1, 1, self._hidden))
                logits = _lm_logits(
                    Tensor(h_last, _internal=True),
                    gpt.embeddings.word_embeddings.weight)
            tok = jnp.argmax(logits._data, axis=-1).astype(jnp.int32)
            # kvs are prefix+suffix concats; keep only the fresh suffix —
            # the stored prefix is re-inserted untouched (for int8 that
            # means NO dequantize->requantize round trip on a hit)
            ks = jnp.stack([c[0]._data[:, :, p:, :] for c in kvs])
            vs = jnp.stack([c[1]._data[:, :, p:, :] for c in kvs])
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
            gpt = self._gpt
            kc, vc, ksc, vsc, lens = self._split_cache(cache)
            # one carrier of the stacked arrays; each layer's attention
            # appends its row in place and leaves the updated arrays here
            kv = cache_mod.StackedKV(kc, vc, lens, ksc, vsc)
            views = [cache_mod.LayerCacheView(
                        kv, i, windows=self._decode_windows)
                     for i in range(self._n_layers)]
            # new token's absolute position == tokens already resident;
            # clamped so idle slots that hit the wall index a real row
            pos = jnp.minimum(lens, self._max_pos - 1)[:, None]
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                hidden, _ = gpt(Tensor(last, _internal=True),
                                Tensor(pos.astype(jnp.int32),
                                       _internal=True), views)
                from ...models.gpt import _lm_logits
                logits = _lm_logits(
                    hidden, gpt.embeddings.word_embeddings.weight)
            tok = jnp.argmax(logits._data, axis=-1).astype(jnp.int32)
            lens = jnp.minimum(lens + 1, jnp.int32(self.max_seq_len))
            return (self._join_cache(kv.k, kv.v, kv.k_scale, kv.v_scale,
                                     lens), tok, RNG.key)
        finally:
            for m, a in zip(self._mutable, saved):
                m._data = a
            RNG.key = saved_key

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
                    kvstate, last, tok, key = self._jit_prefill(
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
                    kvstate, tok, key = self._jit_decode(
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

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
    `lens` index vector (cache.py), never in shapes, and so does which
    slots hold a request: `lens == 0` is an empty slot, the step's `live`
    input (bool [max_batch]) sends a released slot there, and the step's
    own sum of `lens` comes back with its tokens (`pt_kv_rows_given`).

What the cache holds is `cache.py`'s alone: the engine threads
`PagedKVCache.state()` through its executables as an opaque tuple (its
shapes never change, so decode still compiles exactly once), turns it into a
`StackedKV` carrier inside a trace, and asks the carrier to `insert` a
prompt's K/V and the cache for the `views` a model's layers attend
through (`LayerCacheView.attend`). A cached prefix is re-inserted
VERBATIM (an int8 payload with its original scales), so a prefix hit is
bit-identical to the cold path's cache contents.

All executables are wrapped in `StepTelemetry`
("serve_prefill"/"serve_suffix"/"serve_decode") so
`pt_jit_retraces_total` accounts the compile-once contract, and the
engine additionally counts REAL jax traces (the python body runs once
per trace) in `prefill_compiles`/`suffix_prefill_compiles`/
`decode_compiles` — the numbers the tests assert on, immune to the
telemetry kill-switch.

The engine reads no attribute of any particular model. It calls
`model.serving()` and asks the answer for what it needs:

  n_layers                         the model's layers
  layer_kinds, window              "full" | "window" | "latent" a layer
                                   (cache.py)
  kv_geometry                      {kind: (key-value heads, key size,
                                   value size)}: the two kinds of layer
                                   need agree on none of the three, nor
                                   a key row with a value row; a latent
                                   kind has no heads and no value:
                                   {"latent": (latent size, rotary size)}
  max_positions                    the longest context the model places
  prefix_cache                     whether a stored prompt head can be
                                   re-inserted (not into a ring)
  selfchecks                       the Pallas self-checks its kernels need
  moe_layers, moe_top_k, moe_experts
                                   expert layers; a step of such a model
                                   sends its routing statistics (int32
                                   [2]; [3] where the layers hold a share
                                   of their experts: the assignments that
                                   fell on it) back WITH its tokens
  prefill(ids, true_len[, prefix]) -> (logits [1, 1, V] at the last real
                                   row, [k a layer], [v a layer], stats);
                                   a latent model: [the rows its cache
                                   keeps, a layer] and None
  decode(last, views)              -> (logits [B, 1, V], stats), the
                                   views' carrier left holding the
                                   updated cache

`models/gpt.py` and `models/decoder.py` each answer it; the executables
of a GPT are what they were before the question was asked.

Weights are functionalized exactly like jit/engine.py's eval step:
parameter `_data` is swapped for traced inputs during the trace and
restored in `finally` (`_traced`, the one place); at dispatch time
weights pass as arguments (`_run`, the one place), so many engines
(server workers) can share one loaded model read-only. Cache buffers are
donated — XLA updates the paged KV in place in HBM.

Enqueue and read are two calls. Nothing a later program needs ever comes
back to the host: a decode step's tokens ARE the next step's `last`, a
prefill writes its token into `last` inside its executable, the cache
state and the RNG key thread from program to program. So a caller may
enqueue the next program BEFORE it reads the tokens of the one before,
and the device never waits for the read: `enqueue_decode()` enqueues a
step and returns, `decode()` reads the OLDEST step in flight (and
enqueues one first if none is), `prefill()` enqueues and returns a first
token that blocks only when it is converted (`int(...)`). A caller that
never calls `enqueue_decode()` and converts a first token at once runs
one program at a time, as before the split. **The donation rule:** only
the cache state is donated. `last` is NOT: step n's token array is step
n + 1's `last`, and donated it would be a deleted buffer by the time
step n is read behind step n + 1.
"""
from __future__ import annotations

import collections
import contextlib
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
    "Token-expert assignments the expert layers routed (padding rows "
    "and idle slots included: the device computes them)")
MOE_HERE = metrics.counter(
    "pt_moe_assignments_here_total",
    "Of pt_moe_assignments_total, those that fell on the experts held "
    "here (all of them unless the layers hold a share of their experts)")
MOE_HERE_PCT = metrics.histogram(
    "pt_moe_here_pct",
    "Share of a step's assignments that fell on the experts held here, "
    "in percent; one observation a decode step and a prefill of a model "
    "whose expert layers hold a share of their experts",
    buckets=(1.0, 2.0, 4.0, 6.25, 8.0, 12.5, 25.0, 50.0, 100.0))

AHEAD_PCT = metrics.histogram(
    "pt_serve_ahead_pct",
    "100 where another program was already in flight behind the decode "
    "step whose tokens decode() returns, else 0 (the device then waits "
    "out the read); one observation a decode() return",
    buckets=(0.0, 100.0))
ENQUEUE_IDLE_PCT = metrics.histogram(
    "pt_serve_enqueue_idle_pct",
    "100 where a program's enqueue found the device idle (the program "
    "before it already done), else 0; one observation an enqueue",
    labelnames=("program",), buckets=(0.0, 100.0))
DECODE_DEVICE_MS = metrics.histogram(
    "pt_serve_decode_device_ms",
    "A decode step's own time on the device, milliseconds: the time "
    "between its tokens' read and the step's before it, observed only "
    "where that difference is the step's run (GenerationEngine.decode)",
    buckets=metrics.exponential_buckets(0.01, 2.0, 24))

#: a decode step enqueued at least this long before the step ahead of it
#: ended runs straight behind it on the device (on the v5e a program lands
#: up to ≈ 1 ms after its enqueue call returns: PERF.md §6, PR 36)
LAUNCH_S = 1e-3

# Trace-time weight swapping mutates shared Layer state (`p._data`); one
# process-wide lock serializes dispatches so server workers sharing a
# model can never interleave a trace with another engine's dispatch.
_DISPATCH_LOCK = threading.Lock()


class _FirstToken:
    """A prefill's first token while it is still on its way: `int()`
    blocks for it, once (the `fetch` span and the routing statistics are
    observed there), and gives the same number ever after."""

    __slots__ = ("_read", "_value")

    def __init__(self, read):
        self._read, self._value = read, None

    def __int__(self) -> int:
        if self._read is not None:
            self._value, self._read = self._read(), None
        return self._value


class GenerationEngine:
    """Greedy decoding over a static-shape paged KV cache.

    Host API (used by the scheduler):
      prefill(slot, prompt) -> first generated token (admits a request);
                               enqueued at once, read at `int(...)`
      enqueue_decode(live)  -> None; one more decode step in flight, for
                               the slots `live` names (default: every slot
                               that was prefilled)
      decode() -> np.int32[max_batch], next token for every slot, of the
                  OLDEST step in flight (enqueues one if none is)

    An empty slot is one whose `lens` is 0, and that is the only way it
    is told: batch composition stays out of the compiled program's
    shape. The step computes a token for it like for any other (the
    scheduler ignores it) but sweeps no cache row for it, and its length
    stays 0 until a prefill fills it. The caller says which slots hold a
    request (`enqueue_decode(live)`); a slot it leaves out goes to 0.

    `kv_dtype="int8"` swaps the paged cache for the int8 layout
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
        self._max_pos = sv.max_positions

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

        from ...jit.engine import _collect_train_state
        params, frozen, buffers, _ = _collect_train_state(model, None)
        self._weights = params + frozen
        self._buffers = buffers
        self._mutable = self._weights + buffers

        # what a kind of layer keeps a token is the model's to say and
        # the cache's to lay out
        self.kv = cache_mod.PagedKVCache(
            self._n_layers, self.max_batch, None, self.max_seq_len, None,
            kv_dtype=kv_dtype, layer_kinds=sv.layer_kinds,
            window=sv.window, kv_geometry=sv.kv_geometry)
        self._last = jnp.zeros((self.max_batch, 1), jnp.int32)

        budget = cache_mod.prefix_cache_budget(prefix_cache_bytes) \
            if sv.prefix_cache else 0
        self.prefix_cache = (cache_mod.PrefixCache(budget, self.buckets)
                             if budget > 0 else None)
        self.admit_info = {"prefix_len": 0, "bucket": 0}

        self._traces = {"prefill": 0, "decode": 0, "suffix": 0}
        # decode steps in flight, oldest first: (the array its tokens come
        # back in, `_programs` once it was enqueued)
        self._steps = collections.deque()
        self._programs = 0       # programs this engine has enqueued
        # the live mask of the last decode step: (host copy, device copy)
        self._live = None
        # instant the latest result reached the host, until the next
        # enqueue has taken its gap from it; None before the first
        # program and across an idle wait
        self._fetched_ts = None
        # the probes (telemetry on): whether the latest fetch waited for
        # its program, and of the latest decode step read, (its program
        # index, its read instant, whether its fetch waited)
        self._waited = False
        self._read_prev = None
        self._idle_obs = {k: ENQUEUE_IDLE_PCT.labels(k).observe
                          for k in ("decode", "prefill")}
        self._prefill_tel = tracing.StepTelemetry("serve_prefill")
        self._suffix_tel = tracing.StepTelemetry("serve_suffix")
        self._decode_tel = tracing.StepTelemetry("serve_decode")
        # the cache state is donated, `last` is not (the donation rule)
        self._jit_prefill = jax.jit(self._prefill_fn, donate_argnums=(3,))
        self._jit_decode = jax.jit(self._decode_fn, donate_argnums=(3,))
        # one jit object; jax retraces per (prefix_len, suffix bucket)
        # shape pair — counted in _traces["suffix"], never in "prefill"
        self._jit_suffix = jax.jit(self._suffix_fn, donate_argnums=(3,))

    # -- traced bodies ----------------------------------------------------

    @contextlib.contextmanager
    def _traced(self, arrs, buf_arrs, key):
        """The inside of a trace: the model's weights, its buffers and
        the RNG key are the traced arguments and the three guards are
        entered; what was there comes back on the way out."""
        saved = [m._data for m in self._mutable]
        saved_key = RNG.key
        try:
            for m, a in zip(self._weights, arrs):
                m._data = a
            for b, a in zip(self._buffers, buf_arrs):
                b._data = a
            RNG.key = key
            with state.trace_guard(), state.no_grad_guard(), \
                    state.mesh_guard(None):
                yield
        finally:
            for m, a in zip(self._mutable, saved):
                m._data = a
            RNG.key = saved_key

    def _admitted(self, cache, last, logits, ks, vs, tl, slot, offset=0,
                  prefix=None):
        """The tail of both prefills: the first token, the prompt's K/V
        into the slot (`StackedKV.insert`), the token into `last`."""
        import jax
        import jax.numpy as jnp
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        kv = self.kv.carrier(cache)
        kv.insert(ks, vs, tl, slot, offset=offset, prefix=prefix)
        s, z = slot.astype(jnp.int32), jnp.int32(0)
        last = jax.lax.dynamic_update_slice(last, tok, (s, z))
        return kv.state(), last, tok, RNG.key

    def _prefill_fn(self, arrs, buf_arrs, key, cache, last,
                    ids, true_len, slot):
        import jax.numpy as jnp
        self._traces["prefill"] += 1
        with self._traced(arrs, buf_arrs, key):
            tl = true_len.astype(jnp.int32)
            logits, ks, vs, stats = self._sv.prefill(ids, tl)
            cache, last, tok, rng = self._admitted(
                cache, last, logits, ks, vs, tl, slot)
            return (cache, last, tok, rng) + self._packed(tok, stats)

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
        import jax.numpy as jnp
        self._traces["suffix"] += 1
        p = int(prefix[0].shape[3])
        with self._traced(arrs, buf_arrs, key):
            stored = cache_mod.head_kv(prefix)
            tl = true_len.astype(jnp.int32)
            # the model runs ONLY the suffix (its true last row sits
            # at total_len - prefix_len - 1) over the stored prefix
            logits, ks, vs, _ = self._sv.prefill(
                ids, tl - jnp.int32(p), prefix=stored)
            # ks/vs are prefix+suffix concats; keep only the fresh suffix —
            # the stored prefix is re-inserted untouched (for int8 that
            # means NO dequantize->requantize round trip on a hit)
            return self._admitted(
                cache, last, logits, [k[:, :, p:, :] for k in ks],
                [v[:, :, p:, :] for v in vs], tl, slot, offset=p,
                prefix=prefix)

    def _decode_fn(self, arrs, buf_arrs, key, cache, last, live):
        import jax.numpy as jnp
        self._traces["decode"] += 1
        with self._traced(arrs, buf_arrs, key):
            # one carrier of the stacked arrays; each layer's attention
            # appends its row in place and leaves the updated arrays here
            kv = self.kv.carrier(cache)
            # `lens == 0` IS "this slot holds no request": a slot the
            # caller no longer counts live goes to 0 before the step
            # sweeps anything for it, and only a slot above 0 advances
            kv.lens = jnp.where(live, kv.lens, 0)
            rows = jnp.sum(kv.lens, dtype=jnp.int32).reshape(1)
            logits, stats = self._sv.decode(last, self.kv.views(kv))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lens = jnp.where(
                kv.lens > 0,
                jnp.minimum(kv.lens + 1, jnp.int32(self.max_seq_len)), 0)
            # the rows swept (and the routing statistics) go back in the
            # one array the tokens come back in
            return (kv.state(lens), tok, RNG.key) + self._packed(
                tok, rows if stats is None else jnp.concatenate([rows, stats]))

    @staticmethod
    def _packed(tok, stats):
        """A program with statistics sends them back in the one array its
        tokens come back in: int32 [n_tokens + len(stats)] (a decode
        step's swept rows; an expert model's routing, 2 or 3 numbers)."""
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
        routed = n_rows * sv.moe_top_k * layers
        MOE_ASSIGNMENTS.inc(routed)
        here = float(stats[2]) if len(stats) > 2 else routed
        MOE_HERE.inc(here)
        if len(stats) > 2:
            MOE_HERE_PCT.observe(100.0 * here / routed)

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

    def _run(self, jitted, tel, key, gap, *args):
        """Dispatch one program: the weights, the buffers, the RNG key,
        the cache state and `last` as arguments, then `args`. Under the
        dispatch lock the program is enqueued inside `tel`'s step `key`
        (an out-of-memory error leaves its forensics), the host's `gap`
        span is closed, and the key, the cache state and `last` are the
        program's. Nothing is read. -> the array the program's tokens
        come back in (the packed one for a model with expert layers).

        The enqueue probe (telemetry on): programs run in the order they
        are enqueued, so the previous program's `last` being ready means
        the device has nothing left to run — this enqueue finds it idle
        (`pt_serve_enqueue_idle_pct`)."""
        decode = jitted is self._jit_decode
        with _DISPATCH_LOCK:
            if tracing.enabled():
                self._idle_obs["decode" if decode else "prefill"](
                    100.0 if self._last.is_ready() else 0.0)
            try:
                with tel.step(key):
                    kvstate, *out = jitted(
                        [p._data for p in self._weights],
                        [b._data for b in self._buffers], RNG.key,
                        self.kv.state(), self._last, *args)
            except Exception as e:
                if memprof.is_oom(e):
                    memprof.on_oom(tel.engine, e)
                raise
            self._enqueued(gap)
            self._programs += 1
            if decode:                       # its tokens ARE the next `last`
                tok, RNG.key, *packed = out
                self._last = tok
            else:
                self._last, tok, RNG.key, *packed = out
            self.kv.set_state(kvstate)
        return packed[0] if packed else tok

    def prefill(self, slot: int, prompt) -> _FirstToken:
        """Admit a prompt into `slot`: the program is enqueued and the
        first generated token comes back pending; `int(...)` of it
        blocks for the token (and observes the routing statistics).

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
        reused, entry, b = 0, None, None
        if self.prefix_cache is not None:
            reused, entry = self.prefix_cache.lookup(prompt)
            if entry is not None:
                b = self._suffix_bucket(n - reused, reused)
                if b is None:
                    reused, entry = 0, None
        if entry is None:
            b = self.bucket_for(n)
        padded = np.full((1, b), self.pad_id, np.int32)
        padded[0, :n - reused] = prompt[reused:]
        PREFILL_BUCKET_HITS.labels(str(b)).inc()
        args = (padded, np.int32(n), np.int32(slot))
        if entry is not None:
            arr = self._run(
                self._jit_suffix, self._suffix_tel, ("suffix", reused, b),
                "host_gap_prefill", entry, *args)
        else:
            arr = self._run(
                self._jit_prefill, self._prefill_tel, ("prefill", b),
                "host_gap_prefill", *args)
            if self.prefix_cache is not None:
                self._store_prefix(prompt, n, slot)
        self.admit_info = {"prefix_len": reused, "bucket": b}

        def read() -> int:
            out = self._fetch(arr, parent="prefill").reshape(-1)
            if out.size > 1:            # packed: the token, then the stats
                self._observe_moe(out[1:], b)
            return int(out[0])
        return _FirstToken(read)

    def _store_prefix(self, prompt, n: int, slot: int) -> None:
        """Harvest the slot's freshly-prefilled K/V head (largest bucket
        <= prompt length) and admit it to the PrefixCache: slices on the
        device, in program order behind the prefill; nothing is read."""
        p_store = 0
        for b in self.buckets:
            if b <= n:
                p_store = b
        if p_store:
            self.prefix_cache.store(prompt[:p_store],
                                    self.kv.head(slot, p_store))

    def enqueue_decode(self, live=None) -> None:
        """Enqueue one decode step for the whole batch and return; its
        tokens wait on the device for a later `decode()`. `live`: bool
        [max_batch], the slots that hold a request at this enqueue; the
        step sets every other slot's length to 0 before it attends, and
        there it stays until a prefill fills the slot. Without it every
        slot above 0 counts as live (a slot never prefilled stays at 0).
        The mask goes to the device only when it differs from the last."""
        mask = np.ones(self.max_batch, np.bool_) if live is None \
            else np.asarray(live, np.bool_).reshape(self.max_batch)
        if self._live is None or not np.array_equal(mask, self._live[0]):
            import jax
            self._live = (mask, jax.device_put(mask))
        arr = self._run(self._jit_decode, self._decode_tel, "decode",
                        "host_gap_decode", self._live[1])
        self._steps.append((arr, self._programs, time.perf_counter()))

    def decode(self) -> np.ndarray:
        """The next token per slot of the OLDEST decode step in flight;
        with none in flight, one step is enqueued first."""
        if not self._steps:
            self.enqueue_decode()
        arr, programs, t_enq = self._steps.popleft()
        out = self._fetch(arr).reshape(-1)
        AHEAD_PCT.observe(100.0 if self._programs > programs else 0.0)
        if tracing.enabled():
            self._device_time(programs, t_enq)
        # packed: the tokens, the rows the step swept, then the stats
        cache_mod.KV_ROWS_GIVEN.observe(float(out[self.max_batch]))
        if out.size > self.max_batch + 1:
            self._observe_moe(out[self.max_batch + 1:], self.max_batch)
        return out[:self.max_batch]

    def _device_time(self, programs: int, t_enq: float) -> None:
        """`pt_serve_decode_device_ms` from the fetch probe. A fetch that
        waited reads its tokens a fixed transfer after its program ended;
        so where this step's fetch and the step before's both waited, no
        program ran between the two (consecutive program indices) and
        this step's enqueue returned `LAUNCH_S` or more before the step
        before was read (it was on the device before that one ended, and
        started the instant it did), the two reads' difference is this
        step's run on the device, the transfer cancelling out."""
        prev = self._read_prev
        now = self._fetched_ts
        if (self._waited and prev is not None and prev[2]
                and prev[0] == programs - 1 and prev[1] - t_enq >= LAUNCH_S):
            DECODE_DEVICE_MS.observe((now - prev[1]) * 1e3)
        self._read_prev = (programs, now, self._waited)

    # -- the host's side of the gap between two programs -------------------

    def _enqueued(self, gap: str) -> None:
        """A program's enqueue has returned. If tokens were fetched since
        the enqueue before it, the host's Python from that fetch to here
        is one `gap` span, named by this program. It bounds the device's
        wait only where nothing was in flight behind the tokens fetched
        (`pt_serve_ahead_pct` says how often that is)."""
        t0, self._fetched_ts = self._fetched_ts, None
        if t0 is not None:
            spans.record(gap, (time.perf_counter() - t0) * 1e3, t0=t0)

    def _fetch(self, tok, parent=None) -> np.ndarray:
        """Block for a program's tokens (the `fetch` span, a child of
        `decode_step`, or of the `prefill` a first token names); the next
        host gap starts here."""
        with spans.span("fetch", parent=parent) as sp:
            # the fetch probe: not ready yet means this read waits for
            # the program's end
            self._waited = tracing.enabled() and not tok.is_ready()
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

"""Generation serving engine (paddle_tpu/inference/serving/ —
docs/SERVING.md, ROADMAP item 4).

The three ISSUE 10 contracts:
  * decode parity — the static-cache engine reproduces the legacy
    concat-cache `generate()` token-for-token (greedy, seeded tiny GPT),
    solo and while sharing a batch with other requests;
  * compile-once — across a multi-request run with mixed prompt
    lengths, the decode body traces exactly once and prefill at most
    once per configured bucket (real jax trace counts AND the
    pt_jit_retraces_total registry accounting);
  * mid-flight admission — a request admitted into a half-busy batch
    produces exactly the tokens it would have produced alone.

Compiles dominate this file's runtime, so tests that do not assert
compile counters share ONE module-cached engine (max_batch=4,
max_seq_len=32, buckets (8, 16)) — which doubles as a standing
slot-churn check: every test reuses slots the previous test dirtied.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import gpt_tiny
from paddle_tpu.inference.serving import (ContinuousBatcher,
                                          GenerationEngine,
                                          InferenceServer, PagedKVCache,
                                          Request, bucket_for,
                                          run_open_loop)

VOCAB = 64
_CACHE = {}


def _tiny():
    if "model" not in _CACHE:
        paddle.seed(0)
        m = gpt_tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                     num_heads=4, intermediate_size=64,
                     max_position_embeddings=64)
        m.eval()
        _CACHE["model"] = m
    return _CACHE["model"]


def _shared_engine():
    """One engine (3 executables) reused by every non-counter test."""
    if "engine" not in _CACHE:
        _CACHE["engine"] = GenerationEngine(
            _tiny(), max_batch=4, max_seq_len=32, prefill_buckets=(8, 16))
    return _CACHE["engine"]


def _prompt(rs, n):
    return rs.randint(0, VOCAB, (n,)).astype(np.int64)


def _legacy(model, prompt, max_new):
    """Reference output: the old eager concat-cache loop."""
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=max_new).numpy()[0]
    return out[len(prompt):].tolist()


class TestBuckets:
    def test_bucket_selection_and_overflow(self):
        assert bucket_for(3, (8, 16)) == 8
        assert bucket_for(8, (8, 16)) == 8
        assert bucket_for(9, (8, 16)) == 16
        with pytest.raises(ValueError):
            bucket_for(17, (8, 16))

    def test_engine_validates_shapes(self):
        m = _tiny()
        with pytest.raises(ValueError):
            GenerationEngine(m, max_seq_len=256)        # > position table
        with pytest.raises(ValueError):
            GenerationEngine(m, max_seq_len=16, prefill_buckets=(8, 32))

    def test_scheduler_rejects_oversized_request(self):
        b = ContinuousBatcher(_shared_engine())         # max_seq_len=32
        with pytest.raises(ValueError):
            b.submit(Request(prompt=[1] * 17, max_new_tokens=2))
        with pytest.raises(ValueError):   # prompt + new tokens > max_seq
            b.submit(Request(prompt=[1] * 16, max_new_tokens=17))

    def test_paged_cache_layout(self):
        kv = PagedKVCache(2, 3, 4, 16, 8)
        assert kv.k.shape == (2, 3, 4, 16, 8)
        assert kv.lens.shape == (3,)
        assert kv.nbytes == 2 * (2 * 3 * 4 * 16 * 8) * 4 + 3 * 4


class TestDecodeParity:
    def test_single_request_matches_concat_cache_loop(self):
        m = _tiny()
        rs = np.random.RandomState(0)
        prompt = _prompt(rs, 7)
        want = _legacy(m, prompt, 6)
        b = ContinuousBatcher(_shared_engine())
        req = b.submit(Request(prompt=prompt, max_new_tokens=6))
        b.run_until_idle()
        assert req.tokens == want
        assert req.ttft_s is not None and req.latency_s >= req.ttft_s

    def test_batched_mixed_lengths_each_match_solo(self):
        m = _tiny()
        rs = np.random.RandomState(1)
        specs = [(3, 4), (9, 3), (14, 4)]     # (prompt_len, max_new)
        prompts = [_prompt(rs, n) for n, _ in specs]
        want = [_legacy(m, p, mn) for p, (_, mn) in zip(prompts, specs)]
        b = ContinuousBatcher(_shared_engine())
        reqs = [b.submit(Request(prompt=p, max_new_tokens=mn))
                for p, (_, mn) in zip(prompts, specs)]
        b.run_until_idle()
        for req, w in zip(reqs, want):
            assert req.tokens == w


class TestCompileOnce:
    def test_decode_compiles_once_across_buckets_and_slot_churn(self):
        from paddle_tpu.observability.tracing import RETRACES
        m = _tiny()
        rs = np.random.RandomState(2)
        eng = GenerationEngine(m, max_batch=2, max_seq_len=48,
                               prefill_buckets=(4, 8, 16))
        d0 = RETRACES.labels("serve_decode").value
        b = ContinuousBatcher(eng)
        for n, mn in [(3, 5), (5, 3), (7, 4), (12, 6), (16, 2)]:
            b.submit(Request(prompt=_prompt(rs, n), max_new_tokens=mn))
        b.run_until_idle()
        # real jax traces of the bodies: THE compile-once contract
        assert eng.decode_compiles == 1
        assert eng.prefill_compiles <= len(eng.buckets)
        assert eng.prefill_compiles == 3      # buckets 4, 8 and 16 all hit
        # registry-side accounting agrees (pt_jit_retraces_total)
        assert RETRACES.labels("serve_decode").value - d0 == 1
        assert eng.bucket_hits == {4: 1, 8: 2, 16: 2}
        # three more waves through the now-dirty slots: still no retrace
        for wave in range(3):
            b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=3))
            b.run_until_idle()
        assert eng.decode_compiles == 1
        assert eng.prefill_compiles == 3
        assert RETRACES.labels("serve_decode").value - d0 == 1


class TestMidFlightAdmission:
    def test_late_request_output_unaffected_by_batch_sharing(self):
        m = _tiny()
        rs = np.random.RandomState(4)
        early_p, late_p = _prompt(rs, 6), _prompt(rs, 9)
        want_early = _legacy(m, early_p, 8)
        want_late = _legacy(m, late_p, 4)

        b = ContinuousBatcher(_shared_engine())
        early = b.submit(Request(prompt=early_p, max_new_tokens=8))
        for _ in range(3):            # early is mid-generation...
            b.step()
        assert not early.done
        late = b.submit(Request(prompt=late_p, max_new_tokens=4))
        b.run_until_idle()
        # ...and neither side perturbed the other
        assert late.tokens == want_late
        assert early.tokens == want_early

    def test_admission_waits_for_freed_slot(self):
        eng = _shared_engine()                # 4 slots
        rs = np.random.RandomState(5)
        b = ContinuousBatcher(eng)
        first = [b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=2))
                 for _ in range(eng.max_batch)]
        fifth = b.submit(Request(prompt=_prompt(rs, 6), max_new_tokens=2))
        b.step()                              # batch full: fifth must wait
        assert fifth.slot is None and len(b.pending_requests()) == 1
        b.run_until_idle()                    # a slot frees -> admitted
        assert all(r.done for r in first) and fifth.done
        assert fifth.tokens == _legacy(_tiny(), np.asarray(fifth.prompt), 2)


class TestSchedulerModes:
    def test_static_mode_drains_before_refilling(self):
        rs = np.random.RandomState(6)
        eng = _shared_engine()
        b = ContinuousBatcher(eng, admit_mid_flight=False)
        short = b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=2))
        long = b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=8))
        for _ in range(eng.max_batch - 2):    # fill the first wave
            b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=2))
        third = b.submit(Request(prompt=_prompt(rs, 4), max_new_tokens=2))
        b.step()
        assert short.done is False or short.slot is None
        while not (short.done and long.done):
            b.step()
            # static batching: the overflow request must NOT have started
            # while the first wave was still draining
            if not long.done:
                assert third.ttft_s is None
        b.run_until_idle()
        assert third.done

    def test_open_loop_arrivals_measure_ttft_from_arrival(self):
        rs = np.random.RandomState(7)
        b = ContinuousBatcher(_shared_engine())
        arrivals = [(0.0, Request(prompt=_prompt(rs, 4),
                                  max_new_tokens=3)) for _ in range(3)]
        arrivals += [(0.05, Request(prompt=_prompt(rs, 5),
                                    max_new_tokens=3))]
        done = run_open_loop(b, arrivals)
        assert len(done) == 4
        assert all(r.done and r.ttft_s >= 0 for r in done)
        assert b.occupancy_mean > 0


class TestServer:
    def test_staggered_requests_one_decode_compile_and_error_isolation(self):
        m = _tiny()
        rs = np.random.RandomState(8)
        srv = InferenceServer(m, max_batch=2, max_seq_len=32,
                              prefill_buckets=(8,), workers=1)
        with srv:
            handles = []
            for i in range(4):
                handles.append(srv.submit(_prompt(rs, 3 + i).tolist(),
                                          max_new_tokens=3))
                time.sleep(0.01)
            results = [h.result(timeout=120) for h in handles]
            # an invalid request fails ITS handle, not the serving loop
            bad = srv.submit([1] * 30, max_new_tokens=8)   # over max_seq
            good = srv.submit([1, 2, 3], max_new_tokens=2)
            with pytest.raises(RuntimeError):
                bad.result(timeout=60)
            assert len(good.result(timeout=120)) == 2
        assert all(len(r) == 3 for r in results)
        eng = srv.engines[0]
        assert eng.decode_compiles == 1
        assert eng.prefill_compiles == 1
        # parity through the whole threaded stack
        want = _legacy(m, np.asarray(handles[0].request.prompt), 3)
        assert results[0] == want

    def test_submit_before_start_raises(self):
        srv = InferenceServer(_tiny(), max_batch=1, max_seq_len=16,
                              prefill_buckets=(8,))
        with pytest.raises(RuntimeError):
            srv.submit([1, 2], max_new_tokens=1)


class TestServeMetrics:
    def test_counters_and_journal_events(self, tmp_path):
        from paddle_tpu.observability import read_journal
        from paddle_tpu.observability import journal as journal_mod
        from paddle_tpu.inference.serving import scheduler as sched
        rs = np.random.RandomState(9)
        adm0 = sched.ADMITTED.value
        comp0 = sched.COMPLETED.value
        tok0 = sched.TOKENS.value
        j = journal_mod.RunJournal(str(tmp_path), filename="j.jsonl")
        prev = journal_mod.set_journal(j)
        try:
            b = ContinuousBatcher(_shared_engine())
            for _ in range(2):
                b.submit(Request(prompt=_prompt(rs, 4),
                                 max_new_tokens=3))
            b.run_until_idle()
        finally:
            journal_mod.set_journal(prev)
            j.close()
        assert sched.ADMITTED.value - adm0 == 2
        assert sched.COMPLETED.value - comp0 == 2
        assert sched.TOKENS.value - tok0 == 6
        evs = read_journal(str(tmp_path / "j.jsonl"))
        kinds = [e["event"] for e in evs]
        assert kinds.count("serve_admit") == 2
        assert kinds.count("serve_complete") == 2
        adm = next(e for e in evs if e["event"] == "serve_admit")
        assert adm["prompt_len"] == 4 and adm["bucket"] == 8
        done = next(e for e in evs if e["event"] == "serve_complete")
        assert done["tokens"] == 3 and done["latency_s"] >= 0


class TestPrefixCacheLRU:
    def test_lru_eviction_under_byte_budget(self):
        from paddle_tpu.inference.serving.cache import PrefixCache
        pc = PrefixCache(max_bytes=3 * 64, buckets=(4, 8))

        def arrs(fill):
            return (np.full((4, 4), fill, np.float32),)       # 64 bytes

        assert pc.store([1, 2, 3, 4], arrs(1))
        assert pc.store([5, 6, 7, 8], arrs(2))
        assert pc.store([9, 10, 11, 12], arrs(3))
        assert len(pc) == 3 and pc.bytes == 192
        # touch the oldest entry so the LRU victim is the middle one
        p, entry = pc.lookup([1, 2, 3, 4, 99])
        assert p == 4 and entry is not None
        assert pc.store([13, 14, 15, 16], arrs(4))            # forces evict
        assert len(pc) == 3 and pc.evictions == 1
        assert pc.lookup([5, 6, 7, 8, 99])[1] is None         # evicted
        assert pc.lookup([1, 2, 3, 4, 99])[1] is not None     # kept (hot)
        # an entry bigger than the whole budget is refused outright
        assert not pc.store([40, 41, 42, 43],
                            (np.zeros((100, 100), np.float32),))
        assert len(pc) == 3

    def test_proper_prefix_only_and_bucket_alignment(self):
        from paddle_tpu.inference.serving.cache import PrefixCache
        pc = PrefixCache(max_bytes=1 << 20, buckets=(4, 8))
        pc.store([1, 2, 3, 4], (np.zeros((2, 2), np.float32),))
        # p < n strictly: a prompt that IS the stored prefix cannot hit
        # (there would be no suffix token left to produce TTFT from)
        assert pc.lookup([1, 2, 3, 4]) == (0, None)
        # shares 3 tokens then diverges before the bucket boundary: the
        # 4-token key differs, so alignment makes this a miss
        assert pc.lookup([1, 2, 3, 9, 5])[1] is None
        assert pc.lookup([1, 2, 3, 4, 5])[1] is not None


class TestCacheState:
    def test_state_roundtrip_and_dtype_mismatch(self):
        kv = PagedKVCache(2, 2, 2, 8, 4)
        st = kv.state()
        assert len(st) == 3
        kv.set_state(st)                      # single-tuple form
        kv.set_state(*st)                     # splatted form
        kv8 = PagedKVCache(2, 2, 2, 8, 4, kv_dtype="int8")
        st8 = kv8.state()
        assert len(st8) == 5                  # scales travel with values
        kv8.set_state(st8)
        # int8 payload (2x256) + f32 scales (2x256) + int32 lens (8)
        assert kv8.nbytes == 512 + 512 + 8
        with pytest.raises(ValueError):       # arity: float state into q
            kv8.set_state(st)
        with pytest.raises(ValueError):       # dtype: int8 arrays into f32
            kv.set_state(st8[0], st8[1], st[2])


    @pytest.mark.parametrize("kind,fields", [
        ("float", ("k", "v", "lens")),
        ("int8", ("k", "v", "k_scale", "v_scale", "lens")),
        ("ring", ("k", "v", "wk", "wv", "lens"))])
    def test_carrier_round_trips_a_state_of_each_kind(self, kind, fields):
        """`carrier(state).state(lens)` is the state with the new lengths,
        array for array in the one order the jitted steps thread; a state
        of another kind's arity, or of another type, is refused as
        `set_state` refuses it."""
        mk = {"float": lambda: PagedKVCache(2, 2, 2, 8, 4),
              "int8": lambda: PagedKVCache(2, 2, 2, 8, 4, kv_dtype="int8"),
              "ring": lambda: PagedKVCache(
                  2, 2, 2, 8, 4, layer_kinds=("window", "full"), window=4)}
        kv = mk[kind]()
        st = kv.state()
        carrier = kv.carrier(st)
        assert [getattr(carrier, f) for f in fields] == list(st)
        assert all(a is b for a, b in zip(carrier.state(), st))
        lens = st[-1] + 1
        moved = carrier.state(lens)
        assert moved[-1] is lens and carrier.lens is lens
        assert all(a is b for a, b in zip(moved[:-1], st[:-1]))
        assert [v.kind for v in kv.views(carrier)] == list(kv.layer_kinds)
        kv.set_state(moved)
        assert kv.lens is lens
        other = mk["int8" if kind == "float" else "float"]().state()
        for bad in (other, moved[:-1],
                    (moved[0].astype("float16"),) + moved[1:]):
            with pytest.raises(ValueError):
                kv.carrier(bad)
            with pytest.raises(ValueError):
                kv.set_state(bad)


def _prefix_engine():
    """One cached reuse-enabled engine for every TestPrefixReuse test —
    tier-1 wall time is compile-bound, so tests assert counter DELTAS
    against a shared executable set instead of building fresh engines.
    Distinct per-test random seeds keep the stored prefixes disjoint."""
    if "prefix_engine" not in _CACHE:
        _CACHE["prefix_engine"] = GenerationEngine(
            _tiny(), max_batch=2, max_seq_len=32, prefill_buckets=(8, 16),
            prefix_cache_bytes=32 << 20)
    return _CACHE["prefix_engine"]


class TestPrefixReuse:
    def test_hit_parity_vs_cold_prefill_solo(self):
        m = _tiny()
        rs = np.random.RandomState(11)
        head = _prompt(rs, 8)                 # shared "system prompt"
        cold = np.concatenate([head, _prompt(rs, 4)])
        hot = np.concatenate([head, _prompt(rs, 3)])
        eng = _prefix_engine()
        hits0 = eng.prefix_cache.hits
        b = ContinuousBatcher(eng)
        b.submit(Request(prompt=cold, max_new_tokens=5))
        b.run_until_idle()                    # stores the 8-token prefix
        assert eng.prefix_cache.hits == hits0
        r = b.submit(Request(prompt=hot, max_new_tokens=5))
        b.run_until_idle()
        assert eng.prefix_cache.hits == hits0 + 1 and r.prefix_len == 8
        assert r.tokens == _legacy(m, hot, 5)  # reuse is invisible in tokens
        assert eng.decode_compiles == 1

    def test_hit_parity_mid_flight(self):
        m = _tiny()
        rs = np.random.RandomState(12)
        head = _prompt(rs, 8)
        warm = np.concatenate([head, _prompt(rs, 5)])
        other = _prompt(rs, 6)
        hit_p = np.concatenate([head, _prompt(rs, 2)])
        want_other = _legacy(m, other, 8)
        want_hit = _legacy(m, hit_p, 4)
        eng = _prefix_engine()
        b = ContinuousBatcher(eng)
        b.submit(Request(prompt=warm, max_new_tokens=2))
        b.run_until_idle()                    # seed the prefix cache
        other_r = b.submit(Request(prompt=other, max_new_tokens=8))
        for _ in range(3):                    # other is mid-generation...
            b.step()
        assert not other_r.done
        hit_r = b.submit(Request(prompt=hit_p, max_new_tokens=4))
        b.run_until_idle()
        # ...the prefix-hit admission neither perturbed the running
        # request nor its own output
        assert hit_r.prefix_len == 8 and hit_r.tokens == want_hit
        assert other_r.prefix_len == 0 and other_r.tokens == want_other
        # every hit so far landed on the ONE (prefix=8, suffix=8) pair
        assert eng.suffix_prefill_compiles == 1
        assert eng.decode_compiles == 1

    def test_bucket_misaligned_prompt_misses(self):
        rs = np.random.RandomState(13)
        cold = _prompt(rs, 12)
        eng = _prefix_engine()
        hits0, suffix0 = eng.prefix_cache.hits, eng.suffix_prefill_compiles
        b = ContinuousBatcher(eng)
        b.submit(Request(prompt=cold, max_new_tokens=2))
        b.run_until_idle()
        # diverges at index 7, before the 8-token bucket boundary
        div = cold.copy()
        div[7] = (div[7] + 1) % VOCAB
        r = b.submit(Request(prompt=div, max_new_tokens=2))
        b.run_until_idle()
        assert r.prefix_len == 0 and eng.prefix_cache.hits == hits0
        # a prompt exactly equal to the stored prefix must also miss
        # (p < n strictly — the suffix pass yields the first token)
        r2 = b.submit(Request(prompt=cold[:8], max_new_tokens=2))
        b.run_until_idle()
        assert r2.prefix_len == 0 and eng.prefix_cache.hits == hits0
        assert eng.suffix_prefill_compiles == suffix0


class TestInt8KV:
    def _model96(self):
        # head_dim 64 (the serving-bench geometry): int8's worst-case
        # rounding error shrinks with 1/sqrt(head_dim), and at hd=8 the
        # tiny model's logit gaps are close enough for argmax to flip —
        # the parity CONTRACT is stated for production head dims
        if "model96" not in _CACHE:
            paddle.seed(0)
            m = gpt_tiny(vocab_size=VOCAB, hidden_size=128, num_layers=2,
                         num_heads=2, intermediate_size=256,
                         max_position_embeddings=96)
            m.eval()
            _CACHE["model96"] = m
        return _CACHE["model96"]

    def _int8_engine(self):
        # shared by both tests (compile cost): prefix cache ON — it is
        # numerically invisible on the cold path, so parity still holds
        if "int8_engine" not in _CACHE:
            _CACHE["int8_engine"] = GenerationEngine(
                self._model96(), max_batch=1, max_seq_len=80,
                prefill_buckets=(8, 16), kv_dtype="int8",
                prefix_cache_bytes=32 << 20)
        return _CACHE["int8_engine"]

    def test_greedy_parity_64_tokens_vs_float_cache(self):
        m = self._model96()
        rs = np.random.RandomState(14)
        prompt = _prompt(rs, 8)
        toks = {}
        for dt in ("float32", "int8"):
            if dt == "int8":
                eng = self._int8_engine()
            else:
                eng = GenerationEngine(m, max_batch=1, max_seq_len=80,
                                       prefill_buckets=(8,), kv_dtype=dt,
                                       prefix_cache_bytes=0)
            b = ContinuousBatcher(eng)
            r = b.submit(Request(prompt=prompt, max_new_tokens=64))
            b.run_until_idle()
            toks[dt] = list(r.tokens)
            assert eng.decode_compiles == 1   # int8 mustn't cost retraces
            if dt == "int8":
                assert eng.kv.quantized
                q_bytes = eng.kv.nbytes
            else:
                f_bytes = eng.kv.nbytes
        # the ISSUE accuracy contract: >= 64 greedy tokens, token parity
        assert len(toks["int8"]) == 64
        assert toks["int8"] == toks["float32"]
        assert q_bytes < f_bytes              # int8+scales beat f32

    def test_int8_prefix_hit_parity(self):
        rs = np.random.RandomState(15)
        hot = _prompt(rs, 11)                 # head = hot[:8] (bucket 8)
        eng = self._int8_engine()
        hits0 = eng.prefix_cache.hits
        b = ContinuousBatcher(eng)
        # first admission is cold and stores the 8-token head; the SAME
        # prompt resubmitted then hits — the verbatim re-insert (int8
        # payload + original scales, no requantization) makes the hit
        # bit-identical to the cold path, so tokens must match exactly
        r_cold = b.submit(Request(prompt=hot, max_new_tokens=6))
        b.run_until_idle()
        r_hit = b.submit(Request(prompt=hot, max_new_tokens=6))
        b.run_until_idle()
        assert r_cold.prefix_len == 0
        assert r_hit.prefix_len == 8
        assert eng.prefix_cache.hits == hits0 + 1
        assert r_hit.tokens == r_cold.tokens
        assert eng.decode_compiles == 1


# ---- the loop runs one decode step ahead ------------------------------

#: (prompt length, max_new_tokens) in submit order; `EOS_AT` names the
#: request that gets an `eos_id` and stops early. Twelve requests churn
#: through three or four slots: `max_new_tokens` 1 and 2, long and short
CHURN = [(5, 1), (7, 2), (4, 6), (9, 2), (3, 1), (6, 9), (8, 3), (5, 2),
         (4, 12), (7, 4), (6, 1), (5, 5)]
EOS_AT = 8


def depth0_tokens(eng, prompt, max_new, eos_id=None):
    """One request alone, a program at a time — `prefill()` converted at
    once, `decode()` with no `enqueue_decode()` before it: the engine as
    it was before enqueue and read were split."""
    toks = [int(eng.prefill(0, prompt))]
    while len(toks) < max_new and toks[-1] != eos_id:
        toks.append(int(eng.decode()[0]))
    return toks


def churned_requests(eng, vocab, seed=21):
    """(requests, the depth-0 tokens of each) of the churned schedule on
    `eng`; the `EOS_AT`-th request stops at the first token, from its
    third on, that it had not given before."""
    rs = np.random.RandomState(seed)
    reqs, want = [], []
    for i, (n, max_new) in enumerate(CHURN):
        prompt = rs.randint(1, vocab, (n,)).astype(np.int64)
        toks, eos = depth0_tokens(eng, prompt, max_new), None
        if i == EOS_AT:
            stop = next(k for k in range(2, max_new - 2)
                        if toks[k] not in toks[:k])
            eos, toks = toks[stop], toks[:stop + 1]
            assert depth0_tokens(eng, prompt, max_new, eos) == toks
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new,
                            eos_id=eos))
        want.append(toks)
    return reqs, want


def run_ahead_matches_depth0(eng, vocab, admit_mid_flight=True):
    """The run-ahead batcher gives every request of the churned schedule
    the tokens the same engine gives it at depth 0; -> the batcher and
    how many prefills refilled a slot whose last owner's tokens were
    still in flight."""
    reqs, want = churned_requests(eng, vocab)
    compiles = eng.decode_compiles
    b = ContinuousBatcher(eng, admit_mid_flight=admit_mid_flight)
    enqueued, refills = [], []
    enqueue_decode, prefill = eng.enqueue_decode, eng.prefill

    def counted_enqueue(live):
        # no step for a batch whose every request is complete by count:
        # someone's tokens read or in flight (the first token's prefill,
        # the steps that carry the request) fall short of what it asked
        def owed(r):
            coming = sum(any(q is r for _, q in f.pairs)
                         if hasattr(f, "pairs") else f.req is r
                         for f in b._flight)
            return r.max_new_tokens - len(r.tokens) - coming
        assert any(owed(r) > 0 for r in b.pending_requests()
                   if r.slot is not None)
        enqueued.append(b.steps)
        assert list(live) == [r is not None for r in b.slots]
        return enqueue_decode(live)

    def watched_prefill(slot, prompt):
        refills.append(any(r.slot == slot and not r.done
                           for r in b.pending_requests()))
        return prefill(slot, prompt)

    eng.enqueue_decode, eng.prefill = counted_enqueue, watched_prefill
    try:
        for r in reqs:
            b.submit(r)
        done = b.run_until_idle()
    finally:
        del eng.enqueue_decode, eng.prefill
    assert len(done) == len(reqs) and b.idle and not b.pending_requests()
    for i, (r, w) in enumerate(zip(reqs, want)):
        assert r.tokens == w, i          # a dropped token never got here
        assert len(r.token_ts) == len(w) and r.outcome == "completed"
    assert len(reqs[EOS_AT].tokens) < reqs[EOS_AT].max_new_tokens
    assert eng.decode_compiles == max(compiles, 1) == 1
    # every step enqueued was read, none is left in flight
    assert len(enqueued) == b.steps and not eng._steps
    return b, sum(refills)


class TestRunAhead:
    @pytest.mark.parametrize("mode", ["continuous", "static"])
    def test_churned_schedule_token_for_token_with_depth_0(self, mode):
        eng = _shared_engine()
        b, refills = run_ahead_matches_depth0(
            eng, VOCAB, admit_mid_flight=(mode == "continuous"))
        if mode == "continuous":
            # a prefill went into a slot released by count, behind the
            # step that carries its last owner's last token
            assert refills >= 3
            assert b.occupancy_mean > 0.6
        else:
            assert refills == 0

    def test_churned_schedule_with_an_int8_cache(self):
        eng = GenerationEngine(_tiny(), max_batch=3, max_seq_len=32,
                               prefill_buckets=(8, 16), kv_dtype="int8")
        run_ahead_matches_depth0(eng, VOCAB)

    def test_a_step_is_read_after_the_step_behind_it_was_enqueued(self):
        """Step n's token array is step n + 1's `last`: donated, reading
        it behind step n + 1 would raise a deleted-buffer error."""
        eng = _shared_engine()
        rs = np.random.RandomState(22)
        prompt = _prompt(rs, 6)
        want = depth0_tokens(eng, prompt, 6)
        first = eng.prefill(0, prompt)       # pending: nothing read yet
        for _ in range(3):
            eng.enqueue_decode()
        got = [int(first), int(first)]       # read once, the same twice
        got += [int(eng.decode()[0]) for _ in range(3)]
        eng.enqueue_decode()
        eng.enqueue_decode()
        got += [int(eng.decode()[0]), int(eng.decode()[0])]
        assert got[1:] == want and not eng._steps
        assert eng.decode_compiles == 1

    @pytest.mark.parametrize("max_new,steps", [(1, 0), (2, 1), (5, 4)])
    def test_no_step_is_enqueued_that_no_request_needs(self, max_new,
                                                       steps):
        eng = _shared_engine()
        b = ContinuousBatcher(eng)
        rs = np.random.RandomState(23)
        r = b.submit(Request(prompt=_prompt(rs, 5), max_new_tokens=max_new))
        n = []
        enqueue_decode = eng.enqueue_decode
        eng.enqueue_decode = lambda live: (n.append(1),
                                           enqueue_decode(live))[1]
        try:
            b.step()
            # by count, all it needs (up to two steps) is in flight or read
            assert len(n) == min(steps, 2)
            b.run_until_idle()
        finally:
            del eng.enqueue_decode
        assert len(n) == b.steps == steps and len(r.tokens) == max_new

    def test_turn_takes_the_two_reads_in_two_calls(self):
        """The serving loop's iteration: a cold start enqueues ONE step
        behind its prefill and reads the first token; a request that came
        meanwhile is admitted before the second step is enqueued, and gets
        its second token from that step."""
        eng = _shared_engine()
        rs = np.random.RandomState(26)
        pa, pl = _prompt(rs, 5), _prompt(rs, 7)
        want_a, want_l = depth0_tokens(eng, pa, 4), depth0_tokens(eng, pl, 3)
        b = ContinuousBatcher(eng)
        a = b.submit(Request(prompt=pa, max_new_tokens=4))
        b.turn()
        assert a.tokens == want_a[:1] and b.steps == 0
        assert [type(f).__name__ for f in b._flight] == ["_Step"]
        late = b.submit(Request(prompt=pl, max_new_tokens=3))
        b.turn()                   # late's prefill, the second step; D1 read
        assert a.tokens == want_a[:2] and b.steps == 1 and not late.tokens
        first, second = b._flight
        assert first.req is late
        assert [q is a or q is late for _, q in second.pairs] == [True, True]
        b.turn()                   # late's first token: a call of its own
        assert late.tokens == want_l[:1] and b.steps == 1
        while not b.idle:
            b.turn()
        assert a.tokens == want_a and late.tokens == want_l

    def test_the_loop_may_hold_the_top_up_for_half_a_running_step(self):
        """`hold_s()`: while ONE step is in flight, a slot is free and no
        one waits, the serving loop may wait for an arrival until half of
        the running step's measured time is over; the measure is the time
        between the reads of two steps enqueued back to back."""
        from paddle_tpu.inference.serving import VirtualClock
        eng, clk = _shared_engine(), VirtualClock(start=50.0)
        enqueue_decode, decode = eng.enqueue_decode, eng.decode
        free, ends = [clk()], []

        def enqueue_8ms(live):   # the device on this clock: 8 ms a step,
            free[0] = max(free[0], clk()) + 0.008    # one after another
            ends.append(free[0])
            return enqueue_decode(live)

        def decode_when_done():
            clk.now = max(clk(), ends.pop(0))
            return decode()
        eng.enqueue_decode, eng.decode = enqueue_8ms, decode_when_done
        try:
            b = ContinuousBatcher(eng, clock=clk)
            rs = np.random.RandomState(25)
            r = b.submit(Request(prompt=_prompt(rs, 5), max_new_tokens=12))
            assert b.hold_s() == 0.0     # nothing measured, nothing flying
            b.step()                     # P, D1, D2 enqueued; P, D1 read
            assert b.hold_s() == 0.0     # D2's run is not measured yet
            b.step()                     # D3 behind D2; D2 read: 8 ms
            assert b._period == pytest.approx(0.008)
            assert b.hold_s() == pytest.approx(0.004)
            clk.advance(0.003)
            assert b.hold_s() == pytest.approx(0.001)
            late = b.submit(Request(prompt=_prompt(rs, 4),
                                    max_new_tokens=2))
            assert b.hold_s() == 0.0     # someone waits: admit, top up
            b.step()                     # late's prefill, D4; D3 read
            assert b.hold_s() == 0.0     # a prefill is in flight
            b.step()                     # D5 behind D4; D4 read
            assert b._period == pytest.approx(0.008)
            # held too long (the estimate was off, the host was late): the
            # next step is enqueued as the finished one is read, and the
            # time between the two reads is still one step's run
            assert b.hold_s() == pytest.approx(0.004)
            clk.advance(0.02)
            assert b.hold_s() == 0.0     # the running step is past half
            b.step()                     # D6 enqueued late; D5 read
            b.step()                     # D6 read, 8 ms after its enqueue
            assert b._period == pytest.approx(0.008)
            b.run_until_idle()
            assert b.hold_s() == 0.0
            assert len(r.tokens) == 12 and len(late.tokens) == 2
            # every slot taken: an arrival could not be admitted anyway
            full = [b.submit(Request(prompt=_prompt(rs, 4),
                                     max_new_tokens=9))
                    for _ in range(eng.max_batch)]
            b.step()
            b.step()
            assert None not in b.slots and b.hold_s() == 0.0
            b.run_until_idle()
            assert all(len(q.tokens) == 9 for q in full)
            # static batching admits nothing mid-flight: it never holds
            s = ContinuousBatcher(eng, clock=clk, admit_mid_flight=False)
            s.submit(Request(prompt=_prompt(rs, 5), max_new_tokens=8))
            s.step()
            s.step()
            assert s._period is not None and s.hold_s() == 0.0
            s.run_until_idle()
        finally:
            del eng.enqueue_decode, eng.decode

    def test_a_failing_loop_answers_requests_whose_tokens_are_in_flight(
            self):
        """`pending_requests()` (what `_fail_pending` walks) counts a
        request released by count until its tokens have come."""
        eng = _shared_engine()
        b = ContinuousBatcher(eng)
        rs = np.random.RandomState(24)
        r = b.submit(Request(prompt=_prompt(rs, 5), max_new_tokens=3))
        b.step()                 # P, D1, D2 enqueued; P and D1 read
        assert b.slots == [None] * eng.max_batch and len(r.tokens) == 2
        assert b.pending_requests() == [r] and b.active == 1
        assert not b.idle
        b.run_until_idle()
        assert r.done and b.idle and b.active == 0


# ---- an empty slot costs the decode step nothing ----------------------


class ParentRuleEngine(GenerationEngine):
    """The decode step as it was before `lens == 0` meant "empty": every
    slot advances by one a step, whoever holds it, and no one is told
    which slots are live."""

    def _decode_fn(self, arrs, buf_arrs, key, cache, last, live):
        import jax.numpy as jnp
        from paddle_tpu.framework.random import RNG
        self._traces["decode"] += 1
        with self._traced(arrs, buf_arrs, key):
            kv = self.kv.carrier(cache)
            rows = jnp.sum(kv.lens, dtype=jnp.int32).reshape(1)
            logits, stats = self._sv.decode(last, self.kv.views(kv))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lens = jnp.minimum(kv.lens + 1, jnp.int32(self.max_seq_len))
            return (kv.state(lens), tok, RNG.key) + self._packed(
                tok, rows if stats is None else jnp.concatenate([rows, stats]))


def interleaved_tokens(eng, vocab, masked=True):
    """Four slots driven by hand: slots 0 and 2 hold a request, 1 and 3
    stay empty; a request arrives in slot 3 mid-flight; slot 0 is
    released and, three steps on, refilled. -> (the tokens of each of the
    four requests, `lens` on the device after every step). `masked`
    False tells the engine nothing (the parent rule's engine is told
    nothing either way)."""
    rs = np.random.RandomState(31)
    prompts = [rs.randint(1, vocab, (n,)).astype(np.int64)
               for n in (6, 9, 4, 7)]
    toks, lens = {}, []

    def steps(n, live):
        for _ in range(n):
            if masked:
                eng.enqueue_decode([s in live for s in range(4)])
            out = eng.decode()
            lens.append(np.asarray(eng.kv.lens).tolist())
            for s, name in live.items():
                toks[name].append(int(out[s]))

    steps(1, {})                   # no slot was ever filled: nothing to do
    toks["a"] = [int(eng.prefill(0, prompts[0]))]
    toks["b"] = [int(eng.prefill(2, prompts[1]))]
    steps(3, {0: "a", 2: "b"})
    toks["c"] = [int(eng.prefill(3, prompts[2]))]     # arrives mid-flight
    steps(3, {0: "a", 2: "b", 3: "c"})
    steps(3, {2: "b", 3: "c"})                        # "a" is released
    toks["d"] = [int(eng.prefill(0, prompts[3]))]     # its slot refilled
    steps(4, {0: "d", 2: "b", 3: "c"})
    return toks, lens


def live_tokens_are_the_parent_rules(make_engine, vocab):
    """`make_engine(cls)` -> a four-slot engine of class `cls`: the live
    requests' greedy tokens under the rule "an empty slot is `lens == 0`
    and stays there" are the parent rule's, token for token, and the
    device's lengths are the live requests' and 0 for everyone else."""
    want, climbing = interleaved_tokens(make_engine(ParentRuleEngine), vocab)
    eng = make_engine(GenerationEngine)
    got, lens = interleaved_tokens(eng, vocab)
    assert got == want and eng.decode_compiles == 1
    assert [len(t) for t in got.values()] == [7, 14, 11, 5]
    # nobody's slot stays at 0; under the parent's rule it climbs
    assert lens[0] == [0, 0, 0, 0] and climbing[0] == [1, 1, 1, 1]
    assert lens[3] == [9, 0, 12, 0] and climbing[3] == [9, 4, 12, 4]
    assert lens[6] == [12, 0, 15, 7]
    assert lens[7] == [0, 0, 16, 8] and lens[9] == [0, 0, 18, 10]
    assert lens[-1] == [11, 0, 22, 14] and climbing[-1][1] == 14
    return eng


def enqueues_of(eng, b, run, log=None):
    """Run `run()` with every decode step the batcher `b` enqueues on
    `eng` written down (into `log`, for a caller that reads it while it
    grows): [(the live mask it was given, {slot: (request, the steps
    enqueued for it before this one)}, `lens` on the device after the
    step)]."""
    log = [] if log is None else log
    enqueue_decode = eng.enqueue_decode

    def logged(live):
        held = {s: (r, r.max_new_tokens - 1 - b._left[s])
                for s, r in enumerate(b.slots) if r is not None}
        enqueue_decode(live)
        log.append((list(live), held, np.asarray(eng.kv.lens).tolist()))
    eng.enqueue_decode = logged
    try:
        run()
    finally:
        del eng.enqueue_decode
    # what holds whoever is released or admitted: a slot's length is its
    # request's rows, this step's included, and 0 where no request is
    for live, held, lens in log:
        assert live == [s in held for s in range(eng.max_batch)]
        for s in range(eng.max_batch):
            r, before = held.get(s, (None, 0))
            assert lens[s] == (len(r.prompt) + before + 1 if r else 0)
    return log


def eos_request(eng, rs, max_new=10):
    """A request that its eos stops early (-> it, its depth-0 tokens)."""
    while True:
        prompt = _prompt(rs, 5)
        toks = depth0_tokens(eng, prompt, max_new)
        for k in range(2, max_new - 3):
            if toks[k] not in toks[:k]:
                return Request(prompt=prompt, max_new_tokens=max_new,
                               eos_id=toks[k]), toks[:k + 1]


class TestEmptySlots:
    @pytest.mark.parametrize("released", ["by_count", "by_eos", "unused"])
    def test_a_slot_without_a_request_reads_0_from_the_next_enqueue_on(
            self, released):
        """Slot 1's request is released while slot 0's keeps the loop
        stepping: the first step ENQUEUED after the release leaves
        `lens[1]` at 0 on the device, and so does every later one; a slot
        nobody was admitted to reads 0 all through."""
        eng, rs = _shared_engine(), np.random.RandomState(32)
        b = ContinuousBatcher(eng)
        long = Request(prompt=_prompt(rs, 6), max_new_tokens=14)
        if released == "by_eos":
            short, want = eos_request(eng, rs)
        else:
            short, want = Request(prompt=_prompt(rs, 5), max_new_tokens=3), \
                None
        b.submit(long)
        b.submit(short)
        log = enqueues_of(eng, b, b.run_until_idle)
        assert len(log) == 13 and len(long.tokens) == 14
        if released == "unused":
            assert all(lens[2:] == [0, 0] for _, _, lens in log)
            return
        if want is not None:
            assert short.tokens == want
        held = [1 in h for _, h, _ in log]
        last = max(i for i, x in enumerate(held) if x)
        assert held[:last + 1] == [True] * (last + 1)
        # by count it leaves with the step that carries its last token;
        # its eos is learned a step late, so one more step carried it
        assert last + 1 == len(short.tokens) - (released == "by_count")
        assert log[last][2][1] == 5 + last + 1
        after = [lens[1] for _, _, lens in log[last + 1:]]
        assert len(after) >= 6 and set(after) == {0}

    @pytest.mark.parametrize("released", ["by_count", "by_eos"])
    def test_a_slot_refilled_in_the_turn_it_was_released_keeps_its_new_rows(
            self, released):
        """No decode step lies between the release of slot 0 and the
        prefill that refills it: the slot's length goes from the old
        request's to the new prompt's, the next step's mask has it live,
        and the new request's tokens are the ones it gets alone."""
        eng, rs = _shared_engine(), np.random.RandomState(33)
        if released == "by_eos":
            old, _ = eos_request(eng, rs)
        else:
            old = Request(prompt=_prompt(rs, 5), max_new_tokens=2)
        prompt = _prompt(rs, 9)
        want = depth0_tokens(eng, prompt, 6)
        new = Request(prompt=prompt, max_new_tokens=6)
        b = ContinuousBatcher(eng)
        prefills, prefill = [], eng.prefill

        def run():
            b.submit(old)
            while old.slot is not None or not old.tokens:
                b.step()
                if b.slots[0] is None and not new.submit_ts:
                    b.submit(new)         # waits when the slot falls free
            b.run_until_idle()
        eng.prefill = lambda slot, p: (prefills.append((slot, len(log))),
                                       prefill(slot, p))[1]
        log = []
        try:
            enqueues_of(eng, b, run, log)
        finally:
            del eng.prefill
        assert new.tokens == want and len(old.tokens) >= 2
        assert [slot for slot, _ in prefills] == [0, 0]
        refill = prefills[1][1]        # steps enqueued before the refill
        assert log[refill - 1][0][0] and log[refill - 1][2][0] > 0
        assert log[refill][0][0] and log[refill][2][0] == 9 + 1
        assert log[-1][2][0] == 9 + 5

    def test_one_decode_executable_whatever_the_mask(self):
        """Masks come and go, the executable is one; a mask equal to the
        last one is not sent to the device again."""
        eng = GenerationEngine(_tiny(), max_batch=4, max_seq_len=32,
                               prefill_buckets=(8,))
        rs = np.random.RandomState(34)
        for s in range(4):
            int(eng.prefill(s, _prompt(rs, 3 + s)))
        sent = []
        for live in ([1, 1, 1, 1], None, [1, 0, 1, 1], [1, 0, 1, 1],
                     [0, 0, 1, 1], None, [0, 0, 0, 0], [0, 0, 0, 0]):
            eng.enqueue_decode(live)
            sent.append(eng._live[1])
            eng.decode()
        assert eng.decode_compiles == 1
        # `None` is "every slot that holds rows": all four, then the two
        # still above 0 — the same mask of ones both times
        assert sent[0] is sent[1] and sent[2] is sent[3]
        assert sent[6] is sent[7] and sent[4] is not sent[3]
        assert np.asarray(eng.kv.lens).tolist() == [0, 0, 0, 0]
        int(eng.prefill(1, _prompt(rs, 5)))
        assert eng.decode().shape == (4,)
        assert np.asarray(eng.kv.lens).tolist() == [0, 6, 0, 0]
        assert eng.decode_compiles == 1

    @pytest.mark.parametrize("kv_dtype,kernel", [
        ("float32", False), ("float32", True), ("int8", False),
        ("int8", True)])
    def test_live_tokens_are_the_parent_rules(self, kv_dtype, kernel):
        from paddle_tpu.framework.flags import set_flags
        set_flags({"FLAGS_paged_flash_interpret": kernel})
        try:
            live_tokens_are_the_parent_rules(
                lambda cls: cls(_tiny(), max_batch=4, max_seq_len=32,
                                prefill_buckets=(8, 16), kv_dtype=kv_dtype,
                                prefix_cache_bytes=0), VOCAB)
        finally:
            set_flags({"FLAGS_paged_flash_interpret": False})

    def test_rows_given_are_the_live_requests_rows(self):
        """`pt_kv_rows_given` (the device's sum of `lens` at a step's
        start) against `pt_kv_rows_live{kind=full}` (the scheduler's count
        at the harvest, the step's own row included): one observation a
        step each, one row a live slot apart."""
        from paddle_tpu.inference.serving import cache as cache_mod
        eng, rs = _shared_engine(), np.random.RandomState(35)
        given, live = cache_mod.KV_ROWS_GIVEN, \
            cache_mod.KV_ROWS_LIVE.labels("full")
        g0, l0 = (given.sum, given.count), (live.sum, live.count)
        b = ContinuousBatcher(eng)
        for n, max_new in CHURN:
            b.submit(Request(prompt=_prompt(rs, n), max_new_tokens=max_new))
        b.run_until_idle()
        assert given.count - g0[1] == live.count - l0[1] == b.steps > 10
        assert (live.sum - l0[0]) - (given.sum - g0[0]) == b.live_slot_steps
        assert given.sum - g0[0] > 5 * b.live_slot_steps


class TestPredictorPoolSharing:
    def test_pool_members_share_program_and_executables(self, tmp_path):
        import paddle_tpu.inference as infer
        from paddle_tpu import nn, static
        paddle.enable_static()
        static.reset_default_programs()
        try:
            paddle.seed(0)
            x = static.data("x", [-1, 4], "float32")
            y = nn.Linear(4, 2)(x)
            exe = static.Executor()
            exe.run(static.default_startup_program())
            prefix = str(tmp_path / "m")
            static.save_inference_model(prefix, [x], [y], exe)
        finally:
            paddle.disable_static()
        pool = infer.PredictorPool(infer.Config(prefix), size=3)
        a, b, c = (pool.retrieve(i) for i in range(3))
        # one model load: captured weights + program shared by identity
        assert a._captures is b._captures is c._captures
        assert a._program is b._program is c._program
        # one compile serves the whole pool
        arr = np.ones((2, 4), np.float32)
        out_a = a.run([arr])[0].numpy()
        assert len(a._exec_cache) == 1
        out_b = b.run([arr])[0].numpy()
        assert b._exec_cache is a._exec_cache
        assert len(a._exec_cache) == 1     # member b hit a's executable
        np.testing.assert_allclose(out_a, out_b)
        # per-member feed/result state stays private
        assert a._feeds is not b._feeds and a._results is not b._results

"""The checks every decoder family of `models/decoder.py` passes against its
plain float32 reference (`benchmarks/perf/reference_<family>.py`) on seeded
weights, written once; pytest does not collect this module.

A family's test file adds itself with

    weights, net = family_fixtures(ref, CFG, program)

    class TestConformance(DecoderConformance):
        ref, CFG, build = ref, CFG, staticmethod(program)
        ...

and pytest collects every check below in that file as
`test_<family>.py::TestConformance::test_...`. The subclass sets
`KERNEL_CFG` (what a case with the Pallas kernels in interpret mode changes
of the config), `STEP_STATS` (a decode step's routing statistics),
`SERVED_FAULTS` (the reference's faults that must show in the served
tokens' gaps), `ASSIGNMENTS`, `HELD`, `KV_BYTES` and `LIVE_ROWS` (the
counters after one 11-token prompt and one step), `CELL` (the benchmark
cell's cache: arguments, array shapes, bytes), `SHARES` and `SHARE_TOL`
(shares of an expert layer); and the hooks below where its cache or its
experts differ. It gets for free: the logits of the forward pass; prefill
then teacher-forced decoding at every position, through the einsum and the
kernels; the server path's greedy tokens and executables; the run-ahead
loop against depth 0; where a prefill leaves its rows; the counters of a
served model; the cell's cache bytes; shares that add up to the layer.

The teacher-forced loops and every `net.run` go through `jax.jit`, closing
over the tiny net: op by op, each primitive of each layer compiles by
itself, and these cases spent most of their time compiling.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.inference.serving import cache as cache_mod
from paddle_tpu.inference.serving import engine as engine_mod
from paddle_tpu.inference.serving.cache import PagedKVCache
from paddle_tpu.inference.serving.engine import GenerationEngine
from paddle_tpu.models import decoder as dec
from paddle_tpu.ops import pallas_kernels as pk

# float32 on both sides and the same function of the weights: the program
# and the reference differ in the order of summation alone (a grouped
# product against a masked loop, fused norms; a latent family's absorbed
# path in where the up-projections are multiplied in). Logits have a
# standard deviation of 0.16: 2e-5 is a hundred times the 2e-7 to 5e-7 read
# on the sound programs and a thousandth of what bfloat16 would give
# (0.16 * 2^-8 * sqrt(depth)).
TOL = 2e-5
FLAG_DEFAULTS = {"FLAGS_paged_flash_interpret": False,
                 "FLAGS_use_flash_attention": True}


@contextlib.contextmanager
def kernel_flags(on):
    """The Pallas kernels in interpret mode (on), or the einsum with the
    flash path off, for the body; the defaults after it."""
    set_flags(dict.fromkeys(FLAG_DEFAULTS, on))
    try:
        yield
    finally:
        set_flags(FLAG_DEFAULTS)


def family_fixtures(ref, cfg, build):
    """A family module's `weights` (the reference's, seeded, float32) and
    `net` (`build(cfg, weights)`), shared by all its tests."""
    @pytest.fixture(scope="module")
    def weights():
        return ref.make_weights(cfg, 5, "float32")

    @pytest.fixture(scope="module")
    def net(weights):
        return build(cfg, weights)
    return weights, net


@pytest.fixture
def interpret():
    """The decode kernels in interpret mode (a family module imports this
    fixture for its own kernel tests)."""
    set_flags({"FLAGS_paged_flash_interpret": True})
    yield
    set_flags({"FLAGS_paged_flash_interpret": False})


def engine(net, **kw):
    kw = dict(dict(max_batch=3, max_seq_len=64, prefill_buckets=(8, 16, 32),
                   kv_dtype="float32"), **kw)
    return GenerationEngine(net, **kw)


def randn(*shape):
    return jnp.asarray(np.random.RandomState(sum(shape)).randn(*shape),
                       jnp.float32)


class DecoderConformance:
    KERNEL_CFG = {}
    STEP_STATS = (2,)
    SERVED_FAULTS = ()
    HELD = False
    SHARES = ()

    def pytest_generate_tests(self, metafunc):
        if "held" in metafunc.fixturenames:
            metafunc.parametrize("E,held,k,d,f", self.SHARES)

    # -- the family's hooks ------------------------------------------------

    def check_rows(self, ks, vs, b):
        """What `net.run` returned a layer for the cache, at a bucket of b."""

    def check_prefill_rows(self, kv, ks, vs, n, b):
        """`kv` after slot 1's prefill of n tokens holds them as `ks`/`vs`
        (the same prompt's `net.run` at bucket b) say: a window layer its
        last W rows, position p at row p mod W, a full layer every row; a
        kind in `kv.k_cols` keeps K by column."""
        W = kv.window
        arrays = {"full": (np.asarray(kv.k), np.asarray(kv.v)),
                  "window": (np.asarray(kv.wk), np.asarray(kv.wv))}
        for layer in range(len(ks)):
            kind, i = kv.layer_index(layer)
            k, v = arrays[kind]
            for p in range(max(0, n - W) if kind == "window" else 0, n):
                row = p % W if kind == "window" else p
                got = k[i, 1, ..., row] if kind in kv.k_cols \
                    else k[i, 1, :, row]
                assert np.allclose(got, ks[layer][0, :, p], atol=2e-5)
                assert np.allclose(v[i, 1, :, row], vs[layer][0, :, p],
                                   atol=2e-5)
        assert not arrays["window"][0][:, 0].any()  # other slots untouched

    def check_paths(self, paths, with_kernels):
        """The attention paths the server's traces took (see the check)."""

    def check_small_caches(self):
        raise NotImplementedError

    def expert_layer(self, weights, E, k, d, f):
        """-> (reference dims, leaves, tokens, `MoEConfig` arguments before
        `experts_held`, the shared expert's output or 0.0)."""
        raise NotImplementedError

    def check_routed_part(self, m, lw, x, routed, whole):
        """The routed parts of all the shares, summed, beside the whole."""

    # -- what the checks share ---------------------------------------------

    @pytest.fixture(scope="class")
    def programs(self, net, weights):
        """(cfg, program) a case without the kernels and one with them."""
        cfg = dict(self.CFG, **self.KERNEL_CFG)
        return {False: (self.CFG, net),
                True: (cfg, self.build(cfg, weights) if self.KERNEL_CFG
                       else net)}

    @pytest.fixture(scope="class")
    def run(self):
        """run(net, ids) -> `net.run(ids)` through one `jax.jit` a program
        and a setting of the kernel flags (a trace reads them)."""
        jits = {}

        def run(net, ids):
            key = (id(net), tuple(get_flags(list(FLAG_DEFAULTS)).values()))
            if key not in jits:
                jits[key] = jax.jit(lambda x: net.run(x))
            return jits[key](ids)
        return run

    @pytest.fixture(scope="class")
    def served_gaps(self, weights):
        """gaps(cfg, seqs, n_prompt, fault) -> `ref.served_gaps`, with the
        reference's pass over a (config, sequence, fault) made once for
        the class: the sound pass every call over the same tokens makes,
        and the kernel and einsum cases that serve the same tokens."""
        memo, logits = {}, self.ref.logits

        def once(cfg, w, ids, quant=None, fault=None, rows=None):
            key = (repr(sorted(cfg.items())), np.asarray(ids).tobytes(),
                   quant, fault, rows)
            if key not in memo:
                memo[key] = logits(cfg, w, ids, quant, fault, rows)
            return memo[key]

        def gaps(cfg, seqs, n_prompt, fault=None):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(self.ref, "logits", once)
                return self.ref.served_gaps(
                    cfg, weights, [np.asarray(s) for s in seqs], n_prompt,
                    fault=fault)
        return gaps

    # -- (a) the whole forward pass ----------------------------------------

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_logits_match_the_reference(self, net, weights, run, seed):
        ids = self.ref.tokens(seed, 2, 40, self.CFG["vocab_size"])
        got = np.asarray(run(net, jnp.asarray(ids, jnp.int32))[0])
        for row in range(2):
            want = np.asarray(self.ref.logits(self.CFG, weights, ids[row]))
            assert np.abs(got[row] - want).max() < TOL

    # -- (b) prefill, then decoding through the cache ----------------------

    @pytest.mark.parametrize("with_kernels", [False, True])
    def test_prefill_then_decode_gives_the_reference_logits_at_every_position(
            self, programs, weights, run, with_kernels):
        """Three slots of different lengths in one batch, teacher-forced
        along fixed sequences of 4-5 windows, so every ring wraps: the
        logits of the prompt (prefill) and of every decoded position are
        the reference's full forward pass over the same sequence — through
        the einsum, and through the decode and band kernels in interpret
        mode. The decode step is one jitted function: carrier -> `net.step`
        -> the next cache state."""
        cfg, net = programs[with_kernels]
        with kernel_flags(with_kernels):
            e = engine(net)
            kv = e.kv

            @jax.jit
            def step(cache, last):
                carrier = kv.carrier(cache)
                logits, stats = net.step(last, kv.views(carrier))
                return logits, stats, carrier.state(carrier.lens + 1)

            seqs = self.ref.tokens(11, 3, 40, self.CFG["vocab_size"])
            n_prompt = [27, 6, 17]
            want = [np.asarray(self.ref.logits(cfg, weights, s))
                    for s in seqs]
            cache = kv.state()
            for slot, n in enumerate(n_prompt):
                b = e.bucket_for(n)
                ids = np.zeros((1, b), np.int32)
                ids[0, :n] = seqs[slot, :n]
                logits, ks, vs, _ = run(net, jnp.asarray(ids))
                self.check_rows(ks, vs, b)
                assert np.abs(np.asarray(logits)[0, :n]
                              - want[slot][:n]).max() < TOL
                carrier = kv.carrier(cache)
                carrier.insert(ks, vs, jnp.int32(n), jnp.int32(slot))
                cache = carrier.state()
            for t in range(40 - max(n_prompt)):
                last = jnp.asarray([[seqs[s, n + t]] for s, n in
                                    enumerate(n_prompt)], jnp.int32)
                logits, stats, cache = step(cache, last)
                assert stats.shape == self.STEP_STATS
                for s, n in enumerate(n_prompt):
                    assert np.abs(np.asarray(logits)[s, 0]
                                  - want[s][n + t]).max() < TOL, (s, t)

    @pytest.mark.parametrize("with_kernels", [False, True])
    def test_the_server_path_decodes_the_reference_greedy_tokens(
            self, programs, served_gaps, with_kernels):
        """Through `GenerationEngine.prefill` / `.decode` (jitted, donated):
        greedy tokens equal the reference's argmax along the served
        sequence; ONE decode executable, one prefill executable a bucket;
        with the Pallas kernels in interpret mode the same tokens (the band
        kernel takes the emulator's small shapes by itself, so the einsum
        case turns the flash path off)."""
        cfg, net = programs[with_kernels]
        paths0 = dict(pk.attention_path_counts())
        with kernel_flags(with_kernels):
            e = engine(net, max_seq_len=64, prefill_buckets=(16, 32))
            prompts = self.ref.tokens(7, 3, 30, self.CFG["vocab_size"])
            n_prompt = [30, 5, 19]
            seqs = [list(prompts[s, :n]) + [int(e.prefill(s, prompts[s, :n]))]
                    for s, n in enumerate(n_prompt)]
            for _ in range(14):
                toks = e.decode()
                for s in range(3):
                    seqs[s].append(int(toks[s]))
        assert e.decode_compiles == 1 and e.prefill_compiles == 2
        self.check_paths({k: v - paths0.get(k, 0) for k, v in
                          pk.attention_path_counts().items()}, with_kernels)
        assert max(served_gaps(cfg, seqs, n_prompt)) == 0.0
        for fault in self.SERVED_FAULTS:
            assert max(served_gaps(cfg, seqs, n_prompt, fault)) > 0.0, fault

    @pytest.mark.parametrize("with_kernels", [False, True])
    def test_the_run_ahead_loop_gives_the_depth_0_tokens_through_the_cache(
            self, programs, with_kernels):
        """The churned schedule of tests/test_serving.py on the family:
        contexts of up to 21 rows wrap the rings of 8, the tokens come back
        packed with the routing statistics, slots are refilled behind the
        step that carries their last owner's last token."""
        from test_serving import run_ahead_matches_depth0
        with kernel_flags(with_kernels):
            b, refills = run_ahead_matches_depth0(
                engine(programs[with_kernels][1]), self.CFG["vocab_size"])
        assert refills >= 3 and b.steps > 0

    @pytest.fixture(scope="class")
    def prefill_engine(self, net):
        """One engine for the cases below, its executables compiled once a
        bucket; each case starts from an empty cache."""
        return engine(net)

    @pytest.mark.parametrize("n", [5, 8, 19, 27, 32])
    def test_a_prefill_leaves_its_rows_where_the_cache_keeps_them(
            self, net, run, prefill_engine, n):
        e = prefill_engine
        e.kv.set_state(tuple(jnp.zeros_like(a) for a in e.kv.state()))
        ids = self.ref.tokens(n, 1, n, self.CFG["vocab_size"])[0]
        e.prefill(1, ids)
        b = e.bucket_for(n)
        padded = np.zeros((1, b), np.int32)
        padded[0, :n] = ids
        _, ks, vs, _ = run(net, jnp.asarray(padded))
        assert list(np.asarray(e.kv.lens)) == [0, n, 0]
        self.check_rows(ks, vs, b)
        self.check_prefill_rows(e.kv, ks, vs, n, b)

    def test_counters_of_a_served_model(self, net):
        e = engine(net)
        n0, h0 = engine_mod.MOE_ASSIGNMENTS.value, engine_mod.MOE_HERE.value
        c0 = engine_mod.MOE_HERE_PCT.count
        int(e.prefill(0, np.arange(1, 12)))    # observed where it is read
        e.decode()
        routed = self.ASSIGNMENTS
        assert engine_mod.MOE_ASSIGNMENTS.value - n0 == routed
        if self.HELD:                 # a share: what of it fell here
            assert 0 < engine_mod.MOE_HERE.value - h0 < routed
            assert engine_mod.MOE_HERE_PCT.count - c0 == 2
            assert 0.0 < engine_mod.MOE_HERE_PCT.mean < 100.0
        else:
            assert 1 <= engine_mod.MOE_TOUCHED.mean \
                <= self.CFG["num_experts"]
            assert engine_mod.MOE_LOAD.mean >= 1.0
        # bytes and rows by kind, each kind's own geometry
        by_kind = e.kv.nbytes_by_kind()
        assert by_kind == self.KV_BYTES
        for kind, nbytes in by_kind.items():
            assert cache_mod.KV_BYTES.labels(kind).value == nbytes
        assert e.kv.nbytes == sum(by_kind.values()) + 3 * 4
        rows0 = {kind: cache_mod.KV_ROWS_LIVE.labels(kind).sum
                 for kind in self.LIVE_ROWS}
        e.kv.observe_live_rows([3, 40, 100])
        for kind, rows in self.LIVE_ROWS.items():
            assert cache_mod.KV_ROWS_LIVE.labels(kind).sum - rows0[kind] \
                == rows, kind

    # -- (c) the cache of the cell -----------------------------------------

    def test_cache_bytes_of_the_cell(self):
        """The benchmark cell's cache, shaped and not allocated: its arrays
        and the bytes of its rows (`CELL`); then the family's small caches."""
        args, kw, shapes, nbytes = self.CELL
        state = jax.eval_shape(lambda: PagedKVCache(*args, **kw).state())
        assert [a.shape for a in state[:len(shapes)]] == shapes
        assert sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in state if a.ndim > 1) == nbytes
        self.check_small_caches()

    # -- (d) the share of a deployment -------------------------------------

    def test_the_shares_of_the_experts_add_up_to_the_whole_layer(
            self, weights, E, held, k, d, f):
        """What the holders of `held` experts each compute of one layer —
        every one of them the shared expert too, where the family has one,
        which a deployment's combine counts ONCE — adds up to the uncut
        reference's whole layer; the reference given the same share
        computes the same part; the held counts add up to every
        assignment."""
        m, lw, x, moe, shared = self.expert_layer(weights, E, k, d, f)
        part_tol, whole_tol = self.SHARE_TOL
        whole = np.asarray(self.ref.moe(m, lw, x))
        routed, assigned = np.zeros_like(whole), 0
        for first in range(0, E, held):
            mc = dec.MoEConfig(*moe, experts_held=(first, held))
            part = dict(lw, **{n: lw[n][first:first + held]
                               for n in ("e_gate", "e_up", "e_down")})
            got, sizes = dec.moe_layer(mc, part, x)   # routed part + shared
            assert sizes.shape == (held,)
            assigned += int(np.asarray(sizes).sum())
            want = self.ref.moe(m, lw, x, experts_held=(first, held))
            assert np.abs(np.asarray(got) - np.asarray(want)).max() \
                < part_tol
            routed += np.asarray(got) - shared
        assert assigned == x.shape[0] * k            # no assignment dropped
        assert np.abs(routed + shared - whole).max() < whole_tol
        self.check_routed_part(m, lw, x, routed, whole)

"""Flash-attention revival tier (PR 6): CPU-safe parity + diagnostics.

Everything here runs the Pallas kernels in interpret mode (the emulator
executes the SAME kernel bodies Mosaic compiles on TPU, minus the
compiler), so tier-1 exercises the flash fwd/bwd math and the block
autotuner's cache plumbing without a TPU in the loop (the TPU self-checks
are pinned by tests/test_pallas_health.py). Complements tests/test_pallas_fused.py (which covers
the fused-dropout/LN chain and sdpa routing): this file is the parity
matrix — causal x dtype, ragged/odd lengths, multi-block grids, dropout
vs a dense oracle — plus the PR-6 diagnostics surface.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.pallas_kernels import (
    _block_candidates,
    _flash,
    _xla_attention,
    attention_path_counts,
    attention_path_totals,
    flash_block_sizes,
)


def _qkv(B, H, Tq, Tk, D, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, H, Tq, D), dtype)
    k = jnp.asarray(rs.randn(B, H, Tk, D), dtype)
    v = jnp.asarray(rs.randn(B, H, Tk, D), dtype)
    return q, k, v


def _run_flash(q, k, v, causal, block_q=None, block_k=None):
    bq = block_q or min(128, q.shape[2])
    bk = block_k or min(128, k.shape[2])
    return _flash(q, k, v, None, causal, True, 0.0, bq, bk)


class TestFlashParityMatrix:
    """Forward + full vjp vs the dense XLA oracle, interpret mode."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5),
                                           (jnp.bfloat16, 5e-2)])
    def test_fwd_bwd_parity(self, causal, dtype, tol):
        q, k, v = _qkv(1, 2, 48, 48, 32, dtype)

        out, f_vjp = jax.vjp(lambda q, k, v: _run_flash(q, k, v, causal),
                             q, k, v)
        want, o_vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, causal), q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        g = jnp.ones_like(out)
        for got, exp in zip(f_vjp(g), o_vjp(g)):
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(exp, np.float32),
                                       atol=10 * tol, rtol=10 * tol)

    @pytest.mark.parametrize("Tq,Tk,causal", [
        (40, 56, False),   # odd lengths, neither a lane multiple
        (16, 48, True),    # ragged causal: bottom-right aligned band
        (40, 40, True),    # odd square causal
    ])
    def test_odd_and_ragged_lengths(self, Tq, Tk, causal):
        q, k, v = _qkv(1, 1, Tq, Tk, 16, seed=3)
        out, f_vjp = jax.vjp(lambda q, k, v: _run_flash(q, k, v, causal),
                             q, k, v)
        want, o_vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, causal), q, k, v)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
        g = jnp.ones_like(out)
        for got, exp in zip(f_vjp(g), o_vjp(g)):
            np.testing.assert_allclose(got, exp, atol=2e-4, rtol=2e-4)

    def test_multiblock_grid_matches_single_block(self):
        """block 16 on T=48 runs 3x3 grid programs — must agree with the
        single-block answer exactly (same math, different tiling)."""
        q, k, v = _qkv(2, 2, 48, 48, 16, seed=5)
        one = _run_flash(q, k, v, True)
        multi = _run_flash(q, k, v, True, block_q=16, block_k=16)
        np.testing.assert_allclose(multi, one, atol=2e-6, rtol=2e-6)


class TestFlashDropoutParity:
    """Interpret-mode dropout takes a host-side uint32 bits slab; the
    dense oracle below applies the identical keep/scale rule."""

    def _oracle(self, q, k, v, bits, p, causal):
        B, H, Tq, D = q.shape
        Tk = k.shape[2]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / float(np.sqrt(D))
        if causal:
            mask = (jnp.arange(Tk)[None, :]
                    <= jnp.arange(Tq)[:, None] + (Tk - Tq))
            s = jnp.where(mask, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        thr = jnp.uint32(min(int(p * 2 ** 32), 2 ** 32 - 1))
        keep = bits.reshape(B, H, Tq, Tk) >= thr
        wd = jnp.where(keep, w / (1.0 - p), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", wd, v)

    @pytest.mark.parametrize("p", [0.0, 0.25])
    def test_dropout_fwd_bwd_vs_oracle(self, p):
        q, k, v = _qkv(1, 2, 32, 32, 16, seed=7)
        B, H, Tq, _ = q.shape
        Tk = k.shape[2]
        bits = jax.random.bits(jax.random.PRNGKey(11), (B * H, Tq, Tk),
                               jnp.uint32)
        rng = bits if p > 0.0 else None

        def run(q, k, v):
            return _flash(q, k, v, rng, True, True, p, 32, 32)

        out, f_vjp = jax.vjp(run, q, k, v)
        want, o_vjp = jax.vjp(
            lambda q, k, v: self._oracle(q, k, v, bits, p, True)
            if p > 0.0 else _xla_attention(q, k, v, True), q, k, v)
        np.testing.assert_allclose(out, want, atol=5e-5, rtol=5e-5)
        g = jnp.ones_like(out)
        for got, exp in zip(f_vjp(g), o_vjp(g)):
            assert np.isfinite(np.asarray(got)).all()
            np.testing.assert_allclose(got, exp, atol=3e-4, rtol=3e-4)


class TestBlockAutotune:
    def test_block_candidates(self):
        assert _block_candidates(512) == [128, 256, 512]
        assert _block_candidates(256) == [128, 256]
        assert _block_candidates(384) == [128]   # 384 % 256 != 0
        assert _block_candidates(128) == [128]
        assert _block_candidates(100) == [100]   # no legal sweep value
        assert _block_candidates(64) == [64]

    def test_defaults_off_tpu_without_sweeping(self, monkeypatch):
        monkeypatch.setattr(pk, "_sweep_flash_blocks",
                            lambda *a: pytest.fail("swept off-TPU"))
        assert flash_block_sizes(4, 256, 256, 64, jnp.float32, True) == \
            (128, 128)
        assert flash_block_sizes(4, 64, 96, 64, jnp.float32, False) == \
            (64, 96)

    def _fake_tpu(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setitem(pk.flag.__globals__["_FLAGS"],
                            "flash_autotune_blocks", True)

    def test_flag_defaults_off_and_pins_128(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(pk, "_sweep_flash_blocks",
                            lambda *a: pytest.fail("swept with flag off"))
        assert flash_block_sizes(8, 512, 512, 64, jnp.float32, True) == \
            (128, 128)

    def test_sweep_cached_in_process_and_persisted(self, monkeypatch,
                                                   tmp_path):
        self._fake_tpu(monkeypatch)
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
        monkeypatch.setattr(pk, "_AUTOTUNE_CACHE", {})
        monkeypatch.setattr(pk, "_AUTOTUNE_FILE_LOADED", True)
        calls = []
        monkeypatch.setattr(
            pk, "_sweep_flash_blocks",
            lambda *a: (calls.append(a) or ((256, 128),
                                            {"256x128": 1.0})))
        events = []
        from paddle_tpu.observability import journal
        monkeypatch.setattr(
            journal, "emit",
            lambda event, **kw: events.append((event, kw)) or True)

        got = flash_block_sizes(8, 512, 512, 64, jnp.float32, True)
        assert got == (256, 128) and len(calls) == 1
        # second call: in-process cache hit, no re-sweep
        assert flash_block_sizes(8, 512, 512, 64, jnp.float32, True) == \
            (256, 128)
        assert len(calls) == 1
        assert [e for e, _ in events] == ["flash_autotune"]
        assert events[0][1]["block_q"] == 256

        path = tmp_path / "flash_autotune.json"
        assert path.exists()
        data = json.loads(path.read_text())
        assert data["8|512|512|64|float32|True"] == [256, 128]

    def test_persisted_cache_reloads(self, monkeypatch, tmp_path):
        self._fake_tpu(monkeypatch)
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
        (tmp_path / "flash_autotune.json").write_text(
            json.dumps({"8|512|512|64|float32|True": [512, 256]}))
        monkeypatch.setattr(pk, "_AUTOTUNE_CACHE", {})
        monkeypatch.setattr(pk, "_AUTOTUNE_FILE_LOADED", False)
        monkeypatch.setattr(pk, "_sweep_flash_blocks",
                            lambda *a: pytest.fail("cache miss"))
        assert flash_block_sizes(8, 512, 512, 64, jnp.float32, True) == \
            (512, 256)

    def test_single_candidate_skips_sweep(self, monkeypatch, tmp_path):
        self._fake_tpu(monkeypatch)
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
        monkeypatch.setattr(pk, "_AUTOTUNE_CACHE", {})
        monkeypatch.setattr(pk, "_AUTOTUNE_FILE_LOADED", True)
        monkeypatch.setattr(pk, "_sweep_flash_blocks",
                            lambda *a: pytest.fail("swept 1-candidate"))
        assert flash_block_sizes(8, 128, 64, 64, jnp.float32, False) == \
            (128, 64)

    def test_torn_cache_file_is_ignored(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
        (tmp_path / "flash_autotune.json").write_text("{not json")
        monkeypatch.setattr(pk, "_AUTOTUNE_CACHE", {})
        monkeypatch.setattr(pk, "_AUTOTUNE_FILE_LOADED", False)
        pk._autotune_load()  # must not raise
        assert pk._AUTOTUNE_CACHE == {}


class TestFlashOverMesh:
    """Under a GSPMD mesh the kernel runs once per shard inside a
    shard_map (XLA cannot partition a Mosaic kernel; on the TPU a bare
    pallas_call in a sharded step is a compile error). Per-shard results
    must equal the unsharded kernel's — with dropout too: the interpret
    path slices the SAME bits slab, so the masks are identical."""

    @staticmethod
    def _mesh():
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))

    @pytest.mark.parametrize("dropout_p", [0.0, 0.3])
    def test_matches_unsharded_fwd_and_grad(self, dropout_p):
        q, k, v = _qkv(4, 4, 32, 32, 16)
        rng = (jax.random.bits(jax.random.PRNGKey(1), (16, 32, 32),
                               jnp.uint32)
               if dropout_p else jnp.zeros((1,), jnp.int32))
        static = (True, True, dropout_p, 16, 16)

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return (out * jnp.cos(out)).sum(), out
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))(q, k, v)

        (_, want), want_g = loss(lambda q, k, v: _flash(q, k, v, rng,
                                                        *static))
        (_, got), got_g = loss(lambda q, k, v: pk._flash_over_mesh(
            self._mesh(), q, k, v, rng, *static))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        assert len(got.sharding.device_set) == 4

    def test_indivisible_shapes_are_left_to_the_caller(self):
        q, k, v = _qkv(3, 4, 16, 16, 16)      # batch 3 over dp=2
        assert pk._flash_over_mesh(
            self._mesh(), q, k, v, jnp.zeros((1,), jnp.int32),
            True, True, 0.0, 16, 16) is None


class TestPathCounters:
    def test_registry_totals_track_dispatch(self):
        """The registry-sourced totals (what bench.py reports) and the
        resettable counts (what routing tests assert) must move together
        when the public sdpa entry point routes to flash."""
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        paddle.seed(0)
        q = paddle.randn([1, 1, 16, 16])
        before = attention_path_totals()
        attention_path_counts(reset=True)
        F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                       training=False)
        counts = attention_path_counts()
        delta = {k: v - before.get(k, 0)
                 for k, v in attention_path_totals().items()}
        assert counts["flash"] == 1 and delta["flash"] == 1
        assert delta.get("xla_sdpa", 0) == 0

"""Micro-benchmarks: Pallas fused kernels vs their XLA fallbacks on TPU.

Run on a TPU host:  python benchmarks/fused_kernels_bench.py
Prints one JSON line per kernel (bench.py conventions: every row carries
a "config" key) and ends with ONE machine-readable headline line
(metric/value/unit/vs_baseline + the per-config rows under "results") so
driver captures and `ptdoctor bench` can trend the kernels run-over-run.
Shapes follow the GPT-2/ERNIE configs in BASELINE.md."""
from __future__ import annotations

import functools
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

# standalone runs put benchmarks/ (not the repo root) on sys.path[0]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


CHAIN = 10


def timeit(fn, *args, iters=30, warmup=5):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_flash_attention(B=8, H=12, T=1024, D=64, dtype=jnp.bfloat16):
    from paddle_tpu.ops.pallas_kernels import _flash, _xla_attention
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, T, D), dtype)
    k = jnp.asarray(rs.randn(B, H, T, D), dtype)
    v = jnp.asarray(rs.randn(B, H, T, D), dtype)
    interp = jax.default_backend() != "tpu"

    # CHAIN iterations inside one jit, so per-call dispatch latency does
    # not drown the kernel time
    def chain(attn):
        @jax.jit
        def step(q, k, v):
            for _ in range(CHAIN):
                dq, dk, dv = jax.grad(
                    lambda q, k, v: attn(q, k, v).sum(),
                    argnums=(0, 1, 2))(q, k, v)
                q = (q + 1e-3 * dq).astype(q.dtype)
                k = (k + 1e-3 * dk).astype(k.dtype)
                v = (v + 1e-3 * dv).astype(v.dtype)
            return q
        return step

    tp = timeit(chain(lambda q, k, v: _flash(q, k, v, None, True, interp,
                                             0.0)),
                q, k, v, iters=3) / CHAIN
    tx = timeit(chain(lambda q, k, v: _xla_attention(q, k, v, True)),
                q, k, v, iters=3) / CHAIN
    return {"config": "flash_attention_fwd_bwd",
            "kernel": "flash_attention_fwd_bwd",
            "shape": [B, H, T, D], "dtype": str(dtype.__name__),
            "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
            "speedup": round(tx / tp, 2)}


def bench_fused_ln(N=8192, Hdim=768, p=0.1, dtype=jnp.bfloat16):
    from paddle_tpu.ops.pallas_kernels import (
        fused_bias_dropout_residual_ln_arrays)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(N, Hdim), dtype)
    res = jnp.asarray(rs.randn(N, Hdim), dtype)
    bias = jnp.asarray(rs.randn(Hdim), dtype)
    gamma = jnp.ones((Hdim,), dtype)
    beta = jnp.zeros((Hdim,), dtype)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def fused(x, res, key):
        return jax.grad(lambda x: fused_bias_dropout_residual_ln_arrays(
            x, res, bias, gamma, beta, key, p, 1e-5, True,
            "upscale_in_train")[0].sum())(x)

    @jax.jit
    def unfused(x, res, key):
        def f(x):
            keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
            z = res + jnp.where(keep, (x + bias) / (1.0 - p), 0)
            mean = z.mean(-1, keepdims=True)
            var = ((z - mean) ** 2).mean(-1, keepdims=True)
            return ((z - mean) * jax.lax.rsqrt(var + 1e-5) * gamma
                    + beta).sum()
        return jax.grad(f)(x)

    def chain(g):
        @jax.jit
        def step(x, res, key):
            for _ in range(CHAIN):
                x = (x + 1e-3 * g(x, res, key)).astype(x.dtype)
            return x
        return step

    tp = timeit(chain(fused), x, res, key, iters=3) / CHAIN
    tx = timeit(chain(unfused), x, res, key, iters=3) / CHAIN
    return {"config": "fused_bias_dropout_residual_ln_fwd_bwd",
            "kernel": "fused_bias_dropout_residual_ln_fwd_bwd",
            "shape": [N, Hdim], "dtype": str(dtype.__name__),
            "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
            "speedup": round(tx / tp, 2)}


def bench_fused_adamw(numel=768 * 3072, dtype=jnp.float32):
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.ops.pallas_kernels import fused_adamw_or_none
    rs = np.random.RandomState(0)
    shape = (numel // 128, 128)
    p = jnp.asarray(rs.randn(*shape), dtype)
    g = jnp.asarray(rs.randn(*shape), dtype)
    m1 = jnp.zeros(shape, jnp.float32)
    m2 = jnp.zeros(shape, jnp.float32)
    lr, t = jnp.float32(1e-3), jnp.int32(2)
    interp = jax.default_backend() != "tpu"

    pallas_fn = jax.jit(functools.partial(
        fused_adamw_or_none, beta1=0.9, beta2=0.999, epsilon=1e-8,
        coeff=0.01, interpret=interp))
    sa = (0.9, 0.999, 1e-8, 0.01)
    xla_fn = jax.jit(lambda p, g, lr, t, m1, m2:
                     AdamW._update_rule(sa, p, g, lr, t, m1, m2))

    def chain(upd):
        @jax.jit
        def step(p, g, lr, t, m1, m2):
            for _ in range(CHAIN):
                p, m1, m2 = upd(p, g, lr, t, m1, m2)
            return p, m1, m2
        return step

    tp = timeit(chain(lambda *a: pallas_fn(*a)), p, g, lr, t, m1, m2,
                iters=3) / CHAIN
    tx = timeit(chain(lambda *a: xla_fn(*a)), p, g, lr, t, m1, m2,
                iters=3) / CHAIN
    return {"config": "fused_adamw_update",
            "kernel": "fused_adamw_update",
            "shape": list(shape), "dtype": str(np.dtype(dtype).name),
            "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
            "speedup": round(tx / tp, 2)}


def bench_paged_decode(B=8, H=12, T=2048, D=64, live=256, quantized=True,
                       dtype=jnp.float32, L=2):
    """The serving megakernel vs the full-depth masked einsum it
    replaces: CHAIN fused decode steps on one layer of a stacked
    [L, B, H, T, D] cache (updated in place, length pinned at `live`)
    against the same steps as write + dequant + masked einsum over all T
    positions of one layer. The speedup is the HBM-traffic ratio the
    kernel's work list buys (steps and reads scale with `live`, not T)."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.inference.serving.cache import quantize_kv
    interp = jax.default_backend() != "tpu"
    blk = pk._paged_block(T, H, D, jnp.int8 if quantized else dtype, interp)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    nk = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    nv = jnp.asarray(rs.randn(B, H, 1, D), dtype)
    kf = jnp.asarray(rs.randn(L, B, H, T, D), dtype)
    vf = jnp.asarray(rs.randn(L, B, H, T, D), dtype)
    lens = jnp.full((B,), live, jnp.int32)
    if quantized:
        kc, ks = quantize_kv(kf)
        vc, vs = quantize_kv(vf)
    else:
        kc, vc, ks, vs = kf, vf, None, None

    @jax.jit
    def fused(q, kc, vc, ks, vs):
        for _ in range(CHAIN):
            out, kc, vc, ks, vs = pk._paged_decode(
                q, kc, vc, lens, nk, nv, ks, vs, layer=L - 1, block_k=blk,
                interpret=interp)
            q = (q + 1e-3 * out).astype(q.dtype)
        return q

    def _write(buf, new, ln):
        z = jnp.int32(0)
        return jax.lax.dynamic_update_slice(buf, new, (z, ln, z))

    def _write_sc(buf, new, ln):
        return jax.lax.dynamic_update_slice(buf, new, (jnp.int32(0), ln))

    @jax.jit
    def einsum(q, kc, vc, ks, vs):
        for _ in range(CHAIN):
            if quantized:
                nkq, nks = quantize_kv(nk)
                nvq, nvs = quantize_kv(nv)
                kc = jax.vmap(_write)(kc, nkq, lens)
                vc = jax.vmap(_write)(vc, nvq, lens)
                ks = jax.vmap(_write_sc)(ks, nks, lens)
                vs = jax.vmap(_write_sc)(vs, nvs, lens)
                kw = kc.astype(jnp.float32) * ks[..., None]
                vw = vc.astype(jnp.float32) * vs[..., None]
            else:
                kc = jax.vmap(_write)(kc, nk.astype(kc.dtype), lens)
                vc = jax.vmap(_write)(vc, nv.astype(vc.dtype), lens)
                kw, vw = kc.astype(jnp.float32), vc.astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                           kw) * (float(D) ** -0.5)
            valid = (jnp.arange(T)[None, None, None, :]
                     <= lens[:, None, None, None])
            s = jnp.where(valid, s, jnp.float32(-1e30))
            out = jnp.einsum("bhqk,bhkd->bhqd",
                             jax.nn.softmax(s, axis=-1), vw)
            q = (q + 1e-3 * out).astype(q.dtype)
        return q

    def one(a):
        return None if a is None else a[L - 1]

    tp = timeit(fused, q, kc, vc, ks, vs, iters=3) / CHAIN
    tx = timeit(einsum, q, one(kc), one(vc), one(ks), one(vs),
                iters=3) / CHAIN
    return {"config": "paged_decode_attention",
            "kernel": "paged_decode_attention",
            "shape": [L, B, H, T, D], "live_len": live,
            "block_k": blk, "int8": bool(quantized),
            "dtype": str(dtype.__name__),
            "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
            "speedup": round(tx / tp, 2)}


def bench_decoder_block_tail(N=8192, Hdim=768, p=0.1, dtype=jnp.bfloat16):
    """FLAGS_fused_block tail: ONE pass producing (ln_2(z), z) vs the
    composed residual-add + separate LayerNorm read (fwd + bwd), the
    exact pair of ops GPTDecoderLayer fuses between attention and MLP."""
    from paddle_tpu.ops.pallas_kernels import (
        fused_bias_dropout_residual_ln_arrays)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(N, Hdim), dtype)
    res = jnp.asarray(rs.randn(N, Hdim), dtype)
    gamma = jnp.ones((Hdim,), dtype)
    beta = jnp.zeros((Hdim,), dtype)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def fused(x, res, key):
        def f(x):
            y, z = fused_bias_dropout_residual_ln_arrays(
                x, res, None, gamma, beta, key, p, 1e-5, True,
                "upscale_in_train")
            return y.sum() + z.sum()    # both outputs consumed, like the
        return jax.grad(f)(x)           # block (y→MLP, z→residual)

    @jax.jit
    def unfused(x, res, key):
        def f(x):
            keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
            z = res + jnp.where(keep, x / (1.0 - p), 0)
            mean = z.mean(-1, keepdims=True)
            var = ((z - mean) ** 2).mean(-1, keepdims=True)
            y = (z - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
            return y.sum() + z.sum()
        return jax.grad(f)(x)

    def chain(g):
        @jax.jit
        def step(x, res, key):
            for _ in range(CHAIN):
                x = (x + 1e-3 * g(x, res, key)).astype(x.dtype)
            return x
        return step

    tp = timeit(chain(fused), x, res, key, iters=3) / CHAIN
    tx = timeit(chain(unfused), x, res, key, iters=3) / CHAIN
    return {"config": "decoder_block_tail",
            "kernel": "decoder_block_tail_pair_fwd_bwd",
            "shape": [N, Hdim], "dtype": str(dtype.__name__),
            "pallas_ms": round(tp * 1e3, 3), "xla_ms": round(tx * 1e3, 3),
            "speedup": round(tx / tp, 2)}


_METRIC = "fused_kernels_geomean_speedup"


def main():
    tpu = jax.default_backend() == "tpu"
    print(json.dumps({"backend": jax.default_backend(),
                      "note": None if tpu else
                      "non-TPU smoke run: tiny shapes, interpret-mode "
                      "pallas — timings not meaningful"}))
    if tpu:
        benches = [bench_flash_attention, bench_fused_ln,
                   bench_fused_adamw, bench_paged_decode,
                   bench_decoder_block_tail]
    else:
        benches = [
            functools.partial(bench_flash_attention, B=1, H=2, T=64, D=16,
                              dtype=jnp.float32),
            functools.partial(bench_fused_ln, N=64, Hdim=128,
                              dtype=jnp.float32),
            functools.partial(bench_fused_adamw, numel=128 * 16),
            functools.partial(bench_paged_decode, B=2, H=2, T=128, D=16,
                              live=16),
            functools.partial(bench_decoder_block_tail, N=64, Hdim=128,
                              dtype=jnp.float32),
        ]
    rows, failed = [], []
    for fn in benches:
        name = getattr(fn, "__name__", getattr(
            getattr(fn, "func", None), "__name__", "bench"))
        try:
            row = fn()
            rows.append(row)
            print(json.dumps(row), flush=True)
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            print(json.dumps({"config": name, "kernel": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
    # headline: ONE machine-readable line, bench.py conventions
    speedups = [r["speedup"] for r in rows
                if isinstance(r.get("speedup"), (int, float))
                and r["speedup"] > 0]
    geomean = (round(float(np.exp(np.mean(np.log(speedups)))), 3)
               if speedups else None)
    print(json.dumps({"metric": _METRIC, "value": geomean, "unit": "x",
                      "vs_baseline": 0.0, "backend": jax.default_backend(),
                      "results": rows}), flush=True)
    if failed:
        raise SystemExit("fused_kernels_bench.py: failed kernels: %s"
                         % ", ".join(failed))


if __name__ == "__main__":
    main()

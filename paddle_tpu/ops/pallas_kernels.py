"""Pallas TPU kernels for the hot ops.

Flash attention (online-softmax, O(T) memory) — the TPU-native counterpart of
the reference's fused CUDA attention (operators/fused/fused_attention_op.cu,
operators/fused/multihead_matmul_op.cu). Forward is a Pallas kernel tiled for
the MXU (q blocks × k blocks, f32 accumulators, bf16-friendly); backward is
a pair of Pallas kernels (FlashAttention-2 style: a dq kernel streaming K/V
blocks and a dk/dv kernel streaming Q/dO blocks) driven by the forward's
saved logsumexp — no T×T tensor is ever materialised in either direction.
Training forwards additionally save lse (q-row logsumexp, broadcast over a
128-lane minor dim for TPU tiling); inference forwards skip it.

On CPU (tests) the kernel runs in interpret mode on tiny shapes; dispatch is
gated by `flash_attention_or_none` which returns None when the plain XLA path
should be used instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework.dispatch import primitive, raw
from ..framework.flags import flag

_NEG_INF = -1e30


# Per-row scalars (LSE) are stored broadcast over a 128-lane minor dim so
# their blocks satisfy TPU lane alignment (same layout jax's own TPU flash
# attention uses for its l/m residuals).
_LANES = 128


# ---------------------------------------------------------------------------
# TPU self-checks
#
# On backend `tpu` every compile entry point (make_train_step, the static
# Executor, the Predictor, the serving engine) calls pallas_selfcheck()
# once, at an untraced moment: the real kernels run at a small but
# representative shape and are compared with their dense XLA oracles. A
# kernel that fails to lower, compile or match RAISES — nothing here turns
# such an error into an XLA path. The only way to turn a kernel off is its
# flag, where it has one (FLAGS_use_flash_attention,
# FLAGS_use_fused_optimizer, FLAGS_fused_block); a kernel whose flag is off
# is not checked. The paged-decode kernels have none: on the TPU they run.
# ---------------------------------------------------------------------------


class PallasSelfCheckError(RuntimeError):
    """A Pallas kernel compiled on the TPU but disagreed with its XLA
    oracle (or produced non-finite values)."""


_SELFCHECKED = set()   # checks that passed on this process's TPU backend


def _selfcheck_q():
    """Self-check shape: head_dim 64 (what GPT-2/ERNIE/BERT run), 2 heads
    (grid batch axis > 1) and Tq = 256, so the forward streams several
    k-blocks per program and the dkv kernel runs a multi-block grid."""
    rs = np.random.RandomState(0)
    return jnp.asarray(rs.randn(1, 2, 256, 64), jnp.float32)


def _max_err(got, want):
    return float(np.nanmax(np.abs(np.asarray(got, np.float64)
                                  - np.asarray(want, np.float64))))


def _check_flash():
    """Plain flash attention, fwd + dq + dk/dv through the custom vjp,
    value-checked (output AND gradient) against the dense oracle."""
    q = _selfcheck_q()

    def run(attn):
        def f(q):
            out = attn(q)
            return out.astype(jnp.float32).sum(), out
        (_, out), grad = jax.jit(jax.value_and_grad(f, has_aux=True))(q)
        return np.asarray(out), np.asarray(grad)

    out, grad = run(lambda q: _flash(q, q, q, None, True, False, 0.0))
    want, want_grad = run(lambda q: _xla_attention(q, q, q, True))
    if not (np.allclose(out, want, rtol=2e-3, atol=2e-3)
            and np.allclose(grad, want_grad, rtol=2e-2, atol=2e-2)):
        raise PallasSelfCheckError(
            "flash attention disagrees with the XLA oracle on %s: "
            "max|out-want|=%.3e max|grad-want|=%.3e"
            % (jax.devices()[0].device_kind, _max_err(out, want),
               _max_err(grad, want_grad)))


def _check_flash_dropout():
    """The in-kernel-PRNG dropout variant (pltpu.prng_seed /
    prng_random_bits). Its output is stochastic, so the check is finite
    values and gradients plus a keep-rate sanity bound."""
    q = _selfcheck_q()
    seed = jnp.zeros((1,), jnp.int32)
    p = 0.5

    def f(q):
        # v = 1: each output element is the kept share of its softmax
        # row, mean 1; k = q reversed keeps the rows diffuse (q.q would
        # put all the weight on the diagonal and leave one draw per row)
        out = _flash(q, q[:, :, ::-1, :], jnp.ones_like(q), seed, False,
                     False, p)
        return out.astype(jnp.float32).sum(), out

    (_, out), grad = jax.jit(jax.value_and_grad(f, has_aux=True))(q)
    out, grad = np.asarray(out), np.asarray(grad)
    mean = float(out.mean())
    if not (np.isfinite(out).all() and np.isfinite(grad).all()
            and 0.9 < mean < 1.1):
        raise PallasSelfCheckError(
            "flash attention with in-kernel dropout failed its check on "
            "%s: finite out=%s finite grad=%s E[dropout(softmax)@1]=%.3f "
            "(want 1.0)" % (jax.devices()[0].device_kind,
                            bool(np.isfinite(out).all()),
                            bool(np.isfinite(grad).all()), mean))


def pallas_selfcheck(needs_prng=True, needs_paged=False, needs=()):
    """Run the TPU self-checks this entry point's kernels need, once per
    process; no-op off TPU (interpret mode never touches Mosaic).

    needs_prng=False (inference entry points) skips the dropout variant —
    eval traces never use it. needs_paged=True (the serving engine) adds
    the paged-decode kernel; `needs` names further kernels a served model
    runs ("paged_gqa", "band_flash": the grouped-query decode and the
    band prefill of models/decoder.py; "paged_gqa_sink",
    "band_flash_sink": the same at a key size that is not the value size
    with a sink in the softmax; "paged_latent", "band_flash_latent": the
    absorbed decode over a latent cache and the band prefill at one query
    head a key head of a 192-wide key; "band_flash_mha": GPT's prompts,
    one query head a key head of 128, over 768 rows in one block and over
    1024 in blocks of 512; "held_grouped": the grouped product of a layer
    that holds a share of its router's experts), checked only for such a
    model. Raises whatever the compiler raises, or PallasSelfCheckError on a
    value mismatch."""
    if jax.default_backend() != "tpu":
        return
    checks = []
    if flag("use_flash_attention"):
        checks.append(("flash", _check_flash))
        if needs_prng:
            checks.append(("flash_dropout", _check_flash_dropout))
        if "band_flash" in needs:
            checks.append(("band_flash", _check_band_flash))
        if "band_flash_sink" in needs:
            checks.append(("band_flash_sink", _check_band_flash_sink))
        if "band_flash_latent" in needs:
            checks.append(("band_flash_latent", _check_band_flash_latent))
        if "band_flash_mha" in needs:
            checks.append(("band_flash_mha", _check_band_flash_mha))
    if needs_paged:
        checks.append(("paged", _check_paged))
    if "paged_gqa" in needs:
        checks.append(("paged_gqa", _check_paged_gqa))
    if "paged_gqa_sink" in needs:
        checks.append(("paged_gqa_sink", _check_paged_gqa_sink))
    if "paged_latent" in needs:
        checks.append(("paged_latent", _check_paged_latent))
    if "held_grouped" in needs:
        checks.append(("held_grouped", _check_held_grouped))
    for name, check in checks:
        if name not in _SELFCHECKED:
            check()
            _SELFCHECKED.add(name)


# Index-map constant: this framework runs with jax_enable_x64=True (int64
# tensors are first-class, like the reference), under which a bare `0` in a
# BlockSpec index map traces to an i64 literal that Mosaic cannot legalize
# ("func.return (i64)"); an np.int32 scalar keeps its dtype under x64.
_I0 = np.int32(0)


def _pallas_call(*args, **kwargs):
    """pl.pallas_call with the kernel traced under x64=False.

    Global x64 poisons Mosaic two ways (both reproduced on the v5e):
    i64 literals in auto-generated index maps fail to legalize, and
    convert_element_type lowering recurses infinitely on weak-typed
    converts inside kernel bodies. The kernels only consume
    f32/bf16/i32/u32 operands, so tracing them in 32-bit mode is
    semantics-preserving.

    Interpret mode never touches Mosaic, and the x64 flip actively breaks
    it: the kernel jaxpr gets traced with i32 loop counters while the
    emulator's grid machinery is generated later, at jit-lowering time,
    under the ambient (x64) mode — the mixed i64/i32 while-loop the
    verifier rejects ("'stablehlo.compare' op requires compatible element
    types"). Trace interpret calls straight through in the ambient mode
    instead so both halves agree."""
    inner = pl.pallas_call(*args, **kwargs)
    if kwargs.get("interpret", False):
        return inner

    def call(*operands):
        # Only flip the mode when x64 is actually on: the context manager
        # itself changes the trace context, so a 32-bit caller — e.g. a
        # library embedding these kernels without the framework's global
        # x64 — must trace straight through.
        if not jax.config.jax_enable_x64:
            return inner(*operands)
        with jax.enable_x64(False):
            return inner(*operands)

    return call


def _resident_vmem_params(resident_bytes, interpret):
    """Compiler params for a kernel that keeps `resident_bytes` of whole-
    sequence operands in VMEM (K and V in the flash fwd/dq kernels; Q, O,
    dO and the lane-broadcast lse in dk/dv). The pipeline double-buffers
    them, and Mosaic's default scoped limit is 16 MiB: at T=8192 the dk/dv
    kernel asks for 20 MiB and the v5e compiler refuses it. Short
    sequences keep the default (None); long ones get what they hold twice
    over plus room for the blocks and f32 temporaries, up to 100 of the
    chip's 128 MiB."""
    if interpret or 2 * resident_bytes <= (8 << 20):
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(
        2 * resident_bytes + (16 << 20), 100 << 20))


def _attn_drop_keep(rng_ref, qi, j, n_q, n_k, shape, has_rng, slice_axis):
    """Boolean keep-mask for attention-dropout tile (q-block qi, k-block j)
    of the current batch·head program, out of n_q × n_k tiles; `shape` =
    (q rows, k cols) of the tile. Shared by the forward and BOTH backward
    kernels so the keep/scale rule can never diverge between them.

    TPU (`has_rng`): re-seed the hardware PRNG from (seed, tile number) so
    the SAME bits are regenerated everywhere regardless of the kernels'
    different grid/loop orders — the [T, T] mask never touches HBM (same
    trick as the fused dropout chain below). Mosaic seeds from at most two
    values, so (batch·head, qi, j) fold into one flat tile number.
    CPU/interpret: rng_ref is a precomputed bits slab blocked on
    the grid axis; slice the loop axis (`slice_axis`=1 → k columns, fwd/dq
    kernels; 0 → q rows, dkv kernel). Exercised by the exact-oracle tests.
    The threshold comparison is applied by the caller via the returned
    bits."""
    if has_rng:
        tile = (pl.program_id(0) * n_q + qi) * n_k + j
        pltpu.prng_seed(rng_ref[0], tile)
        return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    if slice_axis == 1:
        return rng_ref[:, pl.dslice(j * shape[1], shape[1])
                       ].astype(jnp.uint32)
    return rng_ref[pl.dslice(qi * shape[0], shape[0]), :].astype(jnp.uint32)


def _attn_drop_scale(x, bits, p):
    """where(keep, x/(1-p), 0) with keep ⇔ bits ≥ p·2³² (P(keep) = 1-p)."""
    thr = jnp.uint32(min(int(p * (2.0 ** 32)), 2 ** 32 - 1))
    return jnp.where(bits >= thr, x * (1.0 / (1.0 - p)), 0.0)


def _flash_fwd_kernel(rng_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale, block_k, causal, q_block, shift,
                      dropout_p=0.0, has_rng=True):
    """One (batch·head, q-block) program: stream K/V blocks, online softmax.

    `shift` = Tk - Tq implements bottom-right-aligned causal masking (cached
    decode: a query at row i attends keys [0, i + shift]), matching
    _xla_attention's tril(k=Tk-Tq) exactly.

    With `dropout_p` > 0 the dropout mask is applied to the exp-scores used
    in the PV matmul while the softmax denominator accumulates the UNDROPPED
    sums — elementwise keep/scale commutes with the final 1/l normalisation,
    so this equals dropout(softmax(s)) @ v exactly (the reference's fused
    attention-dropout, operators/fused/fused_attention_op.cu)."""
    qi, n_q = pl.program_id(1), pl.num_programs(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale        # [bq, d]
    bq, d = q.shape
    kt = k_ref.shape[0]
    nblk = kt // block_k

    # Online-softmax state (m_i running max, l_i running denominator) is
    # kept 2-D [bq, 1] throughout: 1-D [bq] f32 vectors as fori_loop
    # carries forced Mosaic to legalize rank-1 vector layouts (sublane-
    # only vregs), which Mosaic refused on the v5e — keepdims reductions
    # stay in the native (sublane, lane) layout.
    def body(j, carry):
        acc, m_i, l_i = carry
        k = k_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bk]
        if causal:
            q_pos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos + shift >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i - m_new)                      # [bq, 1]
        l_new = l_i * alpha + jnp.sum(p, axis=1, keepdims=True)
        pd = p
        if dropout_p > 0.0:
            bits = _attn_drop_keep(rng_ref, qi, j, n_q, nblk,
                                   (bq, block_k), has_rng, slice_axis=1)
            pd = _attn_drop_scale(p, bits, dropout_p)
        acc = acc * alpha + jax.lax.dot_general(
            pd, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc = jnp.zeros((bq, d), jnp.float32)
    m_i = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l_i = jnp.zeros((bq, 1), jnp.float32)
    if causal:
        # only blocks up to (and including) the shifted diagonal contribute
        upper = (qi + 1) * q_block + shift
        nblk_eff = jax.lax.min(
            jnp.int32(nblk), (upper + block_k - 1) // block_k)
    else:
        nblk_eff = nblk
    acc, m_i, l_i = jax.lax.fori_loop(0, nblk_eff, body, (acc, m_i, l_i))
    o_ref[...] = (acc / l_i).astype(o_ref.dtype)
    if lse_ref is not None:
        # logsumexp of the SCALED scores, for the backward kernels;
        # broadcast over the 128-lane minor dim (2-D [bq,1] -> [bq,LANES]
        # is a plain lane broadcast — no rank-1 layout involved)
        lse = m_i + jnp.log(l_i)
        lse_ref[...] = jax.lax.broadcast_in_dim(lse, (bq, _LANES), (0, 1))


def _nolse_kernel(kern, rng_ref, q_ref, k_ref, v_ref, o_ref):
    kern(rng_ref, q_ref, k_ref, v_ref, o_ref, None)


def _attn_rng_spec(rng, block_q, Tk, for_dkv=False, block_k=None):
    """BlockSpec for the dropout rng operand: SMEM scalar seed on TPU, a
    [B*H, Tq, Tk] bits-array tile on CPU/interpret."""
    if rng.ndim == 1:  # TPU hardware-PRNG seed
        return pl.BlockSpec((1,), lambda b, i: (_I0,),
                            memory_space=pltpu.SMEM), True
    if for_dkv:  # dkv kernel: all q rows of one k block
        return pl.BlockSpec((None, rng.shape[1], block_k),
                            lambda b, j: (b, _I0, j)), False
    return pl.BlockSpec((None, block_q, Tk), lambda b, i: (b, i, _I0)), False


def _flash_fwd(q, k, v, causal, block_q=128, block_k=128, interpret=False,
               need_lse=True, dropout_p=0.0, rng=None):
    """q/k/v: [B, H, Tq|Tk, D] → (out [B, H, Tq, D], lse [B*H, Tq, 128]).

    `need_lse=False` (inference) skips the lse output entirely — no extra
    HBM write; returns (out, None)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sm_scale = float(D) ** -0.5
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    if rng is None:
        rng = jnp.zeros((1,), jnp.int32)
    rng_spec, has_rng = _attn_rng_spec(rng, block_q, Tk)
    kernel = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                               block_k=block_k, causal=causal,
                               q_block=block_q, shift=Tk - Tq,
                               dropout_p=dropout_p, has_rng=has_rng)
    o_spec = pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0))
    o_shape = jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype)
    if need_lse:
        out_specs = [o_spec,
                     pl.BlockSpec((None, block_q, _LANES),
                                  lambda b, i: (b, i, _I0))]
        out_shape = [o_shape,
                     jax.ShapeDtypeStruct((B * H, Tq, _LANES), jnp.float32)]
    else:
        kernel = functools.partial(_nolse_kernel, kernel)
        out_specs = [o_spec]
        out_shape = [o_shape]
    outs = _pallas_call(
        kernel,
        grid=(B * H, Tq // block_q),
        in_specs=[
            rng_spec,
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, _I0, _I0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, _I0, _I0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_resident_vmem_params(
            2 * Tk * D * k.dtype.itemsize, interpret),
        interpret=interpret,
    )(rng, qr, kr, vr)
    out = outs[0].reshape(B, H, Tq, D)
    return out, (outs[1] if need_lse else None)


def _flash_bwd_dq_kernel(rng_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                         lse_ref, dq_ref, *, sm_scale, block_k, causal,
                         q_block, shift, dropout_p=0.0, has_rng=True):
    """dq for one (batch·head, q-block): stream K/V blocks.

    FlashAttention-2 backward: p = exp(s·scale − lse), dp = do·vᵀ,
    ds = p·(dp − Δ)·scale with Δ = rowsum(do∘o) (recomputed here — cheaper
    than a broadcast residual array), dq = Σ_j ds·k.

    Dropout: with pd = D∘p (keep/scale mask D regenerated per tile from the
    same seed tuple as the forward), out = pd·v gives dpd = do·vᵀ and
    dp = D∘dpd; the Δ trick still holds because rowsum(dp∘p) =
    rowsum(dpd∘pd) = rowsum(do∘o)."""
    qi, n_q = pl.program_id(1), pl.num_programs(1)
    q = q_ref[...].astype(jnp.float32)                    # [bq, d]
    do = do_ref[...].astype(jnp.float32)
    o = o_ref[...].astype(jnp.float32)
    # lse is stored broadcast over all 128 lanes; reduce instead of
    # slicing out lane 0 — a keepdims lane-reduction keeps the native 2-D
    # layout, while a size-1 lane slice needs a relayout Mosaic rejects
    # on some backends
    lse = jnp.max(lse_ref[...], axis=1, keepdims=True)    # [bq, 1]
    delta = jnp.sum(do * o, axis=1, keepdims=True)        # [bq, 1]
    bq, d = q.shape
    kt = k_ref.shape[0]
    nblk = kt // block_k

    def body(j, dq_acc):
        k = k_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if causal:
            q_pos = qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos + shift >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                              # masked → 0
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            bits = _attn_drop_keep(rng_ref, qi, j, n_q, nblk,
                                   (bq, block_k), has_rng, slice_axis=1)
            dp = _attn_drop_scale(dp, bits, dropout_p)
        ds = p * (dp - delta) * sm_scale
        return dq_acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        upper = (qi + 1) * q_block + shift
        nblk_eff = jax.lax.min(
            jnp.int32(nblk), (upper + block_k - 1) // block_k)
    else:
        nblk_eff = nblk
    dq = jax.lax.fori_loop(0, nblk_eff, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(rng_ref, q_ref, k_ref, v_ref, o_ref, do_ref,
                          lse_ref, dk_ref, dv_ref, *, sm_scale, block_q,
                          causal, k_block, shift, dropout_p=0.0,
                          has_rng=True):
    """dk/dv for one (batch·head, k-block): stream Q/dO blocks.

    dv = Σ_i pdᵀ·do, dk = Σ_i dsᵀ·q; under causal masking q-blocks strictly
    above the shifted diagonal are skipped via the loop lower bound. The
    dropout mask tile (i, ki) is regenerated from the same (seed, tile
    number) pair the forward used."""
    ki, n_k = pl.program_id(1), pl.num_programs(1)
    k = k_ref[...].astype(jnp.float32)                    # [bk, d]
    v = v_ref[...].astype(jnp.float32)
    bk, d = k.shape
    qt = q_ref.shape[0]
    nblk = qt // block_q

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        o = o_ref[pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        lse = jnp.max(lse_ref[pl.dslice(i * block_q, block_q), :],
                      axis=1, keepdims=True)  # lanes identical; see dq
        delta = jnp.sum(do * o, axis=1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                  # [bq, bk]
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = ki * k_block + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos + shift >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        pd = p
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            bits = _attn_drop_keep(rng_ref, i, ki, nblk, n_k,
                                   (block_q, bk), has_rng, slice_axis=0)
            pd = _attn_drop_scale(p, bits, dropout_p)
            dp = _attn_drop_scale(dp, bits, dropout_p)
        dv_acc = dv_acc + jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    if causal:
        # first q row that can see this k block: q_pos + shift >= ki·bk
        start = jax.lax.max(jnp.int32(0),
                            (ki * k_block - shift) // block_q)
    else:
        start = jnp.int32(0)
    dk, dv = jax.lax.fori_loop(
        start, nblk, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal, block_q=128, block_k=128,
               interpret=False, dropout_p=0.0, rng=None):
    """Pallas flash-attention backward: (dq, dk, dv), O(T) memory — the
    TPU-native counterpart of the reference's fused attention grad
    (operators/fused/fused_attention_op.cu backward)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sm_scale = float(D) ** -0.5
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    shift = Tk - Tq
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    orr = o.reshape(B * H, Tq, D)
    dor = do.reshape(B * H, Tq, D)
    if rng is None:
        rng = jnp.zeros((1,), jnp.int32)
    rng_spec_q, has_rng = _attn_rng_spec(rng, block_q, Tk)
    rng_spec_kv, _ = _attn_rng_spec(rng, block_q, Tk, for_dkv=True,
                                    block_k=block_k)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, sm_scale=sm_scale, block_k=block_k,
        causal=causal, q_block=block_q, shift=shift, dropout_p=dropout_p,
        has_rng=has_rng)
    dq = _pallas_call(
        dq_kernel,
        grid=(B * H, Tq // block_q),
        in_specs=[
            rng_spec_q,
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, _I0, _I0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, _I0, _I0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, _I0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, _I0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        compiler_params=_resident_vmem_params(
            2 * Tk * D * k.dtype.itemsize, interpret),
        interpret=interpret,
    )(rng, qr, kr, vr, orr, dor, lse)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, sm_scale=sm_scale, block_q=block_q,
        causal=causal, k_block=block_k, shift=shift, dropout_p=dropout_p,
        has_rng=has_rng)
    dk, dv = _pallas_call(
        dkv_kernel,
        grid=(B * H, Tk // block_k),
        in_specs=[
            rng_spec_kv,
            pl.BlockSpec((None, Tq, D), lambda b, j: (b, _I0, _I0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, _I0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, _I0)),
            pl.BlockSpec((None, Tq, D), lambda b, j: (b, _I0, _I0)),
            pl.BlockSpec((None, Tq, D), lambda b, j: (b, _I0, _I0)),
            pl.BlockSpec((None, Tq, _LANES), lambda b, j: (b, _I0, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, _I0)),
            pl.BlockSpec((None, block_k, D), lambda b, j: (b, j, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype),
        ],
        compiler_params=_resident_vmem_params(
            Tq * (3 * D * q.dtype.itemsize + _LANES * 4), interpret),
        interpret=interpret,
    )(rng, qr, kr, vr, orr, dor, lse)
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


def _xla_attention(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (float(d) ** -0.5)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(cm, s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, rng, causal, interpret, dropout_p, block_q=128,
           block_k=128):
    return _flash_fwd(q, k, v, causal, block_q=block_q, block_k=block_k,
                      interpret=interpret, need_lse=False,
                      dropout_p=dropout_p, rng=rng)[0]


def _flash_vjp_fwd(q, k, v, rng, causal, interpret, dropout_p, block_q=128,
                   block_k=128):
    o, lse = _flash_fwd(q, k, v, causal, block_q=block_q, block_k=block_k,
                        interpret=interpret, dropout_p=dropout_p, rng=rng)
    return o, (q, k, v, o, lse, rng)


def _flash_vjp_bwd(causal, interpret, dropout_p, block_q, block_k, res, g):
    q, k, v, o, lse, rng = res
    # forward and backward MUST tile identically: the dropout keep-mask is
    # regenerated per (q-tile, k-tile) from the tile indices, so a block
    # mismatch would silently change which elements were dropped
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, g, causal, block_q=block_q,
                            block_k=block_k, interpret=interpret,
                            dropout_p=dropout_p, rng=rng)
    from jax.dtypes import float0
    drng = None if rng is None else np.zeros(jnp.shape(rng), float0)
    return dq, dk, dv, drng


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _shapes_ok(q, k, causal, interpret):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if causal and Tk < Tq:
        # bottom-right alignment would fully mask the first Tq-Tk rows
        # (0/0 in the online softmax); no real workload hits this — XLA path
        return False
    if interpret:  # CPU test path: keep interpret-mode cheap
        return Tq * Tk <= 64 * 64 and D <= 128

    # blocks are min(128, T): T < 128 gives a single block, else T must tile
    # exactly — floor-division grids would silently drop trailing rows/keys
    def tiles(T):
        return T % 128 == 0 or (T < 128 and T % 8 == 0)

    return D % 8 == 0 and D <= 256 and tiles(Tq) and tiles(Tk)


def _flash_over_mesh(mesh, q, k, v, rng, *static):
    """_flash under a GSPMD mesh: one kernel call per shard, batch split
    over the data axes and heads over "mp" (attention is independent per
    batch row and head, so no collective is needed). XLA cannot partition
    a Mosaic kernel by itself — on the TPU a bare pallas_call inside a
    sharded step raises "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map". Returns None when
    the batch or the heads do not divide over the mesh."""
    from jax.sharding import PartitionSpec as P
    B, H, Tq, _ = q.shape
    data = tuple(a for a in ("dp", "sharding") if mesh.shape.get(a, 1) > 1)
    heads = "mp" if mesh.shape.get("mp", 1) > 1 else None
    n_data = int(np.prod([mesh.shape[a] for a in data]))
    if B % n_data or H % mesh.shape.get("mp", 1):
        return None
    spec = P(data or None, heads, None, None)
    seeded = rng.ndim == 1   # TPU: a PRNG seed; interpret: [B*H, Tq, Tk] bits
    if not seeded:
        rng = rng.reshape(B, H, Tq, -1)

    def per_shard(q, k, v, rng):
        if seeded:
            # same seed everywhere would give every shard the same masks
            axes = data + ((heads,) if heads else ())
            if axes:
                rng = rng + jax.lax.axis_index(axes).astype(rng.dtype)
        else:
            rng = rng.reshape((-1,) + rng.shape[2:])
        return _flash(q, k, v, rng, *static)

    return jax.shard_map(per_shard, mesh=mesh,
                         in_specs=(spec, spec, spec,
                                   P() if seeded else spec),
                         out_specs=spec, check_vma=False)(q, k, v, rng)


@primitive("flash_attention")
def _flash_op(q, k, v, rng, *, causal=False, interpret=False,
              dropout_p=0.0, block_q=128, block_k=128):
    from ..framework import state
    if rng is None:
        rng = jnp.zeros((1,), jnp.int32)
    static = (causal, interpret, dropout_p, block_q, block_k)
    mesh = state.current_mesh()
    out = None
    if mesh is not None:
        out = _flash_over_mesh(mesh, q, k, v, rng, *static)
    return _flash(q, k, v, rng, *static) if out is None else out


# ---------------------------------------------------------------------------
# Fused bias + dropout + residual (+ layernorm)
#
# TPU-native counterpart of the reference's fused dropout chain
# (/root/reference/paddle/fluid/operators/fused/fused_dropout_helper.h — the
# LaunchResidualDropoutBias / LaunchLayernormResidualDropoutBias kernels used
# by fused_attention_op.cu and fused_feedforward_op.cu). One Pallas program
# computes z = residual + dropout(x + bias) and y = LN(z) in a single HBM
# pass; the backward recomputes LN statistics from the saved z (cheaper than
# storing mean/rstd) and regenerates the dropout mask from the same per-
# program seed (hardware PRNG on TPU — the mask never touches HBM).
# On CPU/interpret the mask bits are generated outside (threefry) and passed
# in, exercising identical keep/scale logic.
# ---------------------------------------------------------------------------

def _dropout_keep(bits, h, p, scale):
    """Shared keep/scale decision: keep iff bits >= p·2³² (P = 1-p)."""
    threshold = jnp.uint32(min(int(p * (2.0 ** 32)), 2 ** 32 - 1))
    keep = bits >= threshold
    return jnp.where(keep, h * scale, 0.0)


def _fbdrln_rng_bits(rng_ref, shape, has_rng):
    if has_rng:
        pltpu.prng_seed(rng_ref[0] + pl.program_id(0))
        return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return rng_ref[...].astype(jnp.uint32)


def _fbdrln_fwd_kernel(rng_ref, x_ref, res_ref, bias_ref, gamma_ref,
                       beta_ref, y_ref, z_ref, *, p, scale, eps, has_rng,
                       with_ln):
    """with_ln=False passes z_ref=None: the no-LN tail has ONE output (z);
    writing a duplicate y would double the HBM write traffic."""
    x = x_ref[...].astype(jnp.float32)                    # [bn, H]
    res = res_ref[...].astype(jnp.float32)
    h = x + bias_ref[...].astype(jnp.float32)             # bias [1, H]
    if p > 0.0:
        bits = _fbdrln_rng_bits(rng_ref, h.shape, has_rng)
        h = _dropout_keep(bits, h, p, scale)
    z = res + h
    if not with_ln:
        y_ref[...] = z.astype(y_ref.dtype)
        return
    z_ref[...] = z.astype(z_ref.dtype)
    mean = jnp.mean(z, axis=1, keepdims=True)
    var = jnp.mean((z - mean) ** 2, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = ((z - mean) * rstd * gamma_ref[...].astype(jnp.float32)
         + beta_ref[...].astype(jnp.float32))
    y_ref[...] = y.astype(y_ref.dtype)


def _fbdrln_fwd_noln_kernel(rng_ref, x_ref, res_ref, bias_ref, gamma_ref,
                            beta_ref, out_ref, *, p, scale, eps, has_rng,
                            with_ln):
    _fbdrln_fwd_kernel(rng_ref, x_ref, res_ref, bias_ref, gamma_ref,
                       beta_ref, out_ref, None, p=p, scale=scale, eps=eps,
                       has_rng=has_rng, with_ln=False)


def _fbdrln_bwd_kernel(rng_ref, z_ref, dy_ref, dz_extra_ref, gamma_ref,
                       dx_ref, dres_ref, *, p, scale, eps, has_rng, with_ln):
    z = z_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    if with_ln:
        mean = jnp.mean(z, axis=1, keepdims=True)
        var = jnp.mean((z - mean) ** 2, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (z - mean) * rstd
        a = dy * gamma_ref[...].astype(jnp.float32)
        dz = rstd * (a - jnp.mean(a, axis=1, keepdims=True)
                     - xhat * jnp.mean(a * xhat, axis=1, keepdims=True))
    else:
        dz = dy
    dz = dz + dz_extra_ref[...].astype(jnp.float32)
    dres_ref[...] = dz.astype(dres_ref.dtype)
    if p > 0.0:
        bits = _fbdrln_rng_bits(rng_ref, dz.shape, has_rng)
        dx = _dropout_keep(bits, dz, p, scale)
    else:
        dx = dz
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _fbdrln_block_n(n, hdim):
    """Row-block size for an (n, hdim) kernel, or None when no legal block
    exists. Two constraints: f32 footprint ~2 MB per array (the kernels hold
    ~6 such blocks, comfortably inside the ~16 MB/core VMEM even at
    hdim=16384), and Pallas-TPU block legality — the sublane dimension must
    be divisible by 8 OR the block must span the whole array, so row blocks
    below 8 are only legal as the full array."""
    cap = max(1, (2 << 20) // (4 * hdim))
    for bn in (256, 128, 64, 32, 16, 8):
        if bn <= cap and n % bn == 0:
            return bn
    if n <= cap:
        return n  # single full-array block: always a legal shape
    return None


def _fbdrln_call(kernel, n_out, rng, arrs, out_dtypes, *, p, scale, eps,
                 has_rng, with_ln, interpret, block_n=None):
    n, hdim = arrs[0].shape
    # an autotuned override must still be legal (divide n, or be the whole
    # array) — a stale persisted entry for a different n falls back to the
    # deterministic chooser rather than producing a ragged grid
    bn = (block_n if block_n and (n % block_n == 0 or block_n == n)
          else _fbdrln_block_n(n, hdim))
    if bn is None:
        # gated entries never get here (fused_ln_shapes_ok checks); direct
        # callers of the public array API can
        raise ValueError(
            f"fused dropout+LN: no legal TPU block for rows={n}, "
            f"hdim={hdim} (rows must be divisible by 8 or small enough "
            "for a single block) — use the unfused functional path")
    row_spec = pl.BlockSpec((bn, hdim), lambda i: (i, _I0))
    vec_spec = pl.BlockSpec((1, hdim), lambda i: (_I0, _I0))
    if has_rng:
        # explicit i32 index map: the default one emits i64 literals under
        # x64 that Mosaic rejects (same issue as _I0 above)
        rng_spec = pl.BlockSpec((1,), lambda i: (_I0,),
                                memory_space=pltpu.SMEM)
    else:
        rng_spec = row_spec  # precomputed mask bits, blocked like the rows
    in_specs = [rng_spec] + [row_spec if a.shape == (n, hdim) else vec_spec
                             for a in arrs]
    kern = functools.partial(kernel, p=p, scale=scale, eps=eps,
                             has_rng=has_rng, with_ln=with_ln)
    return _pallas_call(
        kern,
        grid=(n // bn,),
        in_specs=in_specs,
        out_specs=[row_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n, hdim), dt) for dt in out_dtypes],
        interpret=interpret,
    )(rng, *arrs)


def _fbdrln_make_rng(key, x2d, p, has_rng):
    """TPU: int32 seed scalar (drives the in-kernel hardware PRNG —
    the mask never touches HBM). CPU/interpret: threefry bits of the row
    shape (identical keep/scale logic, exercised by tests)."""
    if p <= 0.0:
        return (jnp.zeros((1,), jnp.int32) if has_rng
                else jnp.zeros(x2d.shape, jnp.uint32))
    if has_rng:
        return jax.random.bits(key, (1,), jnp.uint32).astype(jnp.int32)
    return jax.random.bits(key, x2d.shape, jnp.uint32)


def _fbdrln_vjp_fwd(x2d, res2d, bias, gamma, beta, key, p, scale, eps,
                    has_rng, interpret, block_n=None):
    rng = _fbdrln_make_rng(key, x2d, p, has_rng)
    with_ln = gamma is not None
    g2 = gamma if with_ln else jnp.ones((1, 1), x2d.dtype)
    b2 = beta if with_ln else jnp.zeros((1, 1), x2d.dtype)
    if with_ln:
        y, z = _fbdrln_call(
            _fbdrln_fwd_kernel, 2, rng, [x2d, res2d, bias, g2, b2],
            [x2d.dtype, x2d.dtype], p=p, scale=scale, eps=eps,
            has_rng=has_rng, with_ln=True, interpret=interpret,
            block_n=block_n)
    else:
        # no-LN: y IS z — single kernel output, half the HBM writes
        (z,) = _fbdrln_call(
            _fbdrln_fwd_noln_kernel, 1, rng, [x2d, res2d, bias, g2, b2],
            [x2d.dtype], p=p, scale=scale, eps=eps, has_rng=has_rng,
            with_ln=False, interpret=interpret, block_n=block_n)
        y = z
    return (y, z), (z, gamma, rng, key)


def _fbdrln_vjp_bwd(p, scale, eps, has_rng, interpret, block_n, resids, gs):
    z, gamma, rng, key = resids
    dy, dz_extra = gs
    with_ln = gamma is not None
    g2 = gamma if with_ln else jnp.ones((1, 1), z.dtype)
    # forward and backward MUST use the same row block: the dropout mask
    # is regenerated per program from (seed + program_id), so a block
    # mismatch would silently change which rows were dropped
    dx, dres = _fbdrln_call(
        _fbdrln_bwd_kernel, 2, rng, [z, dy, dz_extra, g2],
        [z.dtype, z.dtype], p=p, scale=scale, eps=eps, has_rng=has_rng,
        with_ln=with_ln, interpret=interpret, block_n=block_n)
    dbias = jnp.sum(dx, axis=0, keepdims=True).astype(z.dtype)
    if with_ln:
        # LN scale/shift grads: cheap XLA column reductions off saved z
        zf = z.astype(jnp.float32)
        mean = jnp.mean(zf, axis=1, keepdims=True)
        var = jnp.mean((zf - mean) ** 2, axis=1, keepdims=True)
        xhat = (zf - mean) * jax.lax.rsqrt(var + eps)
        dyf = dy.astype(jnp.float32)
        dgamma = jnp.sum(dyf * xhat, axis=0, keepdims=True).astype(z.dtype)
        dbeta = jnp.sum(dyf, axis=0, keepdims=True).astype(z.dtype)
    else:
        dgamma = dbeta = None
    from jax.dtypes import float0
    dkey = np.zeros(jnp.shape(key), float0)
    return dx, dres, dbias, dgamma, dbeta, dkey


# Both y and z grads flow in practice (z feeds the next residual chain), so
# the public entry exposes the (y, z) pair under one custom_vjp.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _fbdrln_pair(x2d, res2d, bias, gamma, beta, key, p, scale, eps,
                 has_rng, interpret, block_n=None):
    (y, z), _ = _fbdrln_vjp_fwd(x2d, res2d, bias, gamma, beta, key, p,
                                scale, eps, has_rng, interpret, block_n)
    return y, z


_fbdrln_pair.defvjp(_fbdrln_vjp_fwd, _fbdrln_vjp_bwd)


def fused_bias_dropout_residual_ln_arrays(x, residual, bias, gamma, beta,
                                          key, p, eps, training, mode,
                                          block_n=None):
    """Array-level entry: x/residual [..., H] → (y, z) with
    z = residual + dropout(x + bias), y = LN(z) (or z when gamma is None).

    Dropout semantics mirror paddle's modes (reference
    python/paddle/fluid/layers/nn.py dropout): upscale_in_train scales kept
    values by 1/(1-p) at train time; downscale_in_infer keeps them unscaled
    at train and scales by (1-p) at eval. `block_n` overrides the row
    block (fused_block_rows autotune); None uses the deterministic
    chooser."""
    shape = x.shape
    hdim = shape[-1]
    n = 1
    for s in shape[:-1]:
        n *= s
    x2d = x.reshape(n, hdim)
    res2d = residual.reshape(n, hdim)
    b2 = (bias.reshape(1, hdim) if bias is not None
          else jnp.zeros((1, hdim), x.dtype))
    g2 = gamma.reshape(1, hdim) if gamma is not None else None
    be2 = beta.reshape(1, hdim) if beta is not None else jnp.zeros(
        (1, hdim), x.dtype) if gamma is not None else None
    if not training:
        p_eff = 0.0
        scale = 1.0
        if mode == "downscale_in_infer":
            x2d = x2d * (1.0 - p)
            b2 = b2 * (1.0 - p)
    else:
        p_eff = float(p)
        if mode == "upscale_in_train":
            # p>=1 drops everything: threshold clamps to max and scale 0
            # keeps the arithmetic finite (matches the unfused dropout)
            scale = 1.0 / (1.0 - p) if p < 1.0 else 0.0
        else:
            scale = 1.0
    has_rng = jax.default_backend() == "tpu"
    interpret = jax.default_backend() != "tpu"
    if block_n is None:
        block_n = fused_block_rows(n, hdim, x2d.dtype)
    y, z = _fbdrln_pair(x2d, res2d, b2, g2, be2, key, p_eff, scale,
                        float(eps), has_rng, interpret, block_n)
    return y.reshape(shape), z.reshape(shape)


def fused_ln_geometry_ok(x):
    """Backend/shape eligibility for the fused dropout-LN chain, WITHOUT
    any feature-flag check — shared by fused_ln_shapes_ok (the
    FLAGS_use_fused_dropout_ln entry) and the FLAGS_fused_block decoder
    fusion, which gate the same kernel under independent switches."""
    hdim = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    if jax.default_backend() != "tpu":
        return n * hdim <= 64 * 1024  # keep interpret mode cheap
    return (hdim % 128 == 0 and hdim <= 16384
            and _fbdrln_block_n(n, hdim) is not None)


def fused_ln_shapes_ok(x):
    """Gate for the FLAGS_use_fused_dropout_ln entry points: the flag
    plus the shared backend/shape geometry check."""
    return flag("use_fused_dropout_ln") and fused_ln_geometry_ok(x)


# ---------------------------------------------------------------------------
# Fused AdamW update
#
# TPU-native counterpart of the reference's fused optimizer kernels
# (/root/reference/paddle/fluid/operators/optimizers/adam_op.cu AdamKernelMEM
# and operators/fused/ fused patterns): one Pallas program updates param +
# both moments in a single HBM pass with f32 master arithmetic, in-place via
# input_output_aliases (param/moment buffers are donated, never copied).
# ---------------------------------------------------------------------------


def _adamw_kernel(lr_ref, c_ref, p_ref, g_ref, m1_ref, m2_ref,
                  po_ref, m1o_ref, m2o_ref, *, b1, b2, eps, coeff):
    # bias corrections c1/c2 = 1-bᵗ are precomputed OUTSIDE the kernel:
    # Mosaic has no powf lowering, and they are scalars anyway
    lr = lr_ref[0].astype(jnp.float32)
    c1 = c_ref[0]
    c2 = c_ref[1]
    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    if coeff:
        p = p * (1.0 - lr * coeff)  # decoupled decay (AdamW)
    m1 = b1 * m1_ref[...] + (1.0 - b1) * g
    m2 = b2 * m2_ref[...] + (1.0 - b2) * g * g
    step = lr * (m1 / c1) / (jnp.sqrt(m2 / c2) + eps)
    po_ref[...] = (p - step).astype(po_ref.dtype)
    m1o_ref[...] = m1
    m2o_ref[...] = m2


def _adamw_rows_ok(numel):
    return numel % _LANES == 0


def fused_adamw_or_none(param, grad, lr, t, m1, m2, *, beta1, beta2,
                        epsilon, coeff, interpret=False):
    """Pallas fused Adam/AdamW step, or None for the jnp fallback.

    Used on TPU for lane-aligned params outside a GSPMD mesh step (inside a
    sharded step XLA owns layout/collectives; its fused elementwise update
    is already optimal there). `interpret=True` is the CPU test path."""
    from ..framework import state
    if not flag("use_fused_optimizer") or state.current_mesh() is not None:
        return None
    if jax.default_backend() != "tpu" and not interpret:
        return None
    numel = 1
    for s in param.shape:
        numel *= s
    if numel < _LANES or not _adamw_rows_ok(numel):
        return None

    rows = numel // _LANES
    bn = _fbdrln_block_n(rows, _LANES)
    if bn is None:
        return None  # no legal block shape — take the jnp fallback
    _note_update_path("pallas_fused_adamw")
    shape2d = (rows, _LANES)
    row_spec = pl.BlockSpec((bn, _LANES), lambda i: (i, _I0))
    lr_smem = pl.BlockSpec((1,), lambda i: (_I0,), memory_space=pltpu.SMEM)
    c_smem = pl.BlockSpec((2,), lambda i: (_I0,), memory_space=pltpu.SMEM)
    kern = functools.partial(_adamw_kernel, b1=beta1, b2=beta2,
                             eps=epsilon, coeff=coeff)
    po, m1o, m2o = _pallas_call(
        kern,
        grid=(rows // bn,),
        in_specs=[lr_smem, c_smem, row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(shape2d, param.dtype),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
        ],
        input_output_aliases={2: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(jnp.reshape(lr, (1,)).astype(jnp.float32),
      jnp.stack([1.0 - jnp.power(jnp.float32(beta1),
                                 jnp.asarray(t, jnp.float32)),
                 1.0 - jnp.power(jnp.float32(beta2),
                                 jnp.asarray(t, jnp.float32))]),
      param.reshape(shape2d), grad.astype(jnp.float32).reshape(shape2d),
      m1.reshape(shape2d), m2.reshape(shape2d))
    return (po.reshape(param.shape), m1o.reshape(param.shape),
            m2o.reshape(param.shape))


# ---------------------------------------------------------------------------
# Flash block-size autotune
#
# The kernels were hard-coded to 128×128 blocks; the best (block_q,
# block_k) depends on seq length / head_dim / dtype (bigger k-blocks
# amortize the q-block reload, bigger q-blocks amortize the K/V stream —
# until VMEM pressure or MXU tail effects bite). A one-shot timed sweep
# over {128, 256, 512} (respecting exact tiling and a VMEM budget) picks
# the blocks per (B·H, Tq, Tk, D, dtype, causal), caches the choice
# in-process, and persists it to <PADDLE_TPU_TELEMETRY_DIR>/
# flash_autotune.json so later processes (gang restarts, the bench child)
# skip the sweep entirely. Gated by FLAGS_flash_autotune_blocks; TPU only
# (interpret mode always uses the defaults).
# ---------------------------------------------------------------------------

_BLOCK_SWEEP = (128, 256, 512)
_AUTOTUNE_CACHE = {}       # key tuple -> (block_q, block_k)
_AUTOTUNE_FILE_LOADED = False


def _block_candidates(T):
    """Legal block sizes for a sequence axis of length T: sweep values
    that tile T exactly, else the single full-axis block (T < 128 shapes
    pass _shapes_ok only when T % 8 == 0, which is a legal sublane
    count)."""
    cands = [b for b in _BLOCK_SWEEP if b <= T and T % b == 0]
    return cands or [T]


def _autotune_key(bh, Tq, Tk, D, dtype, causal):
    return (int(bh), int(Tq), int(Tk), int(D), str(jnp.dtype(dtype)),
            bool(causal))


def _autotune_cache_path():
    import os
    d = os.environ.get("PADDLE_TPU_TELEMETRY_DIR", "")
    return os.path.join(d, "flash_autotune.json") if d else None


def _autotune_load():
    """Merge the persisted cache into the in-process one (once)."""
    global _AUTOTUNE_FILE_LOADED
    if _AUTOTUNE_FILE_LOADED:
        return
    _AUTOTUNE_FILE_LOADED = True
    path = _autotune_cache_path()
    if not path:
        return
    try:
        import json
        import os
        if not os.path.exists(path):
            return
        with open(path) as f:
            data = json.load(f)
        for key_s, blocks in data.items():
            parts = key_s.split("|")
            if len(parts) != 6:
                continue
            key = (int(parts[0]), int(parts[1]), int(parts[2]),
                   int(parts[3]), parts[4], parts[5] == "True")
            _AUTOTUNE_CACHE.setdefault(key, (int(blocks[0]),
                                             int(blocks[1])))
    except Exception:
        pass  # a torn/corrupt cache file must never break training


def _autotune_save():
    path = _autotune_cache_path()
    if not path:
        return
    try:
        import json
        import os
        payload = {"|".join(str(p) for p in key): list(blocks)
                   for key, blocks in _AUTOTUNE_CACHE.items()}
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: concurrent ranks race benignly
    except Exception:
        pass


def _untraced(fn, *args):
    """fn(*args) in a fresh thread. The sweeps are reached from inside a
    jit trace (the first trace of an attention shape) but must compile and
    time real executables; jax's trace state is thread-local, so a new
    thread starts untraced. (jax.ensure_compile_time_eval does not serve:
    a pallas_call evaluated under it binds program_id against the eval
    trace and raises.)"""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(1) as pool:
        return pool.submit(fn, *args).result()


def _best_of_two(fn, *args):
    """Seconds per call of jit(fn)(*args): compile + warm once, then the
    faster of two timed calls."""
    import time
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep_flash_blocks(bh, Tq, Tk, D, dtype, causal):
    """Time fwd+bwd for each legal (block_q, block_k) pair on synthetic
    data and return (fastest pair, {pair: ms}). Call through _untraced. A
    candidate inside the VMEM budget that fails to compile raises."""
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, bh, Tq, D), dtype)
    k = jnp.asarray(rs.randn(1, bh, Tk, D), dtype)
    v = jnp.asarray(rs.randn(1, bh, Tk, D), dtype)
    # VMEM budget: the fwd kernel holds q/acc blocks + full K/V + the
    # [bq, bk] score tile in f32; cap the score tile and the streamed
    # K/V copies well under the ~16 MB/core budget
    vmem_cap = 8 << 20
    timings = {}
    best = None
    for bq in _block_candidates(Tq):
        for bk in _block_candidates(Tk):
            foot = 4 * (bq * bk + 2 * Tk * D + 2 * Tq * D + 2 * bq * D)
            if foot > vmem_cap:
                continue

            def run(q, k, v, _bq=bq, _bk=bk):
                return _flash(q, k, v, None, causal, False, 0.0, _bq,
                              _bk).astype(jnp.float32).sum()

            dt = _best_of_two(jax.value_and_grad(run, argnums=(0, 1, 2)),
                              q, k, v)
            timings["%dx%d" % (bq, bk)] = round(dt * 1e3, 3)
            if best is None or dt < best[0]:
                best = (dt, bq, bk)
    if best is None:  # every candidate was over the VMEM budget
        return (min(128, Tq), min(128, Tk)), timings
    return (best[1], best[2]), timings


def flash_block_sizes(bh, Tq, Tk, D, dtype, causal):
    """(block_q, block_k) for this attention shape: in-process cache →
    persisted cache → timed sweep (TPU only). Defaults (128, 128) when
    autotune is off, the backend is not a TPU, or there is only one
    legal candidate anyway."""
    default = (min(128, int(Tq)), min(128, int(Tk)))
    if not flag("flash_autotune_blocks"):
        return default
    if jax.default_backend() != "tpu":
        return default
    key = _autotune_key(bh, Tq, Tk, D, dtype, causal)
    _autotune_load()
    hit = _AUTOTUNE_CACHE.get(key)
    if hit is not None:
        return hit
    cands = (len(_block_candidates(Tq)), len(_block_candidates(Tk)))
    if cands == (1, 1):
        _AUTOTUNE_CACHE[key] = default
        return default
    blocks, timings = _untraced(_sweep_flash_blocks, bh, Tq, Tk, D, dtype,
                                causal)
    _AUTOTUNE_CACHE[key] = blocks
    _autotune_save()
    try:
        from ..observability import journal
        journal.emit("flash_autotune", bh=int(bh), tq=int(Tq), tk=int(Tk),
                     d=int(D), dtype=str(jnp.dtype(dtype)),
                     causal=bool(causal), block_q=blocks[0],
                     block_k=blocks[1], timings_ms=timings)
    except Exception:
        pass
    return blocks


# --- fused dropout-LN row-block autotune (FLAGS_fused_block) ---------------
# Same scheme as the flash autotune: in-process cache → persisted
# <PADDLE_TPU_TELEMETRY_DIR>/fused_block_autotune.json → one timed sweep
# over the legal row blocks. The key is (rows, hdim, dtype); entries are
# consulted by fused_bias_dropout_residual_ln_arrays for every fused
# chain, so the decoder-block fusion and the plain fused-LN entry share
# one table. Gated by FLAGS_flash_autotune_blocks (one switch for all
# Pallas block sweeps); off-TPU the deterministic _fbdrln_block_n chooser
# stands.

_FBDRLN_SWEEP_CACHE = {}   # (n, hdim, dtype_str) -> block_n
_FBDRLN_FILE_LOADED = False


def _fused_block_cache_path():
    import os
    d = os.environ.get("PADDLE_TPU_TELEMETRY_DIR", "")
    return os.path.join(d, "fused_block_autotune.json") if d else None


def _fused_block_load():
    global _FBDRLN_FILE_LOADED
    if _FBDRLN_FILE_LOADED:
        return
    _FBDRLN_FILE_LOADED = True
    path = _fused_block_cache_path()
    if not path:
        return
    try:
        import json
        import os
        if not os.path.exists(path):
            return
        with open(path) as f:
            data = json.load(f)
        for key_s, bn in data.items():
            parts = key_s.split("|")
            if len(parts) != 3:
                continue
            _FBDRLN_SWEEP_CACHE.setdefault(
                (int(parts[0]), int(parts[1]), parts[2]), int(bn))
    except Exception:
        pass  # torn/corrupt cache must never break a train step


def _fused_block_save():
    path = _fused_block_cache_path()
    if not path:
        return
    try:
        import json
        import os
        payload = {"|".join(str(p) for p in key): bn
                   for key, bn in _FBDRLN_SWEEP_CACHE.items()}
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except Exception:
        pass


def _fbdrln_block_candidates(n, hdim):
    """All legal row blocks for an (n, hdim) fused-LN kernel (the values
    _fbdrln_block_n picks from, not just its first hit)."""
    cap = max(1, (2 << 20) // (4 * hdim))
    cands = [bn for bn in (256, 128, 64, 32, 16, 8)
             if bn <= cap and n % bn == 0]
    if not cands and n <= cap:
        cands = [n]
    return cands


def _sweep_fused_block_rows(n, hdim, dtype, cands):
    """Time the fused dropout-LN fwd+bwd pair for each row block in
    `cands`; returns (fastest, {block: ms}). Call through _untraced."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(n, hdim), dtype)
    res = jnp.asarray(rs.randn(n, hdim), dtype)
    bias = jnp.zeros((1, hdim), dtype)
    g2 = jnp.ones((1, hdim), dtype)
    b2 = jnp.zeros((1, hdim), dtype)
    key = jax.random.PRNGKey(0)
    timings = {}
    best = None
    for bn in cands:
        def run(x, _bn=bn):
            y, z = _fbdrln_pair(x, res, bias, g2, b2, key, 0.1,
                                1.0 / 0.9, 1e-5, True, False, _bn)
            return (y.astype(jnp.float32).sum()
                    + z.astype(jnp.float32).sum())

        dt = _best_of_two(jax.value_and_grad(run), x)
        timings[str(bn)] = round(dt * 1e3, 3)
        if best is None or dt < best[0]:
            best = (dt, bn)
    return best[1], timings


def fused_block_rows(n, hdim, dtype):
    """Autotuned row block for the fused dropout-LN chain at (n, hdim,
    dtype), or None to use the deterministic chooser. TPU +
    FLAGS_flash_autotune_blocks only; the sweep times the full fwd+bwd
    pair (the fusion's real cost) per candidate and persists the pick."""
    if not flag("flash_autotune_blocks"):
        return None
    if jax.default_backend() != "tpu":
        return None
    key = (int(n), int(hdim), str(jnp.dtype(dtype)))
    _fused_block_load()
    hit = _FBDRLN_SWEEP_CACHE.get(key)
    if hit is not None:
        return hit
    cands = _fbdrln_block_candidates(n, hdim)
    if len(cands) <= 1:
        bn = cands[0] if cands else None
        if bn is not None:
            _FBDRLN_SWEEP_CACHE[key] = bn
        return bn
    block_n, timings = _untraced(_sweep_fused_block_rows, n, hdim, dtype,
                                 cands)
    _FBDRLN_SWEEP_CACHE[key] = block_n
    _fused_block_save()
    try:
        from ..observability import journal
        journal.emit("fused_block_autotune", n=int(n), hdim=int(hdim),
                     dtype=str(jnp.dtype(dtype)), block_n=block_n,
                     timings_ms=timings)
    except Exception:
        pass
    return block_n


# Which attention implementation actually traced — incremented at trace
# time, so after one compiled step the counters say whether the hot model
# really hit the Pallas kernels (VERDICT r3: "log which path ran").
# Read/reset via attention_path_counts(); the same increments also feed
# the metrics registry (pt_attn_path_total{path=}) via _note_attn_path so
# bench.py and ptdoctor report from one source.
_ATTN_PATHS = {"flash": 0, "flash_dropout": 0, "xla_sdpa": 0,
               "xla_chunked": 0, "paged_flash": 0, "xla_paged": 0,
               "band_flash": 0,
               "latent_absorbed": 0, "latent_expanded": 0, "xla_latent": 0}

_ATTN_HELP = "Attention implementations traced, by path"


def _note_attn_path(path):
    """Bump both the resettable in-process dict (attention_path_counts)
    and the cumulative registry counter (pt_attn_path_total)."""
    _ATTN_PATHS[path] = _ATTN_PATHS.get(path, 0) + 1
    try:
        from ..observability import metrics
        metrics.counter("pt_attn_path_total", _ATTN_HELP,
                        labelnames=("path",)).labels(path).inc()
    except Exception:
        pass


def attention_path_counts(reset=False):
    out = dict(_ATTN_PATHS)
    if reset:
        for k in _ATTN_PATHS:
            _ATTN_PATHS[k] = 0
    return out


def _path_totals(name, help_, paths):
    """{path: cumulative count} of a `path`-labelled registry counter;
    paths that never traced read 0."""
    from ..observability import metrics
    out = {p: 0 for p in paths}
    c = metrics.counter(name, help_, labelnames=("path",))
    for labels, child in c._series():
        out[labels["path"]] = int(child.value)
    return out


def attention_path_totals():
    """Cumulative per-path totals from the metrics registry
    (pt_attn_path_total) — the registry-sourced flavor bench.py reports;
    survives attention_path_counts(reset=True) but not REGISTRY.reset()."""
    return _path_totals("pt_attn_path_total", _ATTN_HELP, _ATTN_PATHS)


# Same idea for the optimizer update: one increment per parameter whose
# Adam/AdamW update traced, by implementation (the Pallas fused kernel, or
# the jnp rule in optimizer/__init__.py that XLA fuses on its own).
_UPDATE_PATHS = ("pallas_fused_adamw", "xla_adamw")
_UPDATE_HELP = "Adam/AdamW parameter updates traced, by implementation"


def _note_update_path(path):
    from ..observability import metrics
    metrics.counter("pt_optimizer_update_path_total", _UPDATE_HELP,
                    labelnames=("path",)).labels(path).inc()


def update_path_totals():
    """Cumulative {path: count} from pt_optimizer_update_path_total."""
    return _path_totals("pt_optimizer_update_path_total", _UPDATE_HELP,
                        _UPDATE_PATHS)


# And for an expert layer's three grouped products: one increment a layer
# traced, by implementation (`incubate/moe.grouped_experts` picks).
_MOE_PATHS = ("held_grouped", "ragged_dot")
_MOE_HELP = "Expert layers' grouped products traced, by implementation"


def _note_moe_path(path):
    from ..observability import metrics
    metrics.counter("pt_moe_path_total", _MOE_HELP,
                    labelnames=("path",)).labels(path).inc()


def moe_path_totals():
    """Cumulative {path: count} from pt_moe_path_total."""
    return _path_totals("pt_moe_path_total", _MOE_HELP, _MOE_PATHS)


def flash_attention_or_none(query, key, value, attn_mask, is_causal,
                            dropout_p=0.0, rng=None):
    """Tensor-level gate: return flash-attention output, or None to signal
    the caller to take the plain XLA sdpa path.

    Training dropout stays ON the flash path: the keep/scale mask is
    generated inside the kernel from the hardware PRNG (per-tile seeding,
    regenerated in backward) — on CPU/interpret the bits slab is
    precomputed host-side (tiny test shapes only)."""
    if attn_mask is not None or not flag("use_flash_attention"):
        return None
    if dropout_p > 0.0 and (rng is None or dropout_p >= 1.0):
        # p>=1 drops everything — degenerate; the XLA path returns zeros
        return None
    q, k = raw(query), raw(key)
    if q.ndim != 4 or k.ndim != 4:
        return None
    backend = jax.default_backend()
    interpret = backend != "tpu"
    if not _shapes_ok(q, k, bool(is_causal), interpret):
        return None
    if dropout_p > 0.0 and interpret and not flag(
            "flash_dropout_interpret"):
        # interpret-mode Pallas is an emulator — fine for kernel tests,
        # far too slow for a CPU train loop; real TPU always routes here
        return None
    rng_arr = None
    if dropout_p > 0.0:
        key_arr = rng._data if hasattr(rng, "_data") else rng
        if interpret:
            B, H, Tq, _ = q.shape
            Tk = k.shape[2]
            rng_arr = jax.random.bits(key_arr, (B * H, Tq, Tk), jnp.uint32)
        else:
            rng_arr = jax.random.bits(key_arr, (1,), jnp.uint32
                                      ).astype(jnp.int32)
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if interpret:
        block_q, block_k = min(128, Tq), min(128, Tk)
    else:
        block_q, block_k = flash_block_sizes(B * H, Tq, Tk, D, q.dtype,
                                             bool(is_causal))
    _note_attn_path("flash_dropout" if dropout_p > 0.0 else "flash")
    return _flash_op(query, key, value, rng_arr, causal=bool(is_causal),
                     interpret=interpret, dropout_p=float(dropout_p),
                     block_q=int(block_q), block_k=int(block_k))


# ---------------------------------------------------------------------------
# Fused paged-decode attention (the serving megakernel)
#
# One Pallas call a layer on the STACKED cache [L, B, H, T, D], with the
# layer index as a scalar-prefetch operand: length-masked flash-style
# attention of one new token a slot over the paged KV cache.
#
# The grid is (H // heads, n): a grid step carries `heads` heads of one slot
# (all of them, where a VMEM budget allows: `_paged_block`) for one block of
# `block_k` key rows, and the second axis walks a WORK LIST of the (slot,
# block) pairs that hold a live row — slot 0's blocks 0 .. lens[0] //
# block_k, then slot 1's, and so on (`_paged_work`, computed from `lens` in
# the jitted step). Its length n is the grid's dynamic bound, so a call is
# sum_b (lens[b] // block_k + 1) steps over the slots that hold a request —
# at most B * T // block_k — and never a step that fetches or computes
# nothing; the executable is still compiled once. A slot with lens == 0
# holds no request (a prompt is at least one token, and the serving engine
# keeps a released slot's lens at 0): it has no entry in the list, no row
# of it is read or written, and its output rows are zeros. A call in which
# every slot is empty is a grid of no steps. Folded into the same pass:
#   * the new token: its score and its value start the running softmax at
#     a slot's first block (for an int8 cache after quantize_kv's exact
#     absmax rule, as the einsum path attends the row it has just
#     written), so no block has the row substituted; at the slot's last
#     block the 16-row (int8: 32-row) tile group that holds the append row
#     is read from the fetched block, the row put in, and that group alone
#     written back through the cache outputs;
#   * int8 dequantization: k_scale multiplies the QK scores and v_scale the
#     softmax probabilities (per-key scalars commute with the row dot
#     products), so the dequantized cache is never materialised.
# The products are head-batched `dot_general`s in the query's dtype when
# that is bfloat16 (K and V are bfloat16 or int8, which it holds exactly;
# the probabilities are rounded to it, as in `_paged_gqa_kernel`), else in
# float32; scores, running max and sum, and the accumulator are float32.
# The cache operands (k, v, and both scales when quantized) are aliased to
# their outputs (input_output_aliases): the call updates the stacked buffer
# in place, and L chained calls thread one HBM buffer through a decode step.
# Every row but the appended one, of every layer, keeps its contents. Rows
# from a slot's length on are whatever was there before (zeros, or a
# previous tenant's rows) and are masked out of every read.
#
# Dispatch: paged_decode_attention_or_none (shape legality,
# FLAGS_paged_flash_interpret for the CPU emulator). An ineligible shape,
# or the CPU without the emulator, takes the einsum of
# inference/serving/cache.LayerCacheView.attend
# (pt_attn_path_total{path=xla_paged}).
# ---------------------------------------------------------------------------

_KV_QUANT_EPS = 1e-8  # quantize_kv's zero-row guard (cache.py)
_PAGED_BLOCK_BYTES = 512 << 10  # a K or V block; twice two are in flight


def _paged_heads(H, block_k, D, itemsize):
    """Heads a grid step carries: the most, of H's divisors, whose block of
    `block_k` key rows stays within _PAGED_BLOCK_BYTES."""
    fit = max(1, _PAGED_BLOCK_BYTES // (block_k * D * itemsize))
    return max(h for h in range(1, min(H, fit) + 1) if H % h == 0)


def _paged_block(T, H, D, dtype, interpret):
    """Key rows a grid step reads from a T-deep cache of H heads of D in
    `dtype`, or None when the shape is ineligible (the caller takes the
    einsum path): the largest block that divides T and holds EVERY head
    within _PAGED_BLOCK_BYTES — K and V blocks, double-buffered, are then
    2 MB in flight — and, where not even the smallest holds them all, the
    smallest (`_paged_heads` then cuts the heads). Fewer, fatter steps
    amortize a step's fixed cost; reads round up to one block.

    Compiled for the TPU a block is a multiple of 128 rows or the whole of
    a short T: the scale rows of an int8 cache are blocked along their
    LANE axis, where Mosaic takes a multiple of 128 or the full axis, and
    int8 tiles want 32 sublanes. The emulator has no tiling, so CPU tests
    may cross blocks at small T."""
    if interpret:
        blocks = [b for b in (128, 64, 32, 16, 8) if T % b == 0]
    elif T % 128 == 0:
        blocks = [b for b in (1024, 512, 256, 128) if T % b == 0]
    else:
        blocks = [T] if T < 128 and T % 32 == 0 else []
    if not blocks:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    for b in blocks:
        if H * b * D * itemsize <= _PAGED_BLOCK_BYTES:
            return b
    return blocks[-1]


def _paged_work(lens, T, block_k):
    """The work list of a call: (slot, block, n), int32 [B * T // block_k]
    twice and a scalar. Entry i < n is the i-th (slot, block) pair in slot
    order, a slot's blocks 0 .. min(lens, T - 1) // block_k in turn, and
    no block of an EMPTY slot (lens == 0: it holds no request, the serving
    engine's one encoding of that); the entries from n on are never
    visited, and n is 0 where every slot is empty."""
    return _work_list(jnp.where(lens > 0,
                                jnp.minimum(lens, T - 1) // block_k + 1, 0),
                      T // block_k)


def _live_work(live, R, block_k):
    """`_paged_work` of a cache whose slots hold `live` rows each, int32
    [B] and at least 1 (the new token's row is one): a slot's blocks 0 ..
    (live - 1) // block_k, n the sum of their counts."""
    return _work_list((live - 1) // block_k + 1, R // block_k)


def _work_list(nblk, per_slot):
    """(slot, block, n) of `nblk` [B] blocks a slot, at most `per_slot`."""
    end = jnp.cumsum(nblk)
    i = jnp.arange(nblk.shape[0] * per_slot, dtype=jnp.int32)
    before = end[None, :] <= i[:, None]       # slots wholly before entry i
    slot = jnp.minimum(jnp.sum(before, axis=1), nblk.shape[0] - 1)
    blk = i - jnp.sum(jnp.where(before, nblk[None, :], 0), axis=1)
    return slot.astype(jnp.int32), blk.astype(jnp.int32), end[-1]


def _kernel_quantize_row(x):
    """In-kernel int8 row quantization — MUST mirror
    inference.serving.cache.quantize_kv exactly (same absmax, eps floor,
    /127.0, round-to-nearest-even) or fused vs einsum engines lose greedy
    parity. x: [.., d] f32 → ([.., d] int8, [.., 1] f32 scale)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _KV_QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _paged_core(lens_ref, slot_ref, blk_ref, q_ref, nk_ref, nv_ref, k_ref,
                v_ref, ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref,
                vso_ref, acc_ref, m_ref, l_ref, *, block_k, t_max,
                sm_scale):
    """Grid (H // heads, n); this body runs once for entry i of the work
    list: block j of slot b, `heads` heads at once (every ref has them as
    its leading axis: q/new k/new v/out [heads, 1, D], K/V [heads, block_k,
    D], scales [heads, 1, block_k]). State (acc/m/l) lives in VMEM scratch
    across a slot's blocks: the new token starts it at j == 0, every block
    with a live row adds to it, and the slot's last block — the one that
    holds the append row — writes the row's tile group back and the
    output out. The cache outputs hold that group for every step of the
    slot (their index map does not move with j)."""
    i = pl.program_id(1)
    b, j = slot_ref[i], blk_ref[i]
    cl = jnp.minimum(lens_ref[b], t_max - 1)  # append row (the einsum path's
    ja = cl // block_k                        # index clamp); its block
    off = cl - j * block_k
    quantized = ks_ref is not None
    hb, _, d = k_ref.shape
    group = ko_ref.shape[1]
    q = q_ref[...]                                            # [hb, 1, d]
    cd = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32

    def new_rows():
        if quantized:
            nkq, nks = _kernel_quantize_row(nk_ref[...].astype(jnp.float32))
            nvq, nvs = _kernel_quantize_row(nv_ref[...].astype(jnp.float32))
            return nkq, nvq, nks, nvs
        return (nk_ref[...].astype(ko_ref.dtype),
                nv_ref[...].astype(vo_ref.dtype), None, None)

    @pl.when(j == 0)
    def _init():
        nkq, nvq, nks, nvs = new_rows()
        s = jnp.sum(q.astype(jnp.float32) * nkq.astype(jnp.float32),
                    axis=-1, keepdims=True) * sm_scale        # [hb, 1, 1]
        v = nvq.astype(jnp.float32)
        if quantized:
            s, v = s * nks, v * nvs
        m_ref[...] = jnp.broadcast_to(s, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = v

    @pl.when(off > 0)          # a row before the append row: a live one
    def _step():
        live = jax.lax.broadcasted_iota(
            jnp.int32, (hb, 1, block_k), 2) < off
        # rows from cl on were written by no tenant of this slot (stale
        # rows; garbage in a test): a NaN there would get through 0 * NaN,
        # so select them to zero rather than relying on p == 0
        rows = jax.lax.broadcasted_iota(jnp.int32, (hb, block_k, d), 1)
        v = v_ref[...].astype(cd)
        v = jnp.where(rows < off, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q.astype(cd), k_ref[...].astype(cd),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [hb, 1, bk]
        if quantized:
            s = s * ks_ref[...]   # per-key k_scale commutes with the D-dot
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[...]   # fold v_scale into the probabilities
        p = jnp.where(live, p, 0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(cd), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # [hb, 1, d]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == ja)
    def _append_and_finish():
        nkq, nvq, nks, nvs = new_rows()
        r0 = pl.multiple_of((off // group) * group, group)
        sel = jax.lax.broadcasted_iota(
            jnp.int32, (hb, group, d), 1) == (off - r0)
        for new, old_ref, out_ref in ((nkq, k_ref, ko_ref),
                                      (nvq, v_ref, vo_ref)):
            out_ref[...] = jnp.where(
                sel, jnp.broadcast_to(new, sel.shape),
                old_ref[:, pl.ds(r0, group), :])
        if quantized:
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (hb, 1, block_k), 2) == off
            kso_ref[...] = jnp.where(lane, nks, ks_ref[...])
            vso_ref[...] = jnp.where(lane, nvs, vs_ref[...])
        # l >= 1 always: the new token is in it
        o_ref[...] = (acc_ref[...] / l_ref[:, :, :1]).astype(o_ref.dtype)


def _paged_f_kernel(lens_ref, layer_ref, slot_ref, blk_ref, q_ref, nk_ref,
                    nv_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, acc_ref,
                    m_ref, l_ref, **kw):
    del layer_ref  # read by the index maps only
    _paged_core(lens_ref, slot_ref, blk_ref, q_ref, nk_ref, nv_ref, k_ref,
                v_ref, None, None, o_ref, ko_ref, vo_ref, None, None,
                acc_ref, m_ref, l_ref, **kw)


def _paged_q_kernel(lens_ref, layer_ref, slot_ref, blk_ref, q_ref, nk_ref,
                    nv_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, ko_ref,
                    vo_ref, kso_ref, vso_ref, acc_ref, m_ref, l_ref, **kw):
    del layer_ref
    _paged_core(lens_ref, slot_ref, blk_ref, q_ref, nk_ref, nv_ref, k_ref,
                v_ref, ks_ref, vs_ref, o_ref, ko_ref, vo_ref, kso_ref,
                vso_ref, acc_ref, m_ref, l_ref, **kw)


def _paged_decode(q, k_cache, v_cache, lens, new_k, new_v, k_scale,
                  v_scale, *, layer, block_k, interpret):
    """Run the megakernel on layer `layer` of the stacked cache.
    q/new_k/new_v: [B, H, 1, D]; caches [L, B, H, T, D] (+f32 scales
    [L, B, H, T] when int8); `layer` an int or an int32 scalar. Returns
    (out, k_cache', v_cache', k_scale'|None, v_scale'|None): the caches
    are the operands updated in place (aliased), one row a (slot, head) of
    the slots with lens > 0; an empty slot's rows stay and its `out` is 0.

    The layer rides as a scalar-prefetch operand, not as a constant folded
    into the index maps, and the work list's length as the grid's dynamic
    bound: every layer of every decode step is then the same kernel,
    traced and lowered once."""
    B, H, _, D = q.shape
    T = k_cache.shape[3]
    quantized = k_scale is not None
    hb = _paged_heads(H, block_k, D, k_cache.dtype.itemsize)
    # the tile group written back: 16 sublanes hold a bf16 tile (and two
    # of float32), 32 an int8 one; the emulator's small blocks are one group
    group = min(32 if quantized else 16, block_k)
    lens = lens.astype(jnp.int32)
    slot, blk, n_work = _paged_work(lens, T, block_k)

    def _row(b, lens):
        return jnp.minimum(lens[b], T - 1)

    def kv_map(h, i, lens, layer, slot, blk):
        return (layer[0], slot[i], h, blk[i], _I0)

    def kv_out_map(h, i, lens, layer, slot, blk):
        return (layer[0], slot[i], h, _row(slot[i], lens) // group, _I0)

    def sc_map(h, i, lens, layer, slot, blk):
        return (layer[0], slot[i], h, _I0, blk[i])

    def sc_out_map(h, i, lens, layer, slot, blk):
        return (layer[0], slot[i], h, _I0, _row(slot[i], lens) // block_k)

    def tok_map(h, i, lens, layer, slot, blk):
        return (slot[i], h, _I0, _I0)

    kv_block = (None, None, hb, block_k, D)
    grp_block = (None, None, hb, group, D)
    # scales ride as [L, B, H, 1, T]: a block [hb, 1, block_k] then has the
    # heads as its leading axis, like the scores it multiplies
    sc_block = (None, None, hb, 1, block_k)
    tok_spec = pl.BlockSpec((None, hb, 1, D), tok_map)
    in_specs = [tok_spec, tok_spec, tok_spec,
                pl.BlockSpec(kv_block, kv_map),
                pl.BlockSpec(kv_block, kv_map)]
    out_specs = [tok_spec, pl.BlockSpec(grp_block, kv_out_map),
                 pl.BlockSpec(grp_block, kv_out_map)]
    out_shape = [jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
                 jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                 jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)]
    operands = [q, new_k, new_v, k_cache, v_cache]
    if quantized:
        sc_shape = k_scale.shape[:3] + (1, T)
        in_specs += [pl.BlockSpec(sc_block, sc_map)] * 2
        out_specs += [pl.BlockSpec(sc_block, sc_out_map)] * 2
        out_shape += [jax.ShapeDtypeStruct(sc_shape, jnp.float32)] * 2
        operands += [k_scale.reshape(sc_shape), v_scale.reshape(sc_shape)]
        kernel = _paged_q_kernel
    else:
        kernel = _paged_f_kernel
    kern = functools.partial(kernel, block_k=block_k, t_max=T,
                             sm_scale=float(D) ** -0.5)
    prefetch = [lens, jnp.asarray(layer, jnp.int32).reshape(1), slot, blk]
    n_tok = 3                          # q, new_k, new_v
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(H // hb, n_work),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((hb, 1, D), jnp.float32),
                        pltpu.VMEM((hb, 1, _LANES), jnp.float32),
                        pltpu.VMEM((hb, 1, _LANES), jnp.float32)])
    # cache operand i (after the prefetch and token operands) -> output
    # 1 + i (after `out`); the alias indices count the prefetch operands
    aliases = {len(prefetch) + n_tok + i: 1 + i
               for i in range(len(operands) - n_tok)}
    outs = _pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                        input_output_aliases=aliases,
                        interpret=interpret)(*prefetch, *operands)
    out, ko, vo, *scales = outs
    # an empty slot had no grid step: nothing wrote its output rows
    out = jnp.where((lens > 0)[:, None, None, None], out, 0)
    if quantized:
        return (out, ko, vo, scales[0].reshape(k_scale.shape),
                scales[1].reshape(v_scale.shape))
    return out, ko, vo, None, None


def _check_paged():
    """The float paged-decode kernel at a small but representative shape
    (stacked cache of 3 layers, eight heads a block, two blocks a slot;
    an empty slot, one whose append row opens the second block and one that
    has crossed into it), called for the middle layer: the output and that
    layer's cache are value-checked against the einsum oracle, and every
    row the call did not append — the other layers, the named layer's
    rows past each slot's length, the whole of the empty slot, whose output
    is 0 — must come back bit-identical."""
    L, B, H, T, D = 3, 3, 8, 256, 128
    layer = 1
    blk = _paged_block(T, H, D, jnp.float32, interpret=False)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32)
    nk = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32)
    nv = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32)
    k = jnp.asarray(rs.randn(L, B, H, T, D), jnp.float32)
    v = jnp.asarray(rs.randn(L, B, H, T, D), jnp.float32)
    lens = jnp.asarray([0, 128, 130], jnp.int32)

    @jax.jit
    def run(q):
        out, ko, vo, _, _ = _paged_decode(
            q, k, v, lens, nk, nv, None, None, layer=layer, block_k=blk,
            interpret=False)
        return out, ko, vo

    out, ko, vo = run(q)

    def wr(buf, new, ln):
        z = jnp.int32(0)
        return jax.lax.dynamic_update_slice(buf, new, (z, ln, z))

    kb = jax.vmap(wr)(k[layer], nk, lens)
    vb = jax.vmap(wr)(v[layer], nv, lens)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb) * (float(D) ** -0.5)
    valid = (jnp.arange(T)[None, None, None, :]
             <= lens[:, None, None, None])
    s = jnp.where(valid, s, jnp.float32(_NEG_INF))
    want = np.array(jnp.einsum("bhqk,bhkd->bhqd",
                               jax.nn.softmax(s, axis=-1), vb))
    # the empty slot on the host (an engine's start compiles nothing for
    # it): its output 0, its rows what they were
    empty = np.asarray(lens) == 0
    want[empty] = 0.0
    out_ok = np.allclose(np.asarray(out), want, rtol=2e-3, atol=2e-3)

    def stack_as_it_must_be(was, rows):
        was, rows = np.array(was), np.array(rows)
        rows[empty] = was[layer][empty]
        was[layer] = rows
        return was

    # the whole stacked cache, exactly: the call changes one row a
    # (slot, head) of the named layer and nothing else
    cache_ok = all(
        np.array_equal(np.asarray(got), stack_as_it_must_be(was, rows))
        for got, was, rows in ((ko, k, kb), (vo, v, vb)))
    if not (out_ok and cache_ok):
        raise PallasSelfCheckError(
            "paged-decode attention disagrees with the einsum oracle on "
            "%s: out ok=%s cache ok=%s max|out-want|=%.3e"
            % (jax.devices()[0].device_kind, out_ok, cache_ok,
               _max_err(out, want)))


def paged_decode_attention_or_none(q, k_cache, v_cache, lens, new_k,
                                   new_v, k_scale=None, v_scale=None, *,
                                   layer):
    """Gate + dispatch for the fused paged-decode attention kernel.

    Arrays only (the caller is inference/serving/cache.LayerCacheView.
    attend): q/new_k/new_v [B, H, 1, D], the STACKED
    caches [L, B, H, T, D] (+ scales [L, B, H, T] for int8), `layer` the
    layer this call attends and appends to, lens [B] int32 = live length
    per slot BEFORE this token, 0 for a slot that holds no request (left
    as it is, its output rows 0). Returns (out, k_cache', v_cache',
    k_scale', v_scale') — the stacked cache updated in place, carrying
    the appended token — or None when the caller must take its einsum
    (ineligible shape, or interpret mode without
    FLAGS_paged_flash_interpret). Bumps
    pt_attn_path_total{path=paged_flash} at trace time when it fires."""
    if q.ndim != 4 or q.shape[2] != 1 or k_cache.ndim != 5:
        return None
    B, H, _, D = q.shape
    T = k_cache.shape[3]
    interpret = jax.default_backend() != "tpu"
    blk = _paged_block(T, H, D, k_cache.dtype, interpret)
    if blk is None or D % 8 != 0 or D > 256:
        return None
    if interpret:
        if not flag("paged_flash_interpret"):
            return None
        if T > 1024 or B * H > 64 or D > 128:
            return None  # keep the emulator cheap (CPU tests/smoke only)
    _note_attn_path("paged_flash")
    return _paged_decode(q, k_cache, v_cache, lens, new_k, new_v, k_scale,
                         v_scale, layer=layer, block_k=blk,
                         interpret=interpret)


# ---------------------------------------------------------------------------
# Grouped-query kernels of models/decoder.py
#
# `paged_gqa_decode`: one new token a slot against the paged cache, G query
# heads sharing each key-value head. One kernel serves both kinds of layer:
# the caller gives, a slot, the row the new K/V is written to and the number
# of live rows — (min(lens, T-1), min(lens+1, T)) for a full layer,
# (lens mod W, min(lens+1, W)) for a window layer's ring, whose rows are in
# no order and need none under a softmax. The stacked cache is aliased to
# the output (PR 27's property) and only the 16-row group round the new row
# is written back. Grid (B, H_kv, rows / block); blocks past the live rows
# are clamped by the index map and skipped.
#
# `prefill_band_flash`: causal flash attention forward for a whole prompt,
# the G query heads of a key-value head folded into the rows of one block,
# with a grid axis over K/V blocks that visits only the blocks inside the
# causal band and (window layers) the sliding window: neither K nor V is
# ever whole in VMEM, so a 14k-token prompt fits. It is the one kernel a
# prompt takes on the generation server, GPT's included (G = 1, window 0,
# no name: `band_flash_attention_or_none`); the flash pair above, with its
# lse, dropout and backward, is the trainer's.
#
# A key row and a value row may differ in size (q and K dk wide, V and the
# output dv; the scores are scaled by dk ** -0.5), and a layer may have a
# SINK: one float32 logit a query head that joins the softmax's denominator
# and takes no value. The running softmax simply starts from it — running
# max = the sink, running sum = 1, accumulator 0 — where without one it
# starts from (-inf, 0, 0); nothing else in a kernel knows of it. The band
# kernel takes both as it is. For decode such a cache keeps K BY COLUMN
# ([.., dk, rows]: why, at `_paged_kv_decode`) and `_paged_kv_kernel` is
# the grouped-query decode over that layout: its grid walks a work list of
# the live (slot, block) pairs, as `_paged_core`'s does, with as many
# key-value heads of a slot a step as keep its K and V blocks within
# `_GQA_STEP_BYTES` (`_gqa_heads`): all 4 heads of a 512-row block
# (`_gqa_cols_block`), all 8 of a 128-row ring, one entry a slot — and
# with the new token folded into the softmax's start instead of
# substituted into a block. A call with dk != dv or
# with a sink bears a name of its own (`paged_kv_ring_decode`,
# `paged_kv_rows_decode`, `prefill_kv_band_flash`), so a trace tells it
# from the calls of a model that has neither, and a ring's call from a
# full layer's.
# ---------------------------------------------------------------------------

_APPEND_ROWS = 16     # a bf16 tile's sublanes: the group written back
# the most K and V blocks of one grid step of the by-column decode kernel
# hold (twice that is in flight): four heads of 1024 rows of a 192-wide key
# and a 128-wide value
_GQA_STEP_BYTES = 2560 << 10


def _gqa_block(rows, interpret):
    """Key rows a grid step of the grouped-query decode kernel reads."""
    if interpret:      # the emulator has no tiling: cross blocks early
        for b in (16, 32):
            if rows % b == 0 and rows // b >= 2:
                return b
        return rows if rows % _APPEND_ROWS == 0 else None
    for b in (1024, 512, 256, 128):
        if rows % b == 0:
            return b
    return None


# the least K and V a grid step of the by-column kernel reads: a smaller
# block reads less past a slot's live rows (half a block a slot), but below
# this a step's fixed cost and its short K runs outweigh that (longgen's
# full layer on the v5e: 1.10 ms a call at 512 rows = 1.3 MB a step, 1.16
# at 256, 1.26 at 1024)
_GQA_COLS_MIN_BYTES = 1 << 20


def _gqa_cols_block(rows, H, dk, dv, itemsize, interpret):
    """Key rows a grid step of the by-column decode kernel reads: the
    fewest, of the multiples of 128 that divide `rows`, whose K and V
    blocks for the step's heads hold _GQA_COLS_MIN_BYTES, else the most."""
    if interpret:
        return _gqa_block(rows, interpret)
    blocks = [b for b in (128, 256, 512, 1024) if rows % b == 0]
    for b in blocks:
        step = _gqa_heads(H, b, dk, dv, itemsize) * b * (dk + dv) * itemsize
        if step >= _GQA_COLS_MIN_BYTES:
            return b
    return blocks[-1] if blocks else None


def _gqa_heads(H, block_k, dk, dv, itemsize):
    """Key-value heads a grid step of `_paged_kv_kernel` carries: the
    most, of H's divisors, whose K and V blocks stay within
    _GQA_STEP_BYTES."""
    fit = max(1, _GQA_STEP_BYTES // (block_k * (dk + dv) * itemsize))
    return max(h for h in range(1, min(H, fit) + 1) if H % h == 0)


def _paged_gqa_kernel(row_ref, live_ref, layer_ref, q_ref, nk_ref, nv_ref,
                      k_ref, v_ref, o_ref, ko_ref, vo_ref, acc_ref, m_ref,
                      l_ref, *, block_k, sm_scale):
    del layer_ref                    # read by the index maps only
    b, j = pl.program_id(0), pl.program_id(2)
    ar, nl = row_ref[b], live_ref[b]         # append row; live rows
    jm, ja = (nl - 1) // block_k, ar // block_k
    d = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j == ja)
    def _append():
        r0 = pl.multiple_of(
            ((ar - j * block_k) // _APPEND_ROWS) * _APPEND_ROWS,
            _APPEND_ROWS)
        sel = jax.lax.broadcasted_iota(
            jnp.int32, (_APPEND_ROWS, d), 0) == (ar - j * block_k - r0)
        for new_ref, old_ref, out_ref in ((nk_ref, k_ref, ko_ref),
                                          (nv_ref, v_ref, vo_ref)):
            new = jax.lax.broadcast_in_dim(
                new_ref[...].astype(out_ref.dtype), sel.shape, (0, 1))
            out_ref[...] = jnp.where(
                sel, new, old_ref[pl.ds(r0, _APPEND_ROWS), :])

    @pl.when(j <= jm)
    def _step():
        pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)                       # [1, bk]
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_k, d), 0)
        new_row = rows == (ar - j * block_k)     # nowhere unless j == ja
        k = jnp.where(new_row, jax.lax.broadcast_in_dim(
            nk_ref[...].astype(k_ref.dtype), new_row.shape, (0, 1)),
            k_ref[...])
        v = jnp.where(new_row, jax.lax.broadcast_in_dim(
            nv_ref[...].astype(v_ref.dtype), new_row.shape, (0, 1)),
            v_ref[...])
        # rows past the live ones were written by no tenant of this slot:
        # a NaN there would get through 0 * NaN, so select them to zero
        v = jnp.where(rows + j * block_k < nl, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [G, bk]
        s = jnp.where(pos < nl, s, _NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)   # [G, 1]
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(pos < nl, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jax.lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        ell = jnp.max(l_ref[...], axis=1, keepdims=True)
        # l > 0 always: the appended row is live
        o_ref[...] = (acc_ref[...] / ell).astype(o_ref.dtype)


def _paged_gqa_decode(q, k_cache, v_cache, row, live, new_k, new_v, *,
                      layer, block_k, interpret):
    """q [B, H, G, D]; new_k/new_v [B, H, 1, D]; caches [L, B, H, R, D];
    row/live int32 [B]. Returns (out [B, H, G, D], k_cache', v_cache'),
    the caches being the operands updated in place."""
    B, H, G, D = q.shape
    R = k_cache.shape[3]

    def _last(b, live):
        return (live[b] - 1) // block_k

    def kv_map(b, h, j, row, live, layer):
        return (layer[0], b, h, jnp.minimum(j, _last(b, live)), _I0)

    def kv_out_map(b, h, j, row, live, layer):
        return (layer[0], b, h, row[b] // _APPEND_ROWS, _I0)

    def tok_map(b, h, j, row, live, layer):
        return (b, h, _I0, _I0)

    kv_spec = pl.BlockSpec((None, None, None, block_k, D), kv_map)
    out_kv_spec = pl.BlockSpec((None, None, None, _APPEND_ROWS, D),
                               kv_out_map)
    q_spec = pl.BlockSpec((None, None, G, D), tok_map)
    new_spec = pl.BlockSpec((None, None, 1, D), tok_map)
    n_prefetch = 3                     # row, live, layer
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, H, R // block_k),
        in_specs=[q_spec, new_spec, new_spec, kv_spec, kv_spec],
        out_specs=[q_spec, out_kv_spec, out_kv_spec],
        scratch_shapes=[pltpu.VMEM((G, D), jnp.float32),
                        pltpu.VMEM((G, _LANES), jnp.float32),
                        pltpu.VMEM((G, _LANES), jnp.float32)])
    kern = functools.partial(_paged_gqa_kernel, block_k=block_k,
                             sm_scale=float(D) ** -0.5)
    # cache operands (after the prefetch and the three token operands)
    # -> outputs 1 and 2
    aliases = {n_prefetch + 3: 1, n_prefetch + 4: 2}
    out, ko, vo = _pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        input_output_aliases=aliases, interpret=interpret,
        name="paged_gqa_decode")(
        row.astype(jnp.int32), live.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q, new_k, new_v,
        k_cache, v_cache)
    return out, ko, vo


def _paged_kv_kernel(row_ref, live_ref, layer_ref, slot_ref, blk_ref, q_ref,
                     nk_ref, nkc_ref, nv_ref, *refs, block_k, sm_scale, sink):
    """The grouped-query decode for a cache whose K is kept BY COLUMN (a
    key row dk wide that is no whole number of 128-lane tiles: see
    `_paged_kv_decode`), and a sink where the layer has one. Grid (H //
    heads, n): this body runs once for entry i of the work list, block j
    of slot b, `heads` key-value heads of the slot as the refs' leading
    axis. q [hb, G, dk]; K [hb, dk, block_k]: position along the lanes; V
    [hb, block_k, dv]; the new token's K as rows [hb, 1, dk] and, for the
    write-back into the by-column cache, as the columns of ALL the slot's
    heads side by side [dk, H] (a [.., dk, 1] operand a head would be
    padded to 128 lanes a column: 75 MB a ring layer in HBM), its V [hb,
    1, dv].

    As in `_paged_core`, state lives in VMEM scratch across a slot's
    blocks: the new token starts the running softmax (behind the sink,
    where there is one) at j == 0, the block that holds the append row
    writes the row's tile back (the cache outputs hold that tile for every
    entry of the slot), and the slot's last live block writes the output.
    The row the new token overwrites is masked out of the blocks: no block
    has the row substituted, so a step's only work on a whole block is its
    two products."""
    if sink:
        sink_ref, *refs = refs
    k_ref, v_ref, o_ref, ko_ref, vo_ref, acc_ref, m_ref, l_ref = refs
    del layer_ref                    # read by the index maps only
    i = pl.program_id(1)
    b, j = slot_ref[i], blk_ref[i]
    ar, nl = row_ref[b], live_ref[b]         # append row; live rows
    jm, ja = (nl - 1) // block_k, ar // block_k
    off = ar - j * block_k           # the append row within this block
    cols = ko_ref.shape[2]           # K positions written back: a lane tile
    first = pl.program_id(0) * ko_ref.shape[0]      # this step's first head
    cd = k_ref.dtype

    @pl.when(j == 0)
    def _init():
        # the sink, where there is one, is the softmax's first, valueless
        # term (max = the sink, sum = 1); then the new token
        m0 = sink_ref[...] if sink else jnp.full_like(m_ref, _NEG_INF)
        l0 = jnp.ones_like(l_ref) if sink else jnp.zeros_like(l_ref)
        s = jnp.sum(q_ref[...].astype(jnp.float32)
                    * nk_ref[...].astype(cd).astype(jnp.float32),
                    axis=2, keepdims=True) * sm_scale         # [hb, G, 1]
        m1 = jnp.maximum(m0, s)
        p = jnp.exp(s - m1)                                   # lane-broadcast
        m_ref[...] = m1
        l_ref[...] = l0 * jnp.exp(m0 - m1) + p
        acc_ref[...] = p[:, :, :1] \
            * nv_ref[...].astype(v_ref.dtype).astype(jnp.float32)

    @pl.when(j == ja)
    def _append():
        if cols == block_k:
            c0, old_k = 0, k_ref[...]
        else:
            c0 = pl.multiple_of((off // cols) * cols, cols)
            old_k = k_ref[:, :, pl.ds(c0, cols)]
        at = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 1)
        cols_of = nkc_ref[...].astype(cd).astype(jnp.float32)   # [dk, H]
        lane = jax.lax.broadcasted_iota(jnp.int32, cols_of.shape, 1)
        for h in range(ko_ref.shape[0]):
            # head h's column: the one lane kept, summed out (exact)
            col = jnp.sum(jnp.where(lane == first + h, cols_of, 0.0),
                          axis=1, keepdims=True).astype(cd)     # [dk, 1]
            ko_ref[h] = jnp.where(at == off - c0, jax.lax.broadcast_in_dim(
                col, at.shape, (0, 1)), old_k[h])
        r0 = pl.multiple_of((off // _APPEND_ROWS) * _APPEND_ROWS,
                            _APPEND_ROWS)
        at = jax.lax.broadcasted_iota(jnp.int32, vo_ref.shape, 1)
        vo_ref[...] = jnp.where(
            at == off - r0,
            jax.lax.broadcast_in_dim(nv_ref[...].astype(vo_ref.dtype),
                                     vo_ref.shape, (0, 1, 2)),
            v_ref[:, pl.ds(r0, _APPEND_ROWS), :])

    def step(last):
        pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2)                 # [1, 1, bk]
        ok = (pos < nl) & (pos != ar)    # live, and not the row overwritten
        v = v_ref[...]
        if last:
            # rows past the live ones were written by no tenant of this
            # slot: a NaN there would get through 0 * NaN, so select them
            # to zero (they lie in a slot's last live block alone)
            rows = jax.lax.broadcasted_iota(jnp.int32, v_ref.shape, 1)
            v = jnp.where(rows + j * block_k < nl, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale  # [hb, G, bk]
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=2, keepdims=True)
        l_prev = jnp.max(l_ref[...], axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = jax.lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1, 2))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1, 2))

    pl.when(j < jm)(lambda: step(False))

    @pl.when(j == jm)
    def _finish():
        step(True)
        ell = jnp.max(l_ref[...], axis=2, keepdims=True)
        # l > 0 always: the new token is in it
        o_ref[...] = (acc_ref[...] / ell).astype(o_ref.dtype)


def _sink_lanes(sink, Hkv, G):
    """A sink a query head [Hkv * G] as the float32 [Hkv, G, 128] the
    kernels' running max starts from (lane-broadcast, like m and l)."""
    return jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(Hkv, G, 1), (Hkv, G, _LANES))


def _paged_kv_decode(q, k_cache, v_cache, row, live, new_k, new_v, *,
                     layer, block_k, interpret, sink=None, heads=None,
                     name="paged_kv_rows_decode"):
    """q [B, H, G, dk]; new_k [B, H, 1, dk], new_v [B, H, 1, dv]; K cache
    BY COLUMN [L, B, H, dk, R], V cache [L, B, H, R, dv]; row/live int32
    [B], live >= 1 (the append row is live); sink float32 [H * G] or None.
    Returns (out [B, H, G, dv], k_cache', v_cache'), the caches being the
    operands updated in place.

    The grid is (H // heads, n) over `_live_work`'s list of the slots'
    live blocks, n its length as the dynamic bound, as in `_paged_decode`:
    no step for a block past a slot's live rows. A ring of one block is
    one entry a slot.

    Why by column: a bfloat16 array whose minor dimension is 192 is padded
    to 256 lanes in a row-major tiled layout, so XLA keeps [.., R, 192]
    with R minor instead — and a kernel that takes it row-major gets the
    whole stack copied in and out of every call (compiled for a described
    v5e: `copy` of bf16[5,192,8,128,192] before and after the custom
    call). [.., 192, R] is the same bytes with nothing to relayout, and
    the score product q . K is then the plain [G, dk] x [dk, rows]."""
    B, H, G, dk = q.shape
    R, dv = k_cache.shape[4], v_cache.shape[4]
    hb = heads or _gqa_heads(H, block_k, dk, dv, k_cache.dtype.itemsize)
    cols = min(_LANES, block_k)      # K positions written back
    row, live = row.astype(jnp.int32), live.astype(jnp.int32)
    slot, blk, n_work = _live_work(live, R, block_k)

    def k_map(h, i, row, live, layer, slot, blk):
        return (layer[0], slot[i], h, _I0, blk[i])

    def v_map(h, i, row, live, layer, slot, blk):
        return (layer[0], slot[i], h, blk[i], _I0)

    def k_out_map(h, i, row, live, layer, slot, blk):
        return (layer[0], slot[i], h, _I0, row[slot[i]] // cols)

    def v_out_map(h, i, row, live, layer, slot, blk):
        return (layer[0], slot[i], h, row[slot[i]] // _APPEND_ROWS, _I0)

    def tok_map(h, i, row, live, layer, slot, blk):
        return (slot[i], h, _I0, _I0)

    def sink_map(h, i, row, live, layer, slot, blk):
        return (h, _I0, _I0)

    def tok_spec(rows, d):
        return pl.BlockSpec((None, hb, rows, d), tok_map)

    def cols_map(h, i, row, live, layer, slot, blk):
        return (slot[i], _I0, _I0)

    tokens = [q, new_k, jnp.swapaxes(new_k[:, :, 0], 1, 2), new_v]
    tok_specs = [tok_spec(G, dk), tok_spec(1, dk),
                 pl.BlockSpec((None, dk, H), cols_map), tok_spec(1, dv)]
    if sink is not None:
        tokens.append(_sink_lanes(sink, H, G))
        tok_specs.append(pl.BlockSpec((hb, G, _LANES), sink_map))
    prefetch = [row, live, jnp.asarray(layer, jnp.int32).reshape(1), slot,
                blk]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(H // hb, n_work),
        in_specs=tok_specs + [
            pl.BlockSpec((None, None, hb, dk, block_k), k_map),
            pl.BlockSpec((None, None, hb, block_k, dv), v_map)],
        out_specs=[tok_spec(G, dv),
                   pl.BlockSpec((None, None, hb, dk, cols), k_out_map),
                   pl.BlockSpec((None, None, hb, _APPEND_ROWS, dv),
                                v_out_map)],
        scratch_shapes=[pltpu.VMEM((hb, G, dv), jnp.float32),
                        pltpu.VMEM((hb, G, _LANES), jnp.float32),
                        pltpu.VMEM((hb, G, _LANES), jnp.float32)])
    kern = functools.partial(_paged_kv_kernel, block_k=block_k,
                             sm_scale=float(dk) ** -0.5,
                             sink=sink is not None)
    # cache operands (after the prefetch and the token operands)
    # -> outputs 1 and 2
    n_in = len(prefetch) + len(tokens)
    aliases = {n_in: 1, n_in + 1: 2}
    out, ko, vo = _pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, G, dv), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        input_output_aliases=aliases, interpret=interpret, name=name)(
        *prefetch, *tokens, k_cache, v_cache)
    return out, ko, vo


def paged_gqa_decode_or_none(q, k_cache, v_cache, row, live, new_k, new_v,
                             *, layer, sink=None, ring=False, k_cols=False):
    """Gate + dispatch of the grouped-query paged decode kernels; None
    when the caller must take its einsum (ineligible shape, or the
    emulator without FLAGS_paged_flash_interpret). `k_cols`: the K cache
    is kept by column ([L, B, H, dk, R]: `_paged_kv_decode`, which also
    takes the `sink`); `ring` says that the layer is a window layer's
    ring, which names that kernel's call and nothing else."""
    if q.ndim != 4 or k_cache.ndim != 5:
        return None
    B, H, G, dk = q.shape
    R, dv = v_cache.shape[3], v_cache.shape[4]
    interpret = jax.default_backend() != "tpu"
    blk = _gqa_cols_block(R, H, dk, dv, q.dtype.itemsize, interpret) \
        if k_cols else _gqa_block(R, interpret)
    if blk is None or k_cache.dtype != q.dtype:
        return None
    if not k_cols and (sink is not None or dk != dv):
        return None
    if interpret:
        if not flag("paged_flash_interpret") or B * H > 64 \
                or max(dk, dv) > 128:
            return None
    elif dv % 128 != 0 or dk % (16 if k_cols else 128) != 0:
        return None
    if k_cols:
        _note_attn_path("paged_kv_work")
        return _paged_kv_decode(
            q, k_cache, v_cache, row, live, new_k, new_v, layer=layer,
            block_k=blk, interpret=interpret, sink=sink,
            name="paged_kv_ring_decode" if ring else "paged_kv_rows_decode")
    _note_attn_path("paged_gqa")
    return _paged_gqa_decode(q, k_cache, v_cache, row, live, new_k, new_v,
                             layer=layer, block_k=blk, interpret=interpret)


def _gqa_oracle(q, k, v, ok, sink=None):
    """softmax(q.k^T / sqrt(dk), masked by ok [.., Tq, Tk]) . v with q
    [B, H, G, Tq, dk], k [B, H, Tk, dk] and v [B, H, Tk, dv], in float32;
    `sink` [H * G]: one more column of the scores, dropped after the
    softmax."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (float(q.shape[-1]) ** -0.5)
    s = jnp.where(ok, s, _NEG_INF)
    if sink is not None:
        col = sink.astype(jnp.float32).reshape(1, q.shape[1], q.shape[2],
                                               1, 1)
        s = jnp.concatenate(
            [s, jnp.broadcast_to(col, s.shape[:-1] + (1,))], -1)
    p = jax.nn.softmax(s, axis=-1)[..., :k.shape[2]]
    return jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))


def _check_paged_gqa():
    """The grouped-query decode kernel on a full layer (ragged lengths,
    one slot at the wall) and on a ring that has wrapped, against the
    einsum; every row the call did not append must come back unchanged."""
    L, B, H, G, D, R = 2, 3, 2, 8, 128, 2048
    rs = np.random.RandomState(0)
    arr = lambda *s: jnp.asarray(rs.randn(*s), jnp.bfloat16)  # noqa: E731
    q, nk, nv = arr(B, H, G, D), arr(B, H, 1, D), arr(B, H, 1, D)
    k, v = arr(L, B, H, R, D), arr(L, B, H, R, D)
    lens = jnp.asarray([0, 1300, 5000], jnp.int32)
    run = jax.jit(functools.partial(
        _paged_gqa_decode, layer=1, block_k=_gqa_block(R, False),
        interpret=False))
    for ring in (False, True):
        if ring:
            row, live = lens % R, jnp.minimum(lens + 1, R)
        else:
            row = jnp.minimum(lens, R - 1)
            live = jnp.minimum(lens + 1, R)
        out, ko, vo = run(q, k, v, row, live, nk, nv)
        slots = jnp.arange(B)
        kb = k.at[1, slots, :, row].set(nk[:, :, 0])
        vb = v.at[1, slots, :, row].set(nv[:, :, 0])
        ok = (jnp.arange(R)[None, :] < live[:, None])[:, None, None, None]
        want = _gqa_oracle(q[:, :, :, None], kb[1], vb[1], ok)[:, :, :, 0]
        if not (np.allclose(np.asarray(out, np.float32), np.asarray(want),
                            rtol=2e-2, atol=2e-2)
                and np.array_equal(np.asarray(ko), np.asarray(kb))
                and np.array_equal(np.asarray(vo), np.asarray(vb))):
            raise PallasSelfCheckError(
                "grouped-query paged decode (ring=%s) disagrees with the "
                "einsum on %s: max|out-want|=%.3e" % (
                    ring, jax.devices()[0].device_kind,
                    _max_err(out, want)))


def _check_paged_gqa_sink():
    """The by-column kernel at a 192-wide key and a 128-wide value with a
    sink, against the einsum: over the work list of a slot's 1024-row
    blocks (ragged lengths, an empty slot, one at the wall), and over a
    wrapped 128-row ring, one entry a slot; every row the call did not
    append must come back unchanged."""
    L, B, H, G, dk, dv = 2, 3, 2, 8, 192, 128
    rs = np.random.RandomState(0)
    arr = lambda *s: jnp.asarray(rs.randn(*s), jnp.bfloat16)  # noqa: E731
    q, nk, nv = arr(B, H, G, dk), arr(B, H, 1, dk), arr(B, H, 1, dv)
    sink = jnp.asarray(rs.randn(H * G), jnp.float32)
    lens = jnp.asarray([0, 1300, 5000], jnp.int32)
    run = jax.jit(_paged_kv_decode,
                  static_argnames=("layer", "block_k", "interpret"))
    for R, ring in ((2048, False), (128, True)):
        k, v = arr(L, B, H, dk, R), arr(L, B, H, R, dv)
        row = lens % R if ring else jnp.minimum(lens, R - 1)
        live = jnp.minimum(lens + 1, R)
        out, ko, vo = run(q, k, v, row, live, nk, nv, sink=sink, layer=1,
                          block_k=_gqa_cols_block(R, H, dk, dv, 2, False),
                          interpret=False)
        slots = jnp.arange(B)
        kb = k.at[1, slots, :, :, row].set(nk[:, :, 0])
        vb = v.at[1, slots, :, row].set(nv[:, :, 0])
        ok = (jnp.arange(R)[None, :] < live[:, None])[:, None, None, None]
        want = _gqa_oracle(q[:, :, :, None], jnp.swapaxes(kb[1], 2, 3),
                           vb[1], ok, sink)[:, :, :, 0]
        if not (np.allclose(np.asarray(out, np.float32), np.asarray(want),
                            rtol=2e-2, atol=2e-2)
                and np.array_equal(np.asarray(ko), np.asarray(kb))
                and np.array_equal(np.asarray(vo), np.asarray(vb))):
            raise PallasSelfCheckError(
                "by-column paged decode (%d rows) disagrees with the "
                "einsum on %s: max|out-want|=%.3e" % (
                    R, jax.devices()[0].device_kind, _max_err(out, want)))


# ---------------------------------------------------------------------------
# The absorbed decode of latent attention (models/decoder.py, kind "latent")
#
# The cache keeps ONE row a token a layer, (normed latent | rotary part),
# that is key and value at once for every query head: `c [L, B, T, r]` by
# row (r = 512: four whole lane tiles) and `kr [L, B, dr, T]` by column
# (dr = 64: the positions along the lanes), see serving/cache.py for why
# two arrays. The query arrives absorbed, q_lat [B, H, r] and q_rope
# [B, H, dr], and a head's score on a row is (q_lat . c + q_rope . kr) *
# scale, its output the probabilities times c.
#
# `paged_latent_decode` is `_paged_core`'s way round that cache: the grid
# walks a work list of the live (slot, block) pairs — no block of an empty
# slot (lens == 0), none past a slot's rows — every head of the slot in the
# step; ONE block of rows of c is read a step and serves the scores and
# the values; the new token starts the running softmax at a slot's first
# block and its row goes back, with the 16-row group of c and the 128-lane
# tile of kr round it, through outputs aliased to the cache at the slot's
# last. The caller states the scale: the row is wider than the head the
# scale belongs to.
# ---------------------------------------------------------------------------

def _paged_latent_kernel(lens_ref, layer_ref, slot_ref, blk_ref, ql_ref,
                         qr_ref, nc_ref, nkr_ref, nkc_ref, c_ref, kr_ref,
                         o_ref, co_ref, kro_ref, acc_ref, m_ref, l_ref, *,
                         block_k, t_max, sm_scale):
    """Grid (n,); this body runs once for entry i of the work list: block j
    of slot b. q_lat [H, r], q_rope [H, dr]; the new row's latent [1, r],
    its rotary part as a row [1, dr] and as a column broadcast over a lane
    tile [dr, cols]; c [block_k, r]; kr [dr, block_k]. State (acc / m / l)
    lives in VMEM scratch across a slot's blocks, as in `_paged_core`."""
    del layer_ref                    # read by the index maps only
    i = pl.program_id(0)
    b, j = slot_ref[i], blk_ref[i]
    cl = jnp.minimum(lens_ref[b], t_max - 1)    # the append row
    ja = cl // block_k                          # its block
    off = cl - j * block_k
    cd = c_ref.dtype
    cols = kro_ref.shape[1]
    ql, qr = ql_ref[...], qr_ref[...]

    @pl.when(j == 0)
    def _init():
        nc = nc_ref[...].astype(cd).astype(jnp.float32)         # [1, r]
        nkr = nkr_ref[...].astype(cd).astype(jnp.float32)       # [1, dr]
        s = (jnp.sum(ql.astype(jnp.float32) * nc, axis=1, keepdims=True)
             + jnp.sum(qr.astype(jnp.float32) * nkr, axis=1,
                       keepdims=True)) * sm_scale               # [H, 1]
        m_ref[...] = jnp.broadcast_to(s, m_ref.shape)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(nc, acc_ref.shape)

    def step(last):
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        live = pos < off             # before the append row: a live one
        c = c_ref[...]
        s = (jax.lax.dot_general(
            ql, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qr, kr_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)) * sm_scale  # [H, bk]
        s = jnp.where(live, s, _NEG_INF)
        if last:
            # rows from the append row on were written by no tenant of
            # this slot: a NaN there would get through 0 * NaN, so select
            # them to zero (they lie in a slot's last block alone)
            rows = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
            c = jnp.where(rows < off, c, jnp.zeros_like(c))
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(cd), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [H, r]
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    pl.when(j < ja)(lambda: step(False))
    pl.when((j == ja) & (off > 0))(lambda: step(True))

    @pl.when(j == ja)
    def _append_and_finish():
        r0 = pl.multiple_of((off // _APPEND_ROWS) * _APPEND_ROWS,
                            _APPEND_ROWS)
        at = jax.lax.broadcasted_iota(jnp.int32, co_ref.shape, 0)
        co_ref[...] = jnp.where(
            at == off - r0,
            jnp.broadcast_to(nc_ref[...].astype(cd), co_ref.shape),
            c_ref[pl.ds(r0, _APPEND_ROWS), :])
        if cols == block_k:
            c0, old = 0, kr_ref[...]
        else:
            c0 = pl.multiple_of((off // cols) * cols, cols)
            old = kr_ref[:, pl.ds(c0, cols)]
        lane = jax.lax.broadcasted_iota(jnp.int32, kro_ref.shape, 1)
        kro_ref[...] = jnp.where(lane == off - c0,
                                 nkc_ref[...].astype(cd), old)
        # l >= 1 always: the new token is in it
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def _paged_latent_decode(q_lat, q_rope, c_cache, kr_cache, lens, new_c,
                         new_kr, *, layer, block_k, scale, interpret):
    """q_lat [B, H, r], q_rope [B, H, dr]; the new rows' latents new_c
    [B, r] and rotary parts new_kr [B, dr]; c_cache [L, B, T, r], kr_cache
    [L, B, dr, T]; lens int32 [B], each slot's rows BEFORE this token, 0
    for a slot that holds no request. Returns (out [B, H, r], c_cache',
    kr_cache'): the caches are the operands updated in place (aliased), one
    row a slot with lens > 0; an empty slot's rows stay and its `out` is 0.
    The layer rides as a scalar-prefetch operand and the work list's
    length as the grid's dynamic bound, as in `_paged_decode`."""
    B, H, r = q_lat.shape
    dr, T = kr_cache.shape[2], kr_cache.shape[3]
    cols = min(_LANES, block_k)      # positions of kr written back
    lens = lens.astype(jnp.int32)
    slot, blk, n_work = _paged_work(lens, T, block_k)

    def _row(b, lens):
        return jnp.minimum(lens[b], T - 1)

    def tok_map(i, lens, layer, slot, blk):
        return (slot[i], _I0, _I0)

    def c_map(i, lens, layer, slot, blk):
        return (layer[0], slot[i], blk[i], _I0)

    def kr_map(i, lens, layer, slot, blk):
        return (layer[0], slot[i], _I0, blk[i])

    def c_out_map(i, lens, layer, slot, blk):
        return (layer[0], slot[i], _row(slot[i], lens) // _APPEND_ROWS, _I0)

    def kr_out_map(i, lens, layer, slot, blk):
        return (layer[0], slot[i], _I0, _row(slot[i], lens) // cols)

    def tok_spec(rows, d):
        return pl.BlockSpec((None, rows, d), tok_map)

    dt = c_cache.dtype
    tokens = [q_lat, q_rope, new_c[:, None, :], new_kr[:, None, :],
              jnp.broadcast_to(new_kr.astype(dt)[:, :, None], (B, dr, cols))]
    prefetch = [lens, jnp.asarray(layer, jnp.int32).reshape(1), slot, blk]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_work,),
        in_specs=[tok_spec(H, r), tok_spec(H, dr), tok_spec(1, r),
                  tok_spec(1, dr), tok_spec(dr, cols),
                  pl.BlockSpec((None, None, block_k, r), c_map),
                  pl.BlockSpec((None, None, dr, block_k), kr_map)],
        out_specs=[tok_spec(H, r),
                   pl.BlockSpec((None, None, _APPEND_ROWS, r), c_out_map),
                   pl.BlockSpec((None, None, dr, cols), kr_out_map)],
        scratch_shapes=[pltpu.VMEM((H, r), jnp.float32),
                        pltpu.VMEM((H, _LANES), jnp.float32),
                        pltpu.VMEM((H, _LANES), jnp.float32)])
    kern = functools.partial(_paged_latent_kernel, block_k=block_k,
                             t_max=T, sm_scale=float(scale))
    # cache operands (after the prefetch and the token operands)
    # -> outputs 1 and 2
    n_in = len(prefetch) + len(tokens)
    out, co, kro = _pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
                   jax.ShapeDtypeStruct(c_cache.shape, dt),
                   jax.ShapeDtypeStruct(kr_cache.shape, dt)],
        input_output_aliases={n_in: 1, n_in + 1: 2}, interpret=interpret,
        name="paged_latent_decode")(*prefetch, *tokens, c_cache, kr_cache)
    # an empty slot had no grid step: nothing wrote its output rows
    return jnp.where((lens > 0)[:, None, None], out, 0), co, kro


def paged_latent_decode_or_none(q, c_cache, kr_cache, lens, new, *, layer,
                                scale):
    """Gate + dispatch of the latent decode kernel. q [B, H, r + dr], the
    absorbed query (latent part | rotary part); `new` [B, r + dr], the new
    token's row; caches as `_paged_latent_decode` takes them. None when
    the caller must take its einsum (ineligible shape, or the emulator
    without FLAGS_paged_flash_interpret). Bumps
    pt_attn_path_total{path=latent_absorbed} at trace time when it fires."""
    if q.ndim != 3 or c_cache.ndim != 4 or kr_cache.ndim != 4:
        return None
    B, H, _ = q.shape
    T, r, dr = c_cache.shape[2], c_cache.shape[3], kr_cache.shape[2]
    interpret = jax.default_backend() != "tpu"
    blk = _gqa_block(T, interpret)       # rows of c a grid step reads
    if blk is None or c_cache.dtype != q.dtype:
        return None
    if interpret:
        if not flag("paged_flash_interpret") or B * H > 64 or r + dr > 128:
            return None
    elif r % 128 != 0 or dr % 16 != 0 or H % 8 != 0:
        return None
    _note_attn_path("latent_absorbed")
    return _paged_latent_decode(
        q[..., :r], q[..., r:], c_cache, kr_cache, lens, new[:, :r],
        new[:, r:], layer=layer, block_k=blk, scale=scale,
        interpret=interpret)


def _latent_oracle(q, c, kr, ok, scale):
    """softmax((q_lat . c + q_rope . kr) * scale, masked by ok [B, T]) . c
    with q [B, H, r + dr], c [B, T, r], kr [B, dr, T], in float32."""
    r = c.shape[-1]
    qf, cf = q.astype(jnp.float32), c.astype(jnp.float32)
    s = (jnp.einsum("bhr,btr->bht", qf[..., :r], cf)
         + jnp.einsum("bhd,bdt->bht", qf[..., r:],
                      kr.astype(jnp.float32))) * scale
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("bht,btr->bhr", p, cf)


def _check_paged_latent():
    """The latent decode kernel at the published sizes (16 heads over a
    512-wide latent and a 64-wide rotary part, scale 1/sqrt(192)) on a
    stack of two layers: an empty slot, one whose append row opens a block,
    one in the middle of its second block and one at the wall, against the
    einsum; every row the call did not append must come back unchanged and
    the empty slot's output is 0."""
    L, B, H, r, dr, T = 2, 4, 16, 512, 64, 4096
    rs = np.random.RandomState(0)
    arr = lambda *s: jnp.asarray(rs.randn(*s), jnp.bfloat16)  # noqa: E731
    q, new = arr(B, H, r + dr), arr(B, r + dr)
    c, kr = arr(L, B, T, r), arr(L, B, dr, T)
    lens = jnp.asarray([0, 1024, 1300, 5000], jnp.int32)
    scale = 192.0 ** -0.5
    run = jax.jit(functools.partial(
        _paged_latent_decode, layer=1, block_k=_gqa_block(T, False),
        scale=scale, interpret=False))
    out, co, kro = run(q[..., :r], q[..., r:], c, kr, lens, new[:, :r],
                       new[:, r:])
    live = np.asarray(lens) > 0
    row = jnp.minimum(lens, T - 1)
    slots = jnp.arange(B)
    cb = np.array(c.at[1, slots, row].set(new[:, :r]))
    krb = np.array(kr.at[1, slots, :, row].set(new[:, r:]))
    ok = jnp.arange(T)[None, :] <= row[:, None]
    want = np.array(_latent_oracle(q, cb[1], krb[1], ok, scale))
    # the empty slot on the host: its output 0, its rows what they were
    want[~live] = 0.0
    cb[1][~live], krb[1][~live] = np.asarray(c)[1][~live], \
        np.asarray(kr)[1][~live]
    if not (np.allclose(np.asarray(out, np.float32), want, rtol=2e-2,
                        atol=2e-2)
            and np.array_equal(np.asarray(co), cb)
            and np.array_equal(np.asarray(kro), krb)):
        raise PallasSelfCheckError(
            "latent paged decode disagrees with the einsum on %s: "
            "max|out-want|=%.3e" % (jax.devices()[0].device_kind,
                                    _max_err(out, want)))


def _band_tile(T, cap):
    """The most rows, at most `cap` and in whole 128-row tiles where `cap`
    holds one, of a block that tiles T (a multiple of 128) exactly."""
    b = cap - cap % _LANES or cap
    while T % b:
        b -= _LANES
    return b


def _band_blocks(T, interpret, window=0, G=8):
    """(query rows, key rows) of a block of the band kernel, or None: a
    rule of (T, G, window), T a multiple of 128. A key block is 512 rows,
    under a window of at most 256 rows the window's own size (128 or 256:
    at 512 three quarters of a 128-row window's two blocks would lie
    outside the band); the query rows are as many as keep the score tile
    at 1024 rows (128 at 8 query heads a key-value head, 64 at 16), at
    most 512: one query head a key head (GPT's prompts; a latent model's
    expanded prefill) takes 512 rows a step, where 128 would leave a step
    a fifth of a microsecond of products and the grid's own cost most of
    it. Where such a block does not tile T: a prompt of under two key
    blocks' rows whose heads' score tile stays within 1024 rows is ONE
    block a key-value head (768 rows at one query head a key head: one
    step that computes the whole square took 52 us a call on the v5e
    where the three 384-row steps of the causal band took 76 and 256-row
    blocks 89: PERF.md section 6, PR 35); else the block is the largest
    whole number of 128-row tiles under it that tiles T."""
    if interpret:
        return (8, 8) if T % 8 == 0 and T <= 64 else None
    if T % _LANES:
        return None
    rows = max(16, min(512, 1024 // G))
    keys = 512 if not window or window > 256 else \
        128 if window <= 128 else 256
    if T % keys and T < 2 * keys and G * T <= 1024:
        return (T, T)
    return (_band_tile(T, rows), _band_tile(T, keys))


def _band_range(i, block_q, block_k, window):
    """First and last K/V block that query block i attends."""
    lo = jnp.maximum(i * block_q - (window - 1), 0) // block_k \
        if window else jnp.int32(0) * i
    return lo, ((i + 1) * block_q - 1) // block_k


def _band_flash_kernel(q_ref, k_ref, v_ref, *refs, block_q, block_k, window,
                       sm_scale, sink):
    if sink:
        sink_ref, *refs = refs
    o_ref, acc_ref, m_ref, l_ref = refs
    i, j = pl.program_id(1), pl.program_id(2)
    lo, hi = _band_range(i, block_q, block_k, window)
    kb = lo + j
    G, _, d = q_ref.shape
    rows = G * block_q

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sink:         # the sink is the softmax's first, valueless term
            m_ref[...] = jnp.broadcast_to(
                sink_ref[...], (G, block_q, _LANES)).reshape(rows, _LANES)
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kb <= hi)
    def _step():
        q = q_ref[...].reshape(rows, d)
        s = jax.lax.dot_general(
            q, k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [rows, bk]
        # the G heads lie one after another along the rows
        q_pos = i * block_q + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0),
            jnp.int32(block_q))
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        ok = k_pos <= q_pos
        if window:
            ok = ok & (q_pos - k_pos < window)
        s = jnp.where(ok, s, _NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row may have no key in this block (the window starts later):
        # exp(-inf - -inf) must not count
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jax.lax.broadcast_in_dim(m_new, m_ref.shape, (0, 1))
        l_ref[...] = jax.lax.broadcast_in_dim(l_new, l_ref.shape, (0, 1))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        ell = jnp.max(l_ref[...], axis=1, keepdims=True)
        o_ref[...] = (acc_ref[...] / ell).reshape(o_ref.shape).astype(
            o_ref.dtype)


def _band_flash(q, k, v, window, block_q, block_k, interpret, sink=None,
                named=True):
    """q [B, Hq, T, dk], k [B, Hkv, T, dk], v [B, Hkv, T, dv] -> out [B,
    Hq, T, dv]: causal, keys within `window` of the query (0 = all),
    grouped heads; `sink` float32 [Hq] or None. A call that is not
    `named` bears its jitted function's name in a trace."""
    B, Hq, T, dk = q.shape
    Hkv, dv = k.shape[1], v.shape[3]
    G = Hq // Hkv
    n_k = T // block_k
    if window:          # the most blocks any query block's band spans
        n_k = min(n_k, (block_q + window - 2) // block_k + 2)

    def q_map(h, i, j):
        return (h, _I0, i, _I0)

    def kv_map(h, i, j):
        lo, hi = _band_range(i, block_q, block_k, window)
        return (h, jnp.minimum(lo + j, hi), _I0)

    def sink_map(h, i, j):
        return (jax.lax.rem(h, jnp.int32(Hkv)), _I0, _I0, _I0)

    kern = functools.partial(_band_flash_kernel, block_q=block_q,
                             block_k=block_k, window=window,
                             sm_scale=float(dk) ** -0.5,
                             sink=sink is not None)
    operands = [q.reshape(B * Hkv, G, T, dk), k.reshape(B * Hkv, T, dk),
                v.reshape(B * Hkv, T, dv)]
    in_specs = [pl.BlockSpec((None, G, block_q, dk), q_map),
                pl.BlockSpec((None, block_k, dk), kv_map),
                pl.BlockSpec((None, block_k, dv), kv_map)]
    name = None
    if named:
        name = "prefill_kv_band_flash" if sink is not None or dk != dv \
            else "prefill_band_flash"
    if sink is not None:
        operands.append(_sink_lanes(sink, Hkv, G)[:, :, None, :])
        in_specs.append(pl.BlockSpec((None, G, 1, _LANES), sink_map))
    rows = G * block_q
    out = _pallas_call(
        kern, grid=(B * Hkv, T // block_q, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, G, block_q, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, T, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, dv), jnp.float32),
                        pltpu.VMEM((rows, _LANES), jnp.float32),
                        pltpu.VMEM((rows, _LANES), jnp.float32)],
        interpret=interpret, name=name)(*operands)
    return out.reshape(B, Hq, T, dv)


def band_flash_attention_or_none(q, k, v, window, sink=None, named=True):
    """Gate + dispatch of the band prefill kernel; None when the caller
    must take another path (flag off or ineligible shape; off the TPU the
    emulator takes the small shapes of `_band_blocks` alone, as the flash
    forward's `_shapes_ok` does). It is the ONE attention a prompt takes
    on the generation server, whatever the family: the decoder family's
    `band_attention` calls it with its window, sink and grouped heads,
    GPT's `_GPTServing`-driven forward (`models/gpt.py::GPTAttention`)
    as the G = 1, window 0 case, `named=False` so that the call keeps the
    name of GPT's jitted prefill in a trace. It has no backward, no lse
    and no dropout: the trainer's attention (no cache at the call site)
    stays `flash_attention_or_none`. Which of the two a call takes is
    decided where it is made, from whether a cache is handed in — no
    flag beyond `FLAGS_use_flash_attention`, which gates both."""
    if not flag("use_flash_attention") or q.ndim != 4:
        return None
    T, dk, dv = q.shape[2], q.shape[3], v.shape[3]
    if k.shape[2] != T or q.shape[1] % k.shape[1]:
        return None
    interpret = jax.default_backend() != "tpu"
    blocks = _band_blocks(T, interpret, int(window or 0),
                          q.shape[1] // k.shape[1])
    if blocks is None:
        return None
    if not interpret and (dk % 64 != 0 or dv % 128 != 0):
        return None
    _note_attn_path("band_flash")
    return _band_flash(q, k, v, int(window or 0), *blocks,
                       interpret=interpret, sink=sink, named=named)


def _check_band_flash(dk=128, dv=128, sink=False, W=256, Hq=8, Hkv=2,
                      T=1024):
    """The band kernel, windowed and not, at two key-value heads of four
    query heads each over 1024 positions, against the masked einsum."""
    B = 1
    rs = np.random.RandomState(0)
    arr = lambda *s: jnp.asarray(rs.randn(*s), jnp.bfloat16)  # noqa: E731
    q, k, v = arr(B, Hq, T, dk), arr(B, Hkv, T, dk), arr(B, Hkv, T, dv)
    b = jnp.asarray(rs.randn(Hq), jnp.float32) if sink else None
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    run = jax.jit(_band_flash, static_argnames=(
        "window", "block_q", "block_k", "interpret"))
    for window in sorted({0, W}):
        bq, bk = _band_blocks(T, False, window, Hq // Hkv)
        got = run(q, k, v, window=window, block_q=bq, block_k=bk,
                  interpret=False, sink=b)
        ok = (j <= i) & ((i - j < window) if window else True)
        want = _gqa_oracle(q.reshape(B, Hkv, Hq // Hkv, T, dk), k, v,
                           ok, b).reshape(B, Hq, T, dv)
        if not np.allclose(np.asarray(got, np.float32), np.asarray(want),
                           rtol=2e-2, atol=2e-2):
            raise PallasSelfCheckError(
                "band flash attention (window=%d, key %d, value %d, "
                "sink=%s) disagrees with the einsum on %s: "
                "max|out-want|=%.3e" % (
                    window, dk, dv, sink, jax.devices()[0].device_kind,
                    _max_err(got, want)))


def _check_band_flash_sink():
    """The same at a 192-wide key and a 128-wide value with a sink, under
    a 128-row window (key blocks of 128) and with sixteen query heads a
    key-value head (query blocks of 64)."""
    _check_band_flash(192, 128, True, W=128)
    _check_band_flash(192, 128, True, W=128, Hq=16, Hkv=1)


def _check_band_flash_latent():
    """The same at a 192-wide key, a 128-wide value and ONE query head a
    key head (query blocks of 512), no sink: what the expanded prefill of
    a latent-attention model asks of the band kernel."""
    _check_band_flash(192, 128, False, W=0, Hq=4, Hkv=4)


def _check_band_flash_mha():
    """The same at one query head a key head, key and value 128 wide, no
    window: what a GPT prompt asks of the band kernel, over 768 positions
    (one block a head, the whole square) and over 1024 (512-row blocks:
    the diagonal, a block below it and a step that is skipped)."""
    for T in (768, 1024):
        _check_band_flash(W=0, Hq=2, Hkv=2, T=T)


# ---------------------------------------------------------------------------
# Grouped product of a held share of the experts
#
# `incubate/moe.grouped_experts` sorts a layer's token-expert assignments by
# expert: the rows of the experts held here come first, one group an expert,
# and the rows of the experts held elsewhere after them. `jax.lax.ragged_dot`
# multiplies every row of that array; where a chip holds a sixteenth of the
# experts fifteen rows in sixteen are computed and thrown away. This kernel
# walks a work list of the (held group, row tile) pairs that hold a row, so
# a tile past the held groups is never read, and a held expert's weights
# are streamed once a visit.
# ---------------------------------------------------------------------------


def _held_work(sizes, m, tm):
    """The work list of a call: (offsets int32 [G + 1], group, tile int32
    [m // tm + G - 1], n). Group g's rows are offsets[g] .. offsets[g + 1];
    entry v < n is the v-th (group, row tile) visit, a group's tiles in
    turn and no tile of an empty group, so that a tile's visits follow one
    another; the entries from n on are never visited, and n is 0 where no
    row is held."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    ntile = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    before = jnp.cumsum(ntile) - ntile        # visits of the groups ahead
    n_max = m // tm + G - 1
    group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), ntile,
                       total_repeat_length=n_max)
    v = jnp.arange(n_max, dtype=jnp.int32)
    tile = jnp.minimum(first[group] + v - before[group], m // tm - 1)
    return (offsets, group.astype(jnp.int32), tile.astype(jnp.int32),
            jnp.sum(ntile).astype(jnp.int32))


def _held_grouped_kernel(off_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
                         *, tm):
    """One (column tile j, visit v) step: x_ref [tm, K] the visit's row
    tile, w_ref [K, tn] of its group's weights. The tile's rows that are
    the group's are written; the others keep what an earlier visit of the
    tile wrote there."""
    v = pl.program_id(1)
    g = group_ref[v]
    y = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, o_ref.shape,
                                                      0)
    mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _whole_tile(n, cap, step):
    """The most, at most `cap` and a multiple of `step`, that tiles n
    exactly; None where no such number does."""
    for t in range(cap - cap % step, 0, -step):
        if n % t == 0:
            return t
    return None


# The most bytes of one block of an expert's weights: the whole width where
# it fits. On the v5e 2-8 MiB blocks stream the decode step's experts at
# 0.40 ms a product, one block of 16 MiB at 0.45 ms (docs/PERFORMANCE.md).
_HELD_W_BLOCK = 8 << 20


def held_grouped_tiles(m, K, N, itemsize, interpret):
    """(rows, columns) of a block of `held_grouped_dot` on x [m, K] and
    experts [K, N] of `itemsize` bytes, or None where the caller takes
    `ragged_dot`. A decode step's rows (m <= 4096) take 128-row tiles, so
    that its held rows, ~100 of them, are one tile and each held expert is
    streamed once; a prompt's take 256-row tiles. The depth is whole: no
    served shape measured faster in depth steps. The emulator takes 8-row
    tiles of whole experts."""
    if interpret:
        return (8, N) if m % 8 == 0 else None
    cap = 128 if m <= 4096 else 256
    tm = m if m <= cap else _whole_tile(m, cap, 16)
    tn = N if K * N * itemsize <= _HELD_W_BLOCK else _whole_tile(
        N, _HELD_W_BLOCK // (K * itemsize), _LANES)
    if tm is None or tn is None or K % _LANES:
        return None
    return tm, tn


def held_grouped_dot(x, w, sizes, *, tiles, interpret):
    """x [m, K] sorted by group, w [G, K, N], sizes int32 [G] with
    sum(sizes) <= m -> [m, N] in x's dtype: rows offsets[g] .. offsets[g +
    1] of the result are those rows of x times w[g], in float32 and rounded
    once. Rows past the groups are undefined, as `ragged_dot` leaves them.
    `tiles` = (rows, columns) of a block, each tiling its dimension exactly
    (`held_grouped_tiles`)."""
    m, K = x.shape
    G, _, N = w.shape
    tm, tn = tiles
    offsets, group, tile, n = _held_work(sizes.astype(jnp.int32), m, tm)

    def x_map(j, v, off, grp, til):
        return (til[v], _I0)

    def w_map(j, v, off, grp, til):
        return (grp[v], _I0, j)

    def o_map(j, v, off, grp, til):
        return (til[v], j)

    it = x.dtype.itemsize
    held = tm * K * it + K * tn * w.dtype.itemsize + tm * tn * it
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(N // tn, n),
        in_specs=[pl.BlockSpec((tm, K), x_map),
                  pl.BlockSpec((None, K, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), o_map))
    return _pallas_call(
        functools.partial(_held_grouped_kernel, tm=tm), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, N), x.dtype),
        compiler_params=_resident_vmem_params(held + tm * tn * 4,
                                              interpret),
        interpret=interpret, name="held_grouped_dot")(
            offsets, group, tile, x, w)


def _check_held_grouped():
    """The held-share product at four experts of [512, 256] over 256 rows
    in the rule's tiles (128 rows, the experts whole): a group that crosses
    a tile, an empty one and a tail of rows held elsewhere, against
    `ragged_dot` on the held rows."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(256, 512), jnp.bfloat16)
    w = jnp.asarray(rs.randn(4, 512, 256) / 16.0, jnp.bfloat16)
    sizes = jnp.asarray([100, 0, 60, 20], jnp.int32)
    tiles = held_grouped_tiles(256, 512, 256, 2, False)
    run = jax.jit(functools.partial(held_grouped_dot, tiles=tiles,
                                    interpret=False))
    got = run(x, w, sizes)
    want = jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)
    got, want = np.asarray(got[:180], np.float32), np.asarray(want[:180])
    if not np.allclose(got, want, rtol=2e-2, atol=2e-2):
        raise PallasSelfCheckError(
            "held-share grouped product disagrees with ragged_dot on %s: "
            "max|out-want|=%.3e" % (jax.devices()[0].device_kind,
                                    _max_err(got, want)))

"""Ring attention + Ulysses all-to-all attention over a sequence-parallel
mesh axis.

NEW capability relative to the reference (SURVEY.md §5 "Long-context /
sequence parallelism: ABSENT — no ring attention / Ulysses / CP"); the
reference scales sequence length only via recompute + pipeline
micro-batching + fused attention (operators/fused/fused_attention_op.cu).
This module is the idiomatic-TPU upgrade: K/V blocks rotate around the
"sep" ring with lax.ppermute (ICI neighbour exchange), combined with an
online-softmax (flash-style) accumulator so the full [T, T] score matrix
never materializes; or, Ulysses-style, heads and sequence are exchanged
with lax.all_to_all and attention runs locally per head shard.

Both run inside shard_map, nested in the surrounding jit: XLA sees the
collectives explicitly and overlaps the ppermute with the block matmuls
(MXU work hides ICI latency for T_local*D big enough).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _online_block(q, k_blk, v_blk, acc, l, m, *, scale, keep,
                  drop_keep=None, drop_scale=1.0):
    """Fold one K/V block into the online-softmax accumulator.

    q [B,H,Tq,D], k_blk/v_blk [B,H,Tk,D], keep [Tq,Tk] bool mask.
    Returns updated (acc [B,H,Tq,D] f32, l [B,H,Tq] f32, m [B,H,Tq] f32).

    drop_keep ([B,H,Tq,Tk] bool) applies attention dropout to the
    NUMERATOR only: dropout(w)·v == (dropout(p)/l)·v because dropout is
    an elementwise mask+rescale, so l stays the undropped softmax
    denominator — same contract as the Pallas flash-dropout kernel."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep[None, None], s, jnp.asarray(-1e30, s.dtype))
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # fully-masked rows keep m == -inf/-1e30: exp underflows to 0 safely
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)                    # rescale old accumulator
    l_new = l * corr + jnp.sum(p, axis=-1)
    p_acc = p if drop_keep is None else \
        p * jnp.where(drop_keep, jnp.float32(drop_scale), jnp.float32(0))
    acc_new = acc * corr[..., None] + \
        jnp.einsum("bhqk,bhkd->bhqd", p_acc, v_blk.astype(jnp.float32))
    return acc_new, l_new, m_new


def _ring_attention_local(q, k, v, *, axis_name, causal, scale,
                          dropout_p=0.0, key=None, drop_axes=(),
                          checkpoint_steps=False):
    """Per-shard body (inside shard_map). q/k/v: [B, H, T_local, D] — the
    sequence dim is the axis_name shard. Online-softmax across ring steps;
    causal masking is done by GLOBAL positions so the result equals
    full-sequence causal attention. Block 0 (the local K/V) is folded
    before the scan so only size-1 ppermute rotations happen — none of
    them wasted.

    Attention dropout (dropout_p>0 + key): each [Tq_local, Tk_local]
    block draws its keep mask from fold_in(key, my_idx·size + kb) —
    globally consistent block ids, so the result is a well-defined
    dropout sample of full-sequence attention — after folding the
    replicated key by each `drop_axes` mesh index (dp/mp shards hold
    different examples/heads and must draw independent masks)."""
    size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    tq_pos = jnp.arange(t_local) + my_idx * t_local

    if dropout_p > 0.0 and key is not None:
        for ax in drop_axes:
            key = jax.random.fold_in(key, lax.axis_index(ax))

    def keep_for(kb):
        if not causal:
            return jnp.ones((t_local, t_local), bool)
        tk = jnp.arange(t_local) + kb * t_local
        return tq_pos[:, None] >= tk[None, :]

    def drop_for(kb):
        if dropout_p <= 0.0 or key is None:
            return None, 1.0
        bkey = jax.random.fold_in(key, my_idx * size + kb)
        return (jax.random.bernoulli(bkey, jnp.float32(1.0 - dropout_p),
                                     q.shape[:-1] + (t_local,)),
                1.0 / (1.0 - dropout_p))

    acc0 = jnp.zeros(q.shape[:-1] + (q.shape[-1],), jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    m0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    dk0, ds0 = drop_for(my_idx)
    acc0, l0, m0 = _online_block(q, k, v, acc0, l0, m0, scale=scale,
                                 keep=keep_for(my_idx), drop_keep=dk0,
                                 drop_scale=ds0)

    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(carry, i):
        acc, l, m, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        kb = (my_idx - i) % size                 # global block id of k_cur
        dk, ds = drop_for(kb)
        acc, l, m = _online_block(q, k_cur, v_cur, acc, l, m, scale=scale,
                                  keep=keep_for(kb), drop_keep=dk,
                                  drop_scale=ds)
        return (acc, l, m, k_cur, v_cur), ()

    if checkpoint_steps:
        # backward otherwise saves each ring step's [Tq_l, Tk_l] probs
        # (O(T^2/size) residuals); remat keeps only the carries and
        # replays the block compute + ppermute — O(size · Tl · D).
        # prevent_cse=False: safe and recommended for scan bodies, and
        # avoids optimization barriers that would inhibit the
        # ppermute/matmul overlap this module relies on
        step = jax.checkpoint(step, prevent_cse=False)
    (acc, l, m, _, _), _ = lax.scan(
        step, (acc0, l0, m0, k, v), jnp.arange(1, size))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _shard_dispatch(body, mesh, spec, q, k, v, key=None):
    """shard_map the attention body over q/k/v (+ an optional replicated
    PRNG key operand) — single dispatch point shared by ring/Ulysses,
    dropout and not."""
    if key is not None:
        return jax.shard_map(lambda a, b, c, kk: body(a, b, c, key=kk),
                             mesh=mesh, in_specs=(spec, spec, spec, P()),
                             out_specs=spec, check_vma=False)(q, k, v, key)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis="sep", batch_axes=("dp",),
                   head_axis="mp", causal=True, scale=None, dropout_p=0.0,
                   key=None, checkpoint_steps=False):
    """Full-sequence attention with q/k/v sharded over `seq_axis` on dim 2.

    q/k/v: jax arrays [B, H, T, D] (T = GLOBAL sequence). Returns [B,H,T,D]
    with the same sharding. Differentiable (scan+ppermute transpose).

    dropout_p>0 with a PRNG `key` applies attention dropout on the ring
    (per-block fold_in masks; dp/mp shards fold their mesh index in so
    different examples/heads draw independent masks)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    spec = P(batch_axes, head_axis if head_axis in mesh.shape else None,
             seq_axis, None)
    use_drop = dropout_p > 0.0 and key is not None
    fn = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal,
        scale=scale, checkpoint_steps=checkpoint_steps,
        dropout_p=float(dropout_p) if use_drop else 0.0,
        drop_axes=tuple(a for a in (*batch_axes, head_axis)
                        if a in mesh.shape))
    return _shard_dispatch(fn, mesh, spec, q, k, v,
                           key if use_drop else None)


def _blockwise_attention(q, k, v, *, causal, scale, block_k=512,
                         checkpoint_blocks=False, dropout_p=0.0,
                         dropout_key=None):
    """Single-device flash-style attention: scan K/V in blocks with the
    online-softmax accumulator, so the [Tq, Tk] score matrix never
    materializes (only [Tq, block_k] tiles). q/k/v: [B,H,T,D].

    checkpoint_blocks=True remats each block step, so the BACKWARD pass
    also avoids the [Tq, Tk] residual (it stores only the per-step
    carries, O(nblk · B·H·Tq·D), and recomputes the block probs) — the
    lax-level stand-in for the Pallas flash backward when Mosaic is
    unavailable (see nn_ops.sdpa chunked gate).

    Attention dropout (dropout_p>0 with a dropout_key) draws each block's
    [B,H,Tq,block_k] keep mask from fold_in(dropout_key, block_idx) —
    deterministic per (key, block), so the remat'd backward regenerates
    the identical mask."""
    t = k.shape[-2]
    bk = min(block_k, t)
    nblk = -(-t // bk)
    pad = nblk * bk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    tq_pos = jnp.arange(q.shape[-2])

    acc = jnp.zeros(q.shape[:-1] + (q.shape[-1],), jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)
    m = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)

    kb = jnp.moveaxis(k.reshape(k.shape[:2] + (nblk, bk, k.shape[-1])), 2, 0)
    vb = jnp.moveaxis(v.reshape(v.shape[:2] + (nblk, bk, v.shape[-1])), 2, 0)

    def step(carry, blk):
        acc, l, m, i = carry
        k_blk, v_blk = blk
        tk = jnp.arange(bk) + i * bk
        keep = tk[None, :] < t
        if causal:
            keep = keep & (tq_pos[:, None] >= tk[None, :])
        else:
            keep = jnp.broadcast_to(keep, (q.shape[-2], bk))
        drop_keep, drop_scale = None, 1.0
        if dropout_p > 0.0 and dropout_key is not None:
            drop_keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, i),
                jnp.float32(1.0 - dropout_p),
                q.shape[:-1] + (bk,))
            drop_scale = 1.0 / (1.0 - dropout_p)
        acc, l, m = _online_block(q, k_blk, v_blk, acc, l, m, scale=scale,
                                  keep=keep, drop_keep=drop_keep,
                                  drop_scale=drop_scale)
        return (acc, l, m, i + 1), ()

    if checkpoint_blocks:
        step = jax.checkpoint(step)
    (acc, l, m, _), _ = lax.scan(step, (acc, l, m, 0), (kb, vb))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _ulysses_local(q, k, v, *, axis_name, causal, scale, dropout_p=0.0,
                   key=None, drop_axes=()):
    """Ulysses (all-to-all) body: exchange sequence shards for head shards,
    run blockwise (online-softmax) local attention on the full sequence /
    subset of heads, exchange back. q/k/v local: [B, H, T_local, D]; H
    divisible by ring size.

    Attention dropout folds the replicated key by this shard's axis index
    (each shard holds a DIFFERENT head group post-exchange) and by every
    `drop_axes` mesh index, then rides _blockwise_attention's per-block
    fold_in masks."""
    def seq2head(x):
        # [B,H,Tl,D] -> [B, H/size, T, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    if dropout_p > 0.0 and key is not None:
        for ax in (*drop_axes, axis_name):
            key = jax.random.fold_in(key, lax.axis_index(ax))
    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    o = _blockwise_attention(qh, kh, vh, causal=causal, scale=scale,
                             dropout_p=dropout_p, dropout_key=key)
    return head2seq(o)


def ulysses_attention(q, k, v, mesh: Mesh, *, seq_axis="sep",
                      batch_axes=("dp",), head_axis="mp", causal=True,
                      scale=None, dropout_p=0.0, key=None):
    """DeepSpeed-Ulysses-style sequence parallelism: all_to_all turns the
    sequence shard into a head shard, local attention sees the FULL
    sequence. Needs num_heads_local % sep_degree == 0.

    dropout_p>0 with a PRNG `key` applies attention dropout in the local
    blockwise attention (independent masks per head/batch shard)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    spec = P(batch_axes, head_axis if head_axis in mesh.shape else None,
             seq_axis, None)
    use_drop = dropout_p > 0.0 and key is not None
    fn = functools.partial(
        _ulysses_local, axis_name=seq_axis, causal=causal, scale=scale,
        dropout_p=float(dropout_p) if use_drop else 0.0,
        drop_axes=tuple(a for a in (*batch_axes, head_axis)
                        if a in mesh.shape))
    return _shard_dispatch(fn, mesh, spec, q, k, v,
                           key if use_drop else None)

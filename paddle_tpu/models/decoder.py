"""One configurable decoder block for today's open decoder-only models.

Where `models/gpt.py` is GPT (learned positions, LayerNorm, GELU, full
multi-head attention, tied head), this file is the block the newer
families are made of, every part chosen by `DecoderConfig` and none by a
model's name:

  * RMSNorm; optionally four norms a layer ("sandwich": the attention and
    MLP outputs are normed inside the residual branch);
  * grouped-query attention: `num_heads` query heads over `num_kv_heads`
    key-value heads of `head_dim`; optional RMSNorm over each head of q
    and k; optional sigmoid output gate before the output projection;
  * per layer, full causal attention or a sliding window, and rotary
    positions or none (`layer_kinds`, `rope_layers`);
  * per layer, a dense SwiGLU or an expert layer (`mlp_kinds`): sigmoid
    router with a bias for the choice, top-k, a shared expert, NO capacity
    and no dropped token (`incubate/moe.py`: tokens sorted by expert, a
    grouped matrix product over the experts held);
  * optional sqrt(d) embedding scale, untied output head.

The mathematics is plain `jax.numpy` over the parameters' arrays: forward
only (serving and evaluation). There is no backward through the
framework's tape yet — rotary scaling, latent attention, chunked prefill
and experts over chips are not here either (ROADMAP R7, R9).

`DecoderLM.serving()` answers what `inference/serving/engine.py` asks of a
model; the sliding-window layers keep a ring of `window` rows in the paged
cache and the full layers every row (`serving/cache.py`).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.tensor import Parameter, Tensor
from ..incubate import moe as moe_ops
from ..nn.layer_base import Layer
from ..nn.layers import LayerList

__all__ = ["DecoderConfig", "DecoderLM", "MoEConfig"]

F32 = jnp.float32
_NEG = -1e30


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    width: int                      # of one routed expert
    shared_width: int = 0           # of the shared expert(s) together
    route_norm: bool = True
    route_scale: float = 1.0
    #: (first, count) of the experts this holder has; None = all
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def held(self):
        return self.experts_held or (0, self.num_experts)


@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    layer_kinds: Tuple[str, ...]            # "full" | "window", a layer
    mlp_kinds: Tuple[str, ...]              # "dense" | "moe", a layer
    dense_width: int
    window: int = 0
    rope_layers: Tuple[bool, ...] = ()      # rotary on this layer's q, k
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    qk_norm: bool = False
    attn_gate: bool = False
    sandwich_norm: bool = False
    embed_scale: float = 1.0
    max_positions: int = 2048
    moe: Optional[MoEConfig] = field(default=None)

    def __post_init__(self):
        n = len(self.layer_kinds)
        if len(self.mlp_kinds) != n or len(self.rope_layers) != n:
            raise ValueError("layer_kinds, mlp_kinds and rope_layers must "
                             "name the same layers")
        if set(self.layer_kinds) - {"full", "window"} \
                or set(self.mlp_kinds) - {"dense", "moe"}:
            raise ValueError("unknown layer kind")
        if "window" in self.layer_kinds and self.window < 1:
            raise ValueError("window layers need a window")
        if "moe" in self.mlp_kinds and self.moe is None:
            raise ValueError("expert layers need a MoEConfig")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("query heads must divide over the kv heads")

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @classmethod
    def from_hf(cls, cfg, experts_held=None):
        """From the keys of a published `config.json` whose block is this
        one (sliding and full attention mixed, sigmoid-routed experts with
        a shared expert behind `num_dense_layers` dense layers)."""
        kinds = tuple("window" if k == "sliding_attention" else "full"
                      for k in cfg["layer_types"])
        n = int(cfg["num_hidden_layers"])
        if len(kinds) != n:
            raise ValueError("layer_types has %d entries for %d layers"
                             % (len(kinds), n))
        dense = int(cfg.get("num_dense_layers", n))
        d = int(cfg["hidden_size"])
        moe = None
        if dense < n:
            moe = MoEConfig(
                num_experts=int(cfg["num_experts"]),
                top_k=int(cfg["num_experts_per_tok"]),
                width=int(cfg["moe_intermediate_size"]),
                shared_width=int(cfg["moe_intermediate_size"])
                * int(cfg.get("num_shared_experts", 0)),
                route_norm=bool(cfg.get("route_norm", True)),
                route_scale=float(cfg.get("route_scale", 1.0)),
                experts_held=experts_held)
        return cls(
            vocab_size=int(cfg["vocab_size"]), hidden_size=d,
            num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]), layer_kinds=kinds,
            mlp_kinds=tuple("dense" if i < dense else "moe"
                            for i in range(n)),
            dense_width=int(cfg["intermediate_size"]),
            window=int(cfg.get("sliding_window") or 0),
            # rotary on the sliding layers alone: full layers carry none
            rope_layers=tuple(k == "window" for k in kinds),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            qk_norm=True, attn_gate=True, sandwich_norm=True,
            embed_scale=math.sqrt(d) if cfg.get("mup_enabled") else 1.0,
            max_positions=int(cfg["max_position_embeddings"]), moe=moe)


# ---------------------------------------------------------------------------
# the mathematics (arrays)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def rotary(x, pos, theta):
    """x [B, H, T, hd] (float32) at positions pos [B, T]; rotate-half."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None, :, None] * inv              # [B,1,T,hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


def swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def band_attention(q, k, v, window):
    """Causal attention of q [B, Hq, T, hd] over k, v [B, Hkv, T, hd],
    grouped heads, keys j <= i and (window) i - j < window. The Pallas
    band kernel where it applies, else the masked einsum."""
    from ..ops import pallas_kernels as pk
    out = pk.band_flash_attention_or_none(q, k, v, window)
    if out is not None:
        return out
    B, Hq, T, hd = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, Hq // Hkv, T, hd).astype(F32)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg, k.astype(F32)) / math.sqrt(hd)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = j <= i
    if window:
        ok = ok & (i - j < window)
    p = jax.nn.softmax(jnp.where(ok, s, _NEG), axis=-1)
    o = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(F32))
    return o.reshape(B, Hq, T, hd).astype(q.dtype)


def paged_attention(q, k, v, view):
    """One new token a slot against the paged cache: q [B, Hkv, G, hd],
    k, v [B, Hkv, 1, hd] through `view` (serving/cache.LayerCacheView),
    which appends, attends and leaves the carrier updated."""
    return view.attend(q, k, v)


def _attention(cfg, i, p, h, pos, view=None):
    """Attention branch of layer i on h [B, T, d] (float32) at positions
    pos [B, T]. Without `view`: the whole sequence, returning this
    layer's k and v [B, Hkv, T, hd] for the cache; with it: one token a
    slot through the paged cache."""
    B, T, _ = h.shape
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = p["wq"].dtype
    a = rms_norm(h, p["attn_norm"], cfg.rms_eps).astype(dt)
    heads = lambda y, n: y.reshape(B, T, n, hd).transpose(0, 2, 1, 3)  # noqa
    q, k = heads(_mm(a, p["wq"]), Hq), heads(_mm(a, p["wk"]), Hkv)
    v = heads(_mm(a, p["wv"]), Hkv).astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_layers[i]:
        q, k = rotary(q, pos, cfg.rope_theta), rotary(k, pos, cfg.rope_theta)
    q, k = q.astype(dt), k.astype(dt)
    if view is None:
        window = cfg.window if cfg.layer_kinds[i] == "window" else 0
        o = band_attention(q, k, v, window)                   # [B,Hq,T,hd]
    else:
        o = paged_attention(q.reshape(B, Hkv, Hq // Hkv, hd), k, v, view)
        o = o.reshape(B, Hq, 1, hd)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, Hq * hd).astype(F32)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(_mm(a, p["wg"]))
    return _mm(o, p["wo"]), k, v


def moe_layer(mc, p, x):
    """The expert layer on tokens x [N, d]: (float32 [N, d], int32
    [E_held] assignments each held expert got)."""
    first, _ = mc.held
    chosen, w = moe_ops.sigmoid_topk_route(
        x, p["router"], p["expert_bias"], mc.top_k, mc.route_norm,
        mc.route_scale)
    out, sizes = moe_ops.grouped_experts(
        x.astype(p["e_gate"].dtype), chosen, w, p["e_gate"], p["e_up"],
        p["e_down"], first=first)
    if mc.shared_width:
        out = out + swiglu(x, p["s_gate"], p["s_up"], p["s_down"])
    return out, sizes


def _layer(cfg, i, p, h, pos, view=None):
    """(h', k, v, expert assignment counts or None) of layer i."""
    attn, k, v = _attention(cfg, i, p, h, pos, view)
    if cfg.sandwich_norm:
        h = h + rms_norm(attn, p["post_attn_norm"], cfg.rms_eps)
        x = rms_norm(h, p["pre_mlp_norm"], cfg.rms_eps)
    else:
        h = h + attn
        x = rms_norm(h, p["pre_mlp_norm"], cfg.rms_eps)
    sizes = None
    if cfg.mlp_kinds[i] == "dense":
        y = swiglu(x, p["gate"], p["up"], p["down"])
    else:
        B, T, d = x.shape
        y, sizes = moe_layer(cfg.moe, p, x.reshape(B * T, d))
        y = y.reshape(B, T, d)
    if cfg.sandwich_norm:
        y = rms_norm(y, p["post_mlp_norm"], cfg.rms_eps)
    return h + y, k, v, sizes


def route_stats(sizes):
    """int32 [2] from the expert layers' assignment counts: experts that
    got at least one assignment, and the fullest expert's count, each
    summed over the layers (the host divides by the layers)."""
    if not sizes:
        return None
    touched = sum(jnp.sum(s > 0) for s in sizes)
    fullest = sum(jnp.max(s) for s in sizes)
    return jnp.stack([touched, fullest]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# the Layer that holds the parameters


def _param(shape, dtype, key, mean, std, abstract, trainable=True):
    if abstract:
        p = Parameter(jnp.zeros((), dtype), trainable=trainable)
        p._data = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
        return p
    data = (mean + std * jax.random.normal(key, tuple(shape), F32))
    return Parameter(data.astype(dtype), trainable=trainable)


class DecoderBlock(Layer):
    """The parameters of one layer (see `leaf_shapes`)."""

    def __init__(self, cfg, i, dtype, key, abstract):
        super().__init__()
        for n, (name, (shape, mean, std, dt)) in enumerate(
                block_leaves(cfg, i, dtype).items()):
            setattr(self, name, _param(shape, dt, jax.random.fold_in(key, n),
                                       mean, std, abstract))

    def arrays(self):
        return {n: p._data for n, p in self._parameters.items()}


def block_leaves(cfg, i, dtype):
    """{name: (shape, mean, std, dtype)} of layer i's parameters."""
    d, hd = cfg.hidden_size, cfg.head_dim
    qd, kd = cfg.num_heads * hd, cfg.num_kv_heads * hd
    gain, mat = (1.0, 0.02), (0.0, 0.02)
    out = {"attn_norm": ((d,),) + gain, "wq": ((d, qd),) + mat,
           "wk": ((d, kd),) + mat, "wv": ((d, kd),) + mat}
    if cfg.attn_gate:
        out["wg"] = ((d, qd),) + mat
    if cfg.qk_norm:
        out["q_norm"] = ((hd,),) + gain
        out["k_norm"] = ((hd,),) + gain
    out["wo"] = ((qd, d),) + mat
    if cfg.sandwich_norm:
        out["post_attn_norm"] = ((d,),) + gain
    out["pre_mlp_norm"] = ((d,),) + gain
    if cfg.mlp_kinds[i] == "dense":
        F = cfg.dense_width
        out.update(gate=((d, F),) + mat, up=((d, F),) + mat,
                   down=((F, d),) + mat)
    else:
        mc = cfg.moe
        E, f, fs = mc.held[1], mc.width, mc.shared_width
        out["router"] = ((d, mc.num_experts),) + mat
        out["expert_bias"] = ((mc.num_experts,), 0.0, 0.0)
        out.update(e_gate=((E, d, f),) + mat, e_up=((E, d, f),) + mat,
                   e_down=((E, f, d),) + mat)
        if fs:
            out.update(s_gate=((d, fs),) + mat, s_up=((d, fs),) + mat,
                       s_down=((fs, d),) + mat)
    if cfg.sandwich_norm:
        out["post_mlp_norm"] = ((d,),) + gain
    return {k: v + (F32 if k == "expert_bias" else dtype,)
            for k, v in out.items()}


class DecoderLM(Layer):
    """Embedding, `cfg.num_layers` decoder blocks, final norm, untied head.

    `abstract=True` builds the parameters as shapes alone (nothing is
    allocated): `load_arrays` then adopts arrays made elsewhere without a
    copy, so a model of many gigabytes is never held twice."""

    def __init__(self, cfg: DecoderConfig, dtype="float32", seed=0,
                 abstract=False):
        super().__init__()
        self.cfg = cfg
        key = jax.random.PRNGKey(seed)
        V, d = cfg.vocab_size, cfg.hidden_size
        self.embed = _param((V, d), dtype, jax.random.fold_in(key, 0),
                            0.0, 0.02, abstract)
        self.layers = LayerList([
            DecoderBlock(cfg, i, dtype, jax.random.fold_in(key, 10 + i),
                         abstract) for i in range(cfg.num_layers)])
        self.norm_f = _param((d,), dtype, jax.random.fold_in(key, 1),
                             1.0, 0.02, abstract)
        self.head = _param((V, d), dtype, jax.random.fold_in(key, 2),
                           0.0, 0.02, abstract)

    def load_arrays(self, arrays):
        """Adopt {parameter name: array}, each as it is (no copy); shape
        and type must be the parameter's own."""
        for name, p in self.named_parameters():
            a = arrays[name]
            if tuple(a.shape) != tuple(p._data.shape) \
                    or a.dtype != p._data.dtype:
                raise ValueError("parameter %s is %s %s, given %s %s" % (
                    name, p._data.shape, p._data.dtype, a.shape, a.dtype))
            p._data = a

    # -- arrays in, arrays out (what the jitted serving steps trace) ------

    def _embed(self, ids):
        return self.embed._data[ids].astype(F32) * self.cfg.embed_scale

    def _logits(self, h):
        h = rms_norm(h, self.norm_f._data, self.cfg.rms_eps)
        head = self.head._data
        return jnp.einsum("btd,vd->btv", h.astype(head.dtype), head,
                          preferred_element_type=F32)

    def run(self, ids, last_row=None):
        """ids [B, T] -> (logits, [k a layer], [v a layer], stats): the
        whole sequence, no cache. `last_row` (traced int) keeps the head
        to that one position: logits [B, 1, V]."""
        cfg = self.cfg
        B, T = ids.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        h = self._embed(ids)
        ks, vs, sizes = [], [], []
        for i, blk in enumerate(self.layers):
            h, k, v, sz = _layer(cfg, i, blk.arrays(), h, pos)
            ks.append(k)
            vs.append(v)
            if sz is not None:
                sizes.append(sz)
        if last_row is not None:
            h = jax.lax.dynamic_slice_in_dim(h, last_row, 1, axis=1)
        return self._logits(h), ks, vs, route_stats(sizes)

    def step(self, last, views):
        """last [B, 1] token a slot, views: a LayerCacheView a layer ->
        (logits [B, 1, V], stats); the carrier holds the updated cache."""
        cfg = self.cfg
        lens = views[0].kv.lens
        pos = jnp.minimum(lens, cfg.max_positions - 1)[:, None]
        h = self._embed(last)
        sizes = []
        for i, blk in enumerate(self.layers):
            h, _, _, sz = _layer(cfg, i, blk.arrays(), h, pos, views[i])
            if sz is not None:
                sizes.append(sz)
        return self._logits(h), route_stats(sizes)

    def forward(self, input_ids):
        """[B, T, V] logits of input_ids [B, T] (no cache, no gradient)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return Tensor(self.run(ids.astype(jnp.int32))[0], _internal=True)

    def serving(self):
        return _Serving(self)


class _Serving:
    """What `GenerationEngine` asks of a model (see its docstring)."""

    prefix_cache = False          # a ring holds no reusable prompt head

    def __init__(self, model):
        cfg = model.cfg
        self.model = model
        self.n_layers = cfg.num_layers
        self.kv_heads, self.head_dim = cfg.num_kv_heads, cfg.head_dim
        self.layer_kinds, self.window = cfg.layer_kinds, cfg.window
        self.max_positions = cfg.max_positions
        self.moe_layers = cfg.mlp_kinds.count("moe")
        self.selfchecks = ("paged_gqa", "band_flash")
        if cfg.num_heads == cfg.num_kv_heads and "full" in cfg.layer_kinds:
            # one query head a key-value head: a full layer's decode
            # takes GPT's kernel (serving/cache.LayerCacheView.attend)
            self.selfchecks += ("paged",)
        self.moe_top_k = cfg.moe.top_k if cfg.moe else 0
        self.moe_experts = cfg.moe.num_experts if cfg.moe else 0

    def prefill(self, ids, true_len):
        logits, ks, vs, stats = self.model.run(ids, last_row=true_len - 1)
        return logits, ks, vs, stats

    def decode(self, last, views):
        return self.model.step(last, views)

"""One configurable decoder block for today's open decoder-only models.

Where `models/gpt.py` is GPT (learned positions, LayerNorm, GELU, full
multi-head attention, tied head), this file is the block the newer
families are made of, every part chosen by `DecoderConfig` and none by a
model's name:

  * RMSNorm; optionally four norms a layer ("sandwich": the attention and
    MLP outputs are normed inside the residual branch);
  * grouped-query attention: `num_heads` query heads over `num_kv_heads`
    key-value heads; a key row `head_dim` wide and a value row
    `v_head_dim`; the window layers may have head counts and sizes of
    their own (`window_geometry`); optional RMSNorm over each head of q
    and k; optional sigmoid output gate before the output projection;
    optional scale on the values (`value_scale`); optionally one learned
    logit a query head in the softmax's denominator, which takes no value
    (`sink_kinds`: the kinds of layer that have one);
  * per layer, full causal attention or a sliding window, and rotary
    positions or none (`layer_kinds`, `rope_layers`), rotate-half over the
    first `rope_dim` of a head's key size, with a base a kind
    (`rope_theta`, `window_rope_theta`);
  * or, on every layer, multi-head LATENT attention (`latent_rank`, kind
    "latent"): keys and values are up-projections of one `latent_rank`-wide
    normed latent a token, and a `latent_rope_dim`-wide rotary part that all
    the heads share is appended to each head's key. A whole sequence
    EXPANDS the latent into per-head keys and values (`mla_expand`) and
    takes the band kernel; one new token a slot ABSORBS the two
    up-projections into its query and its output (`mla_absorb`) and attends
    the cached rows themselves, each (latent | rotary part), key and value
    at once — the two paths are one function of the weights;
  * per layer, a dense SwiGLU or an expert layer (`mlp_kinds`): sigmoid
    router with a bias for the choice, top-k, a shared expert or none, NO
    capacity and no dropped token (`incubate/moe.py`: tokens sorted by
    expert, a grouped matrix product over the experts held, which may be
    one chip's share of them: `MoEConfig.experts_held`);
  * optional sqrt(d) embedding scale, untied output head.

The mathematics is plain `jax.numpy` over the parameters' arrays: forward
only (serving and evaluation). There is no backward through the
framework's tape yet — rotary scaling, softmax and group-limited routing,
a low-rank query projection, a suffix prefill over cached latents, chunked
prefill and the exchange of a sharded expert layer between chips are not
here either (ROADMAP R7, R8, R9).

`DecoderLM.serving()` answers what `inference/serving/engine.py` asks of a
model; the sliding-window layers keep a ring of `window` rows in the paged
cache and the full layers every row, each kind with its own key-value
heads and its own key and value sizes; a latent layer keeps one row of
`latent_rank + latent_rope_dim` numbers a token and no per-head key or
value (`serving/cache.py`).
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
    layer_kinds: Tuple[str, ...]    # "full" | "window" | "latent", a layer
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
    v_head_dim: Optional[int] = None        # None: a value row is head_dim
    #: (num_heads, num_kv_heads, head_dim, v_head_dim) of the window
    #: layers; None: the full layers' own
    window_geometry: Optional[Tuple[int, int, int, int]] = None
    rope_dim: Optional[int] = None          # leading dims rotated; None: all
    window_rope_theta: Optional[float] = None   # None: rope_theta
    value_scale: float = 1.0
    sink_kinds: Tuple[str, ...] = ()        # kinds with a sink a query head
    #: latent attention: the width of the normed latent a token that every
    #: head's keys (their first head_dim - latent_rope_dim dims) and values
    #: are up-projections of, and of the rotary part the heads share
    latent_rank: int = 0
    latent_rope_dim: int = 0

    def __post_init__(self):
        n = len(self.layer_kinds)
        if len(self.mlp_kinds) != n or len(self.rope_layers) != n:
            raise ValueError("layer_kinds, mlp_kinds and rope_layers must "
                             "name the same layers")
        if set(self.layer_kinds) - {"full", "window", "latent"} \
                or set(self.mlp_kinds) - {"dense", "moe"}:
            raise ValueError("unknown layer kind")
        if "latent" in self.layer_kinds:
            if set(self.layer_kinds) != {"latent"}:
                raise ValueError("latent layers beside full or window "
                                 "layers are not here")
            if self.latent_rank < 1 or self.num_kv_heads != self.num_heads \
                    or not 0 < self.latent_rope_dim < self.head_dim:
                raise ValueError(
                    "latent layers need a latent_rank, a latent_rope_dim "
                    "within a key row and one key head a query head")
        if "window" in self.layer_kinds and self.window < 1:
            raise ValueError("window layers need a window")
        if "moe" in self.mlp_kinds and self.moe is None:
            raise ValueError("expert layers need a MoEConfig")
        for kind in set(self.layer_kinds):
            hq, hkv, dk, _ = self.geometry(kind)
            if hq % hkv:
                raise ValueError("query heads must divide over the kv heads")
            r = self.rotary_dim(kind)
            if not 0 < r <= dk or r % 2:
                raise ValueError("rope_dim must be even and within a key row")
        if set(self.sink_kinds) - {"full", "window"}:
            raise ValueError("unknown layer kind")

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    def geometry(self, kind):
        """(query heads, key-value heads, key size, value size) of the
        layers of `kind`."""
        if kind == "window" and self.window_geometry is not None:
            return tuple(self.window_geometry)
        return (self.num_heads, self.num_kv_heads, self.head_dim,
                self.v_head_dim or self.head_dim)

    def rotary_dim(self, kind):
        if kind == "latent":
            return self.latent_rope_dim
        return self.rope_dim or self.geometry(kind)[2]

    def theta(self, kind):
        if kind == "window" and self.window_rope_theta is not None:
            return self.window_rope_theta
        return self.rope_theta

    @classmethod
    def from_hf(cls, cfg, experts_held=None):
        """From the keys of a published `config.json` whose block is this
        one. Every part is read from a key, under either of the two sets
        of names the published configs of such models use (first name
        below, then its other spelling):

          layer_types ("sliding_attention" | "full_attention") or
          hybrid_layer_pattern (1 window, 0 full), neither: every layer
          full; num_dense_layers (leading) or moe_layer_freq (a list: 0
          dense, 1 experts, a layer; an integer f with
          first_k_dense_replace k: experts on the layers i >= k with
          i mod f = 0); num_experts / n_routed_experts; num_shared_experts /
          n_shared_experts; route_norm / norm_topk_prob; route_scale /
          routed_scaling_factor; rms_norm_eps / layernorm_epsilon;
          v_head_dim; swa_num_attention_heads, swa_num_key_value_heads,
          swa_head_dim, swa_v_head_dim (the window layers' own);
          partial_rotary_factor; swa_rope_theta; attention_value_scale;
          add_swa_attention_sink_bias, add_full_attention_sink_bias;
          kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim
          (latent attention on every layer: a key row is the two qk sizes
          together and no `head_dim` is read; `q_lora_rank` must be null).

        Four parts of the block no published key states; each has a key
        here, and a config that leaves it out gets what the modelling code
        behind its set of names does: `qk_norm`, `attention_output_gate`,
        `sandwich_norm` (all three on for a config that says `layer_types`,
        off for one that says `hybrid_layer_pattern`) and `rope_layer_kinds`
        (the kinds of layer that rotate: window alone for the first set,
        every kind otherwise). Rotary scaling other than `default`, a
        low-rank query projection and group-limited or softmax routing are
        not here: a config that asks for one is refused."""
        def key(*names, default=None):
            for name in names:
                if cfg.get(name) is not None:
                    return cfg[name]
            return default

        n = int(cfg["num_hidden_layers"])
        rank = int(cfg.get("kv_lora_rank") or 0)
        if rank and cfg.get("q_lora_rank") is not None:
            raise NotImplementedError(
                "a low-rank query projection (q_lora_rank %r) is not in "
                "this block" % (cfg["q_lora_rank"],))
        pattern = cfg.get("hybrid_layer_pattern")
        if rank:
            kinds = ("latent",) * n
        elif pattern is not None:
            kinds = tuple("window" if k else "full" for k in pattern)
        elif cfg.get("layer_types") is not None:
            kinds = tuple("window" if k == "sliding_attention" else "full"
                          for k in cfg["layer_types"])
        else:
            kinds = ("full",) * n
        freq = cfg.get("moe_layer_freq")
        if isinstance(freq, (list, tuple)):
            mlps = tuple("moe" if f else "dense" for f in freq)
        elif freq is not None:
            first = int(cfg.get("first_k_dense_replace") or 0)
            mlps = tuple("moe" if i >= first and i % int(freq) == 0
                         else "dense" for i in range(n))
        else:
            dense = int(cfg.get("num_dense_layers", n))
            mlps = tuple("dense" if i < dense else "moe" for i in range(n))
        if len(kinds) != n or len(mlps) != n:
            raise ValueError("the layer lists have %d and %d entries for %d "
                             "layers" % (len(kinds), len(mlps), n))
        scaling = cfg.get("rope_scaling") or {}
        if scaling.get("rope_type", scaling.get("type", "default")) \
                != "default":
            raise NotImplementedError("rotary scaling %r" % (scaling,))
        if key("scoring_func", "score_func", default="sigmoid") != "sigmoid" \
                or int(key("n_group", "num_expert_groups", default=1)) != 1:
            raise NotImplementedError(
                "softmax or group-limited routing is not in this block")
        d = int(cfg["hidden_size"])
        moe = None
        if "moe" in mlps:
            width = int(cfg["moe_intermediate_size"])
            moe = MoEConfig(
                num_experts=int(key("num_experts", "n_routed_experts")),
                top_k=int(cfg["num_experts_per_tok"]), width=width,
                shared_width=width * int(key(
                    "num_shared_experts", "n_shared_experts", default=0)),
                route_norm=bool(key("route_norm", "norm_topk_prob",
                                    default=True)),
                route_scale=float(key("route_scale", "routed_scaling_factor",
                                      default=1.0)),
                experts_held=experts_held)
        heads, kv = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        rope_part = int(cfg["qk_rope_head_dim"]) if rank else 0
        if rank:
            hd = int(cfg["qk_nope_head_dim"]) + rope_part
        elif cfg.get("head_dim") is None:
            raise KeyError(
                "head_dim: the config states no size of a head (only a "
                "latent-attention config, which says qk_nope_head_dim and "
                "qk_rope_head_dim, goes without)")
        else:
            hd = int(cfg["head_dim"])
        vd = int(cfg.get("v_head_dim") or hd)
        win = (int(cfg.get("swa_num_attention_heads") or heads),
               int(cfg.get("swa_num_key_value_heads") or kv),
               int(cfg.get("swa_head_dim") or hd),
               int(cfg.get("swa_v_head_dim") or vd))
        # the first set of names: see above
        unstated = cfg.get("layer_types") is not None and not rank
        rotating = tuple(cfg.get("rope_layer_kinds",
                                 ("window",) if unstated
                                 else ("full", "window", "latent")))
        sinks = tuple(kind for kind, name in (
            ("full", "add_full_attention_sink_bias"),
            ("window", "add_swa_attention_sink_bias")) if cfg.get(name))
        return cls(
            vocab_size=int(cfg["vocab_size"]), hidden_size=d,
            num_heads=heads, num_kv_heads=kv, head_dim=hd, layer_kinds=kinds,
            mlp_kinds=mlps, dense_width=int(cfg["intermediate_size"]),
            window=int(cfg.get("sliding_window") or 0),
            rope_layers=tuple(k in rotating for k in kinds),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rms_eps=float(key("rms_norm_eps", "layernorm_epsilon",
                              default=1e-5)),
            qk_norm=bool(cfg.get("qk_norm", unstated)),
            attn_gate=bool(cfg.get("attention_output_gate", unstated)),
            sandwich_norm=bool(cfg.get("sandwich_norm", unstated)),
            embed_scale=math.sqrt(d) if cfg.get("mup_enabled") else 1.0,
            max_positions=int(cfg["max_position_embeddings"]), moe=moe,
            v_head_dim=None if vd == hd else vd,
            window_geometry=None if win == (heads, kv, hd, vd) else win,
            rope_dim=None if cfg.get("partial_rotary_factor") is None
            else int(hd * float(cfg["partial_rotary_factor"])),
            window_rope_theta=None if cfg.get("swa_rope_theta") is None
            else float(cfg["swa_rope_theta"]),
            value_scale=float(cfg.get("attention_value_scale") or 1.0),
            sink_kinds=sinks, latent_rank=rank, latent_rope_dim=rope_part)


# ---------------------------------------------------------------------------
# the mathematics (arrays)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def rotary(x, pos, theta, dim=None):
    """x [B, H, T, hd] (float32) at positions pos [B, T]; rotate-half over
    the first `dim` of hd (all of it by default), the rest unrotated."""
    hd = x.shape[-1]
    if dim is not None and dim < hd:
        return jnp.concatenate(
            [rotary(x[..., :dim], pos, theta), x[..., dim:]], -1)
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None, :, None] * inv              # [B,1,T,hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def rotary_pairs(x, pos, theta):
    """x [B, H, T, d] (float32) at positions pos [B, T]: each ADJACENT pair
    (x[2i], x[2i+1]) turned by the angle pos * theta^(-2i/d), which is how
    the latent-attention models rotate (their published code de-interleaves
    a head and then rotates halves: the same rotation, its output permuted
    alike for q and k). The neighbour comes by a lane roll, so no array
    with a minor dimension of 2 is ever made."""
    d = x.shape[-1]
    lane = jnp.arange(d)
    inv = theta ** (-(2 * (lane // 2)).astype(F32) / d)
    ang = pos.astype(F32)[:, None, :, None] * inv              # [B,1,T,d]
    other = jnp.where(lane % 2 == 0, -jnp.roll(x, -1, -1),
                      jnp.roll(x, 1, -1))
    return x * jnp.cos(ang) + other * jnp.sin(ang)


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=F32)


def swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def band_attention(q, k, v, window, sink=None):
    """Causal attention of q [B, Hq, T, dk] over k [B, Hkv, T, dk] and v
    [B, Hkv, T, dv], grouped heads, keys j <= i and (window) i - j <
    window; `sink` [Hq] float32, where given, is one more logit a query
    head in the softmax's denominator that takes no value. -> [B, Hq, T,
    dv]. The Pallas band kernel where it applies, else the masked einsum."""
    from ..ops import pallas_kernels as pk
    out = pk.band_flash_attention_or_none(q, k, v, window, sink)
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
    s = jnp.where(ok, s, _NEG)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        b = jnp.broadcast_to(sink.astype(F32).reshape(1, Hkv, -1, 1, 1),
                             s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, b], -1), axis=-1)[..., :-1]
    o = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(F32))
    return o.reshape(B, Hq, T, v.shape[-1]).astype(q.dtype)


def paged_attention(q, k, v, view, sink=None):
    """One new token a slot against the paged cache: q [B, Hkv, G, dk],
    k [B, Hkv, 1, dk], v [B, Hkv, 1, dv] through `view`
    (serving/cache.LayerCacheView), which appends, attends and leaves the
    carrier updated; `sink` as in `band_attention`."""
    return view.attend(q, k, v, sink)


def _latent_attention(cfg, i, p, h, pos, view=None):
    """`_attention` of a latent layer; returns beside its output the rows
    the cache keeps, [B, T, latent_rank + latent_rope_dim] = (normed latent
    | rotary part), and no v. Without `view` the sequence's latents are
    EXPANDED into every head's keys and values and attended causally. With it
    the two up-projections are ABSORBED: the query is taken into the
    latent space, attends the cached rows themselves (each key and value at
    once) and its output comes back through the values' up-projection.
    Either way the softmax scale is the expanded key's, 1/sqrt(head_dim)."""
    from ..ops import pallas_kernels as pk
    B, T, _ = h.shape
    H, r, dr = cfg.num_heads, cfg.latent_rank, cfg.latent_rope_dim
    dk, dv = cfg.head_dim, cfg.v_head_dim or cfg.head_dim
    dn = dk - dr
    dt = p["wq"].dtype
    a = rms_norm(h, p["attn_norm"], cfg.rms_eps).astype(dt)
    q = _mm(a, p["wq"])
    if dk % 128:         # as `_attention`'s `keyed`: relay out rows, not wq
        q = jax.lax.optimization_barrier(q)
    q = q.reshape(B, T, H, dk).transpose(0, 2, 1, 3)           # [B,H,T,dk]
    row = _mm(a, p["wkv_a"])                                   # [B,T,r+dr]
    c = rms_norm(row[..., :r], p["kv_norm"], cfg.rms_eps)
    q_rope, k_rope = q[..., dn:], row[:, None, :, r:]          # one k head
    if cfg.rope_layers[i]:
        theta = cfg.theta("latent")
        q_rope = rotary_pairs(q_rope, pos, theta)
        k_rope = rotary_pairs(k_rope, pos, theta)
    c, k_rope = c.astype(dt), k_rope.astype(dt)
    rows = jnp.concatenate([c, k_rope[:, 0]], -1)      # what the cache keeps
    up = p["wkv_b"].reshape(r, H, dn + dv)     # a head: (keys' | values')
    scale = 1.0 / math.sqrt(dk)
    if view is None:
        pk._note_attn_path("latent_expanded")
        with jax.named_scope("mla_expand"):
            kv = jnp.einsum("btr,rhn->bhtn", c, up,
                            preferred_element_type=F32).astype(dt)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_rope, (B, H, T, dr))], -1)
        q = jnp.concatenate([q[..., :dn], q_rope], -1).astype(dt)
        o = band_attention(q, k, kv[..., dn:], 0)              # [B,H,T,dv]
    else:
        with jax.named_scope("mla_absorb"):
            q_lat = jnp.einsum("bhtn,rhn->bthr", q[..., :dn].astype(dt),
                               up[..., :dn], preferred_element_type=F32)
        q_row = jnp.concatenate(
            [q_lat, q_rope.transpose(0, 2, 1, 3)], -1).astype(dt)
        o_lat = view.attend(q_row, rows[:, None], None,
                            scale=scale)                       # [B,1,H,r]
        with jax.named_scope("mla_absorb"):
            o = jnp.einsum("bthr,rhn->bhtn", o_lat.astype(dt),
                           up[..., dn:], preferred_element_type=F32)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * dv).astype(F32)
    return _mm(o, p["wo"]), rows, None


def _attention(cfg, i, p, h, pos, view=None):
    """Attention branch of layer i on h [B, T, d] (float32) at positions
    pos [B, T]. Without `view`: the whole sequence, returning this
    layer's k [B, Hkv, T, dk] and v [B, Hkv, T, dv] for the cache (a
    latent layer: its rows and None); with it: one token a slot through
    the paged cache."""
    B, T, _ = h.shape
    kind = cfg.layer_kinds[i]
    if kind == "latent":
        return _latent_attention(cfg, i, p, h, pos, view)
    Hq, Hkv, dk, dv = cfg.geometry(kind)
    dt = p["wq"].dtype
    a = rms_norm(h, p["attn_norm"], cfg.rms_eps).astype(dt)
    heads = lambda y, n, w: y.reshape(B, T, n, w).transpose(0, 2, 1, 3)  # noqa

    def keyed(w, n):
        """A projection split into n heads of the key size. Heads that are
        no whole number of 128-lane tiles make the split a relayout, and
        left to itself XLA relays out the WEIGHT (compiled for a v5e: a
        `copy` of bf16[4096, 12288] a layer in every step) to get the
        product head-major; behind the barrier it relays out the few rows
        of the product."""
        y = _mm(a, w)
        if dk % 128:
            y = jax.lax.optimization_barrier(y)
        return heads(y, n, dk)

    q, k = keyed(p["wq"], Hq), keyed(p["wk"], Hkv)
    v = heads(_mm(a, p["wv"]), Hkv, dv)
    if cfg.value_scale != 1.0:
        v = v * cfg.value_scale
    v = v.astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_layers[i]:
        theta, dim = cfg.theta(kind), cfg.rotary_dim(kind)
        q, k = rotary(q, pos, theta, dim), rotary(k, pos, theta, dim)
    q, k = q.astype(dt), k.astype(dt)
    # a layer without a sink calls the two as it always has
    sink = {"sink": p["sink"]} if "sink" in p else {}
    if view is None:
        window = cfg.window if kind == "window" else 0
        o = band_attention(q, k, v, window, **sink)           # [B,Hq,T,dv]
    else:
        o = paged_attention(q.reshape(B, Hkv, Hq // Hkv, dk), k, v, view,
                            **sink)
        o = o.reshape(B, Hq, 1, dv)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, Hq * dv).astype(F32)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(_mm(a, p["wg"]))
    return _mm(o, p["wo"]), k, v


def moe_layer(mc, p, x):
    """The expert layer on tokens x [N, d]: (float32 [N, d], int32
    [E_held] assignments each held expert got)."""
    first, held = mc.held
    chosen, w = moe_ops.sigmoid_topk_route(
        x, p["router"], p["expert_bias"], mc.top_k, mc.route_norm,
        mc.route_scale)
    # the router's width goes along only where it scores more experts
    # than are held here, so a layer that holds them all makes the call
    # it made before `experts` existed (wrappers of the older signature)
    E = p["router"].shape[1]
    share = {"experts": E} if held < E else {}
    out, sizes = moe_ops.grouped_experts(
        x.astype(p["e_gate"].dtype), chosen, w, p["e_gate"], p["e_up"],
        p["e_down"], first=first, **share)
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


def route_stats(sizes, share=False):
    """int32 [2] from the expert layers' assignment counts: experts that
    got at least one assignment, and the fullest expert's count, each
    summed over the layers (the host divides by the layers). Where the
    layers hold a `share` of their experts, a third: the assignments that
    fell on the experts held."""
    if not sizes:
        return None
    stats = [sum(jnp.sum(s > 0) for s in sizes),
             sum(jnp.max(s) for s in sizes)]
    if share:
        stats.append(sum(jnp.sum(s) for s in sizes))
    return jnp.stack(stats).astype(jnp.int32)


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
    d = cfg.hidden_size
    kind = cfg.layer_kinds[i]
    hq, hkv, hd, vd = cfg.geometry(kind)
    gain, mat = (1.0, 0.02), (0.0, 0.02)
    out = {"attn_norm": ((d,),) + gain, "wq": ((d, hq * hd),) + mat}
    if kind == "latent":
        r, dr = cfg.latent_rank, cfg.latent_rope_dim
        out.update(wkv_a=((d, r + dr),) + mat, kv_norm=((r,),) + gain,
                   wkv_b=((r, hq * (hd - dr + vd)),) + mat)
    else:
        out.update(wk=((d, hkv * hd),) + mat, wv=((d, hkv * vd),) + mat)
    if cfg.attn_gate:
        out["wg"] = ((d, hq * vd),) + mat
    if cfg.qk_norm:
        out["q_norm"] = ((hd,),) + gain
        out["k_norm"] = ((hd,),) + gain
    if kind in cfg.sink_kinds:
        out["sink"] = ((hq,), 0.0, 1.0)
    out["wo"] = ((hq * vd, d),) + mat
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
    return {k: v + (F32 if k in ("expert_bias", "sink") else dtype,)
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
        # one chip's share of the experts: the steps then count what fell
        # on it (`route_stats`)
        self._share = cfg.moe is not None \
            and cfg.moe.held[1] < cfg.moe.num_experts
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
        whole sequence, no cache (a latent layer's k is its cache rows
        [B, T, latent_rank + latent_rope_dim], its v None). `last_row`
        (traced int) keeps the head to that one position: logits
        [B, 1, V]."""
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
        return self._logits(h), ks, vs, route_stats(sizes, self._share)

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
        return self._logits(h), route_stats(sizes, self._share)

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
        self.layer_kinds, self.window = cfg.layer_kinds, cfg.window
        geo = {kind: cfg.geometry(kind) for kind in set(cfg.layer_kinds)}
        self.kv_geometry = {kind: g[1:] for kind, g in geo.items()}
        self.max_positions = cfg.max_positions
        self.moe_layers = cfg.mlp_kinds.count("moe")
        self.moe_top_k = cfg.moe.top_k if cfg.moe else 0
        self.moe_experts = cfg.moe.num_experts if cfg.moe else 0
        # a share of the experts takes the held-share grouped product
        held = ("held_grouped",) if model._share else ()
        if "latent" in geo:
            # a row of the cache is (latent | rotary part): no per-head
            # key, no value; the absorbed decode over those rows and the
            # band prefill at one query head a key head
            self.kv_geometry = {
                "latent": (cfg.latent_rank, cfg.latent_rope_dim)}
            self.selfchecks = ("paged_latent", "band_flash_latent") + held
            return
        # the grouped-query decode over rings and over full rows, and the
        # band prefill; each also at a key size that is not the value
        # size with a sink in the softmax, where a layer has either
        self.selfchecks = ("paged_gqa", "band_flash") + held
        if cfg.sink_kinds or any(g[2] != g[3] for g in geo.values()):
            self.selfchecks += ("paged_gqa_sink", "band_flash_sink")
        full = geo.get("full")
        if full and full[0] == full[1] and full[2] == full[3] \
                and "full" not in cfg.sink_kinds:
            # one query head a key-value head: a full layer's decode
            # takes GPT's kernel (serving/cache.LayerCacheView.attend)
            self.selfchecks += ("paged",)

    def prefill(self, ids, true_len):
        logits, ks, vs, stats = self.model.run(ids, last_row=true_len - 1)
        return logits, ks, vs, stats

    def decode(self, last, views):
        return self.model.step(last, views)

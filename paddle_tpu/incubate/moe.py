"""Mixture-of-Experts layer with expert parallelism over the "ep" mesh axis.

TPU-native counterpart of the reference's MoE stack (the all-to-all
dispatch ops `global_scatter`/`global_gather`,
/root/reference/python/paddle/distributed/utils.py:57,151 over
operators/collective/global_scatter_op.cu.cc): where the reference routes
variable-size token buffers between expert ranks with ncclSend/Recv loops,
the TPU realization is the GShard einsum formulation — fixed expert
capacity, one-hot dispatch/combine tensors, and batched-over-experts FFN
einsums. Sharding the expert dimension over the "ep" mesh axis makes XLA
insert the token all-to-alls over ICI automatically; there is no
hand-rolled exchange, no dynamic shapes, and the whole layer fuses into
the surrounding compiled train step.

Gating: top-k (default 2) with normalized gate weights, fixed capacity
C = ceil(S / E · capacity_factor · k), GShard load-balancing auxiliary
loss (E · Σ_e mean_prob_e · frac_tokens_e) exposed as `layer.l_aux` for
the training loss. Tokens over capacity are dropped (their combine weight
is zero — the residual path of the surrounding transformer carries them),
matching the standard capacity-based semantics.
"""
from __future__ import annotations

import math

from ..nn import functional as F
from ..nn.layer_base import Layer

try:  # optional: only needed when an "ep" mesh axis is active
    from jax.sharding import PartitionSpec as P
    from ..distributed.fleet.meta_parallel.mp_layers import constrain
except Exception:  # pragma: no cover
    P = None
    constrain = None


def _ep_constrain(t, spec_head):
    """Pin the expert dim of a traced activation to the "ep" axis (no-op
    outside a mesh trace or when the mesh has no ep axis)."""
    if constrain is None:
        return t
    return constrain(t, P(*spec_head, *([P.UNCONSTRAINED]
                                        * (t.ndim - len(spec_head)))))


class MoELayer(Layer):
    """Position-wise MoE FFN: y[token] = Σ_chosen gate · expert(token).

    Args:
        d_model: token width.
        d_hidden: expert FFN hidden width.
        num_experts: total experts E (sharded over "ep" when present).
        top_k: experts per token (1 or 2).
        capacity_factor: slack over the perfectly-balanced S·k/E.
        activation: expert nonlinearity name in paddle.nn.functional.
        normalize_gates: renormalize the k gate values to sum to 1.

    Expert parameters are stacked on a leading expert dim with
    `sharding_spec = P("ep", ...)` — under a mesh whose "ep" degree
    divides E, each device holds E/ep experts and XLA converts the
    dispatch/combine einsums into all-to-alls over ICI. Everything is a
    framework primitive, so the layer trains on the eager tape and inside
    compiled/pjit steps alike.
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, activation="gelu",
                 normalize_gates=True, name=None):
        super().__init__()
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 or 2, got %r" % (top_k,))
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = float(capacity_factor)
        self.activation = activation
        self.normalize_gates = normalize_gates

        self.gate_weight = self.create_parameter(
            shape=[d_model, num_experts])
        self.w1 = self.create_parameter(
            shape=[num_experts, d_model, d_hidden])
        self.b1 = self.create_parameter(shape=[num_experts, d_hidden],
                                        is_bias=True)
        self.w2 = self.create_parameter(
            shape=[num_experts, d_hidden, d_model])
        self.b2 = self.create_parameter(shape=[num_experts, d_model],
                                        is_bias=True)
        if P is not None:
            self.w1.sharding_spec = P("ep", None, None)
            self.b1.sharding_spec = P("ep", None)
            self.w2.sharding_spec = P("ep", None, None)
            self.b2.sharding_spec = P("ep", None)
        # Aux-loss plumbing (see the l_aux property): the registered
        # buffer rides the compiled-step engine's buffer round-trip (like
        # BN running stats) so post-step eager reads see the concrete
        # value; the live tensor keeps the differentiable tape/trace link.
        import numpy as _np
        from ..framework.tensor import Tensor as _T
        self._l_aux_buf = self.register_buffer(
            "l_aux_value", _T(_np.zeros((), _np.float32)))
        self._l_aux_live = None

    def capacity(self, n_tokens):
        return max(1, int(math.ceil(
            n_tokens / self.num_experts * self.capacity_factor
            * self.top_k)))

    @property
    def l_aux(self):
        """Load-balance auxiliary loss of the latest forward.

        Add `coef * layer.l_aux` to the training loss and it backprops
        into the gate — on the eager tape (the live tensor carries the
        tape node) and inside a jit trace (the buffer's `_data` is
        aliased to the live tracer by forward, so the read is the same
        differentiable tracer). After a compiled step the engine's
        buffer round-trip leaves the concrete value, so
        `float(net.moe.l_aux.numpy())` logs a number instead of raising
        on a leaked tracer; a trace that reads l_aux WITHOUT this
        layer's forward having run sees the last concrete value as a
        constant."""
        live = self._l_aux_live
        if live is not None:
            import jax
            if not isinstance(live._data, jax.core.Tracer):
                return live       # eager: fully tape-linked
        return self._l_aux_buf

    def forward(self, x):
        import paddle_tpu as paddle  # deferred: incubate loads at pkg init
        shape = x.shape
        M, E = self.d_model, self.num_experts
        S = 1
        for s in shape[:-1]:
            S = S * s
        C = self.capacity(S)
        xs = x.reshape([S, M])

        # --- gate (f32 math like every published MoE) -------------------
        logits = paddle.matmul(paddle.cast(xs, "float32"),
                               paddle.cast(self.gate_weight, "float32"))
        probs = F.softmax(logits, axis=-1)                     # [S, E]

        idx1 = paddle.argmax(probs, axis=-1)                   # [S]
        mask1 = F.one_hot(idx1, E)                             # [S, E] f32
        g1 = paddle.sum(probs * mask1, axis=-1)                # [S]

        # GShard load-balance aux loss — differentiable through probs
        me = paddle.mean(probs, axis=0)                        # [E]
        ce = paddle.mean(mask1, axis=0)                        # [E]
        aux = paddle.sum(me * ce) * float(E)
        self._l_aux_live = aux               # tape/trace-linked value
        import jax
        from ..framework import state
        if state.in_trace() or not isinstance(aux._data, jax.core.Tracer):
            # engine buffer round-trip. Under an ENGINE trace (trace_guard)
            # the tracer is collected as a buffer output and replaced with
            # a concrete array after the step; under a USER-owned jax.jit
            # the tracer would simply leak into the persistable buffer and
            # poison every later eager read — keep the previous concrete
            # value there instead (l_aux still flows via _l_aux_live).
            self._l_aux_buf._data = aux._data

        if self.top_k == 2:
            probs2 = probs * (1.0 - mask1)
            idx2 = paddle.argmax(probs2, axis=-1)
            mask2 = F.one_hot(idx2, E)
            g2 = paddle.sum(probs2 * mask2, axis=-1)
            if self.normalize_gates:
                denom = g1 + g2 + 1e-9
                g1, g2 = g1 / denom, g2 / denom

        # --- capacity assignment (positions within each expert) ---------
        pos1 = paddle.cumsum(mask1, axis=0) * mask1            # 1-based
        keep1 = paddle.cast(pos1 <= float(C), "float32") * mask1
        slot1 = paddle.cast(paddle.sum(pos1, axis=-1), "int64") - 1  # [S]
        in1 = paddle.sum(keep1, axis=-1)                       # [S] 0/1

        combine = (g1 * in1).unsqueeze(-1).unsqueeze(-1) \
            * mask1.unsqueeze(-1) \
            * F.one_hot(paddle.clip(slot1, 0, C - 1), C).unsqueeze(1)

        if self.top_k == 2:
            # second choices are placed after ALL first choices of that
            # expert (GShard): offset by the expert's first-choice count
            count1 = paddle.sum(mask1, axis=0, keepdim=True)   # [1, E]
            pos2 = (paddle.cumsum(mask2, axis=0) + count1) * mask2
            keep2 = paddle.cast(pos2 <= float(C), "float32") * mask2
            slot2 = paddle.cast(paddle.sum(pos2, axis=-1), "int64") - 1
            in2 = paddle.sum(keep2, axis=-1)
            combine = combine + (g2 * in2).unsqueeze(-1).unsqueeze(-1) \
                * mask2.unsqueeze(-1) \
                * F.one_hot(paddle.clip(slot2, 0, C - 1), C).unsqueeze(1)

        combine = paddle.cast(combine, x.dtype)                # [S, E, C]
        dispatch = paddle.cast(combine > 0, x.dtype)

        # --- dispatch -> expert FFN -> combine (the all-to-alls live in
        # these einsums once the e dim is pinned to "ep") ----------------
        dispatched = paddle.einsum("sec,sm->ecm", dispatch, xs)
        dispatched = _ep_constrain(dispatched, ("ep",))
        h = paddle.einsum("ecm,emh->ech", dispatched, self.w1) \
            + self.b1.unsqueeze(1)
        h = getattr(F, self.activation)(h)
        h = _ep_constrain(h, ("ep",))
        y = paddle.einsum("ech,ehm->ecm", h, self.w2) \
            + self.b2.unsqueeze(1)
        y = _ep_constrain(y, ("ep",))
        out = paddle.einsum("sec,ecm->sm", combine, y)
        return out.reshape(shape)


# ---------------------------------------------------------------------------
# No-drop routing over the experts held here (arrays in, arrays out).
#
# Where `MoELayer` above gives every expert a fixed capacity and drops what
# overflows, these two functions drop nothing under any imbalance: the
# token-expert assignments are sorted by expert, each expert's rows are one
# group of a grouped matrix product (`jax.lax.ragged_dot`, which XLA lowers
# to a Mosaic grouped matmul on the TPU), and the results are gathered back
# by the inverse permutation. The layer is told which experts it holds: the
# router scores ALL experts and keeps its top-k, and assignments to experts
# that live elsewhere add nothing to this holder's part of the result.


def sigmoid_topk_route(x, router_w, expert_bias, top_k, route_norm=True,
                       route_scale=1.0):
    """(chosen int32 [T, k], weights float32 [T, k]) of tokens x [T, d].

    Scores are sigmoids of a float32 product (precision `highest`: 128
    columns, negligible work, and a rounded score flips the top-k). The
    choice is by score + `expert_bias`, the weight by the score alone,
    normalised over the chosen (`route_norm`) and scaled."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + expert_bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * route_scale


def grouped_experts(x, chosen, weights, e_gate, e_up, e_down, first=0):
    """Σ_k weights[t, k] · SwiGLU_{chosen[t, k]}(x[t]) over the experts
    held here, [first, first + e_gate.shape[0]): float32 [T, d], and the
    int32 [E_held] number of assignments each held expert got.

    x [T, d]; e_gate / e_up [E_held, d, f]; e_down [E_held, f, d]. Every
    assignment to a held expert is computed; there is no capacity."""
    import jax
    import jax.numpy as jnp
    T, k = chosen.shape
    held = e_gate.shape[0]
    local = chosen - jnp.int32(first)
    here = (local >= 0) & (local < held)
    flat = jnp.where(here, local, held).reshape(-1)          # [T·k]
    order = jnp.argsort(flat, stable=True)    # sorted row -> assignment
    sizes = jnp.sum(flat[:, None] == jnp.arange(held, dtype=flat.dtype),
                    axis=0, dtype=jnp.int32)
    xs = x[order // k]                                       # [T·k, d]
    g = jax.lax.ragged_dot(xs, e_gate, sizes)
    u = jax.lax.ragged_dot(xs, e_up, sizes)
    y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(xs.dtype), e_down,
                           sizes)                            # [T·k, d]
    # back to assignment order; rows past the held groups are undefined.
    # The inverse of a permutation is its argsort: a sort is 0.2 ms on the
    # chip at 114 688 assignments where the scatter it replaces was 8.7 ms
    inv = jnp.argsort(order)
    y = jnp.where(here.reshape(-1, 1), y[inv].astype(jnp.float32), 0.0)
    out = jnp.sum(y.reshape(T, k, -1) * weights[..., None], axis=1)
    return out, sizes

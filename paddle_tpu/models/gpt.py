"""GPT decoder-only LM, hybrid-parallel-native (dp x mp x pp x sep).

TPU-first design notes
  * Attention/MLP use the GSPMD tensor-parallel layers
    (distributed/fleet/meta_parallel/mp_layers.py): weights carry
    PartitionSpecs over the "mp" mesh axis, XLA inserts the ICI
    collectives. With mp degree 1 the same code is the single-chip model.
  * The attention math routes through F.scaled_dot_product_attention →
    Pallas flash attention on TPU (ops/pallas_kernels.py), causal.
  * Sequence parallelism: hidden states are sharding-constrained to
    P("dp", "sep", None) between blocks when a "sep" axis exists, so
    LayerNorm/dropout/elementwise work is split along the sequence —
    the reference has NO sequence parallel (SURVEY.md §5); this is the
    idiomatic-TPU upgrade. Ring attention lives in
    distributed/fleet/meta_parallel/sep_utils.py.
  * Pipeline: GPTForPipeline declares the same model as LayerDescs with
    tied input/output embeddings via SharedLayerDesc (reference:
    fleet/meta_parallel/parallel_layers/pp_layers.py:63 and the external
    fleetx GPTForPipeline it hosts).

Reference capability anchors: hybrid layer stack
python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py:30-249,
pp_layers.py:63-132; fused attention
paddle/fluid/operators/fused/fused_attention_op.cu; BASELINE.md config 5.
"""
from __future__ import annotations

import math

import numpy as np

from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layers import Dropout, Embedding, LayerList, LayerNorm, Linear
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, constrain)
from ..distributed.fleet.meta_parallel.pp_layers import (
    LayerDesc, PipelineLayer, SharedLayerDesc)

__all__ = ["GPTModel", "GPTForPretraining", "GPTForPipeline",
           "GPTEmbeddings", "GPTDecoderLayer", "GPTPretrainingCriterion",
           "GPT_CONFIGS", "gpt_tiny", "gpt2_small", "gpt3_1p3b"]


def _seq_spec():
    """Activation spec [B, T, H] with batch on the data axes and sequence
    on sep (sequence parallelism: LayerNorm/MLP elementwise work splits
    along T between attention calls)."""
    from jax.sharding import PartitionSpec as P
    return P(("dp", "sharding"), "sep", None)


class GPTEmbeddings(Layer):
    """Word + learned-position embeddings (vocab sharded over mp)."""

    def __init__(self, vocab_size, hidden_size, max_position_embeddings,
                 hidden_dropout_prob=0.1, initializer_range=0.02):
        super().__init__()
        init = I.Normal(0.0, initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            vocab_size, hidden_size)
        self.word_embeddings.weight.set_value(
            init((vocab_size, hidden_size), "float32"))
        self.position_embeddings = Embedding(
            max_position_embeddings, hidden_size,
            weight_attr=None)
        self.position_embeddings.weight.set_value(
            init((max_position_embeddings, hidden_size), "float32"))
        self.dropout = Dropout(hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        import jax.numpy as jnp
        T = input_ids.shape[-1]
        wemb = self.word_embeddings(input_ids)
        if position_ids is None:
            pos = Tensor(jnp.arange(T, dtype=jnp.int32), _internal=True)
        else:
            pos = position_ids
        pemb = self.position_embeddings(pos)
        x = wemb + pemb
        return constrain(self.dropout(x), _seq_spec())


def _prefix_concat_attention(q, k, v, prefix_len):
    """Suffix-prefill attention: Tq suffix queries over prefix+suffix keys.

    Query i sits at ABSOLUTE position prefix_len + i, so it may attend
    keys j <= prefix_len + i — a bottom-right-aligned causal mask. The
    plain `is_causal` path aligns top-left (query i sees keys j <= i),
    which would hide the reused prefix from every early suffix query;
    that is why the serving engine's prefix-hit path needs its own mask.
    Right-padding within the suffix bucket stays exact: a pad key at
    absolute position prefix_len + j is visible only to queries i >= j,
    which are themselves pad.
    """
    import jax
    import jax.numpy as jnp
    qa, ka, va = q._data, k._data, v._data
    scale = 1.0 / math.sqrt(qa.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", qa.astype(jnp.float32),
                        ka.astype(jnp.float32)) * scale
    tq, tk = qa.shape[2], ka.shape[2]
    valid = (jnp.arange(tk)[None, :]
             <= (jnp.int32(prefix_len) + jnp.arange(tq))[:, None])
    scores = jnp.where(valid[None, None], scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, va.astype(jnp.float32))
    return Tensor(out.astype(qa.dtype), _internal=True)


class GPTAttention(Layer):
    """Causal self-attention: fused QKV column-parallel, out row-parallel.

    Heads divide across mp (the fused QKV output dim is sharded), matching
    the reference's head-parallel fused attention
    (operators/fused/fused_attention_op.cu) without hand-written
    collectives."""

    def __init__(self, hidden_size, num_heads, attn_dropout_prob=0.1,
                 hidden_dropout_prob=0.1, use_flash=True):
        super().__init__()
        assert hidden_size % num_heads == 0
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.hidden_size = hidden_size
        self.attn_dropout_prob = attn_dropout_prob
        self.qkv_proj = ColumnParallelLinear(
            hidden_size, 3 * hidden_size, gather_output=False)
        self.out_proj = RowParallelLinear(
            hidden_size, hidden_size, input_is_parallel=True)

    def forward(self, x, cache=None):
        from ..ops import manipulation as mp
        B, T = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)                      # [B, T, 3H] mp-sharded
        qkv = qkv.reshape((B, T, 3, self.num_heads, self.head_dim))
        qkv = qkv.transpose((2, 0, 3, 1, 4))        # [3, B, nh, T, hd]
        q, k, v = qkv[0], qkv[1], qkv[2]
        if cache is not None and hasattr(cache, "lens"):
            # serving path: one new token a slot through the paged cache
            # (inference/serving/cache.LayerCacheView.attend; T == 1)
            out = Tensor(cache.attend(q._data, k._data, v._data),
                         _internal=True)
            out = out.transpose((0, 2, 1, 3)).reshape(
                (B, T, self.hidden_size))
            return self.out_proj(out), cache
        if cache is not None:
            prefix_len = cache[0].shape[2]
            if prefix_len:
                # an empty cache is not concatenated: K and V stay in the
                # model's dtype whatever dtype the empty arrays have
                k = mp.concat([cache[0], k], axis=2)
                v = mp.concat([cache[1], v], axis=2)
            cache = (k, v)
            if q.shape[2] > 1 and prefix_len > 0:
                # serving suffix-prefill: multi-token queries behind a
                # non-empty cache need the bottom-right causal mask
                out = _prefix_concat_attention(q, k, v, prefix_len)
                out = out.transpose((0, 2, 1, 3)).reshape(
                    (B, T, self.hidden_size))
                return self.out_proj(out), cache
        causal = cache is None or q.shape[2] > 1
        out = None
        if cache is not None and causal and not self.training:
            # a whole prompt into a cache of a model in eval mode is
            # inference (no backward, no lse, no dropout): the band
            # kernel's one query head a key head, window 0 case, as the
            # decoder family's prompts take it; it bears no name, so a
            # trace reads it under this prefill's own
            # (`custom-call._prefill_fn`)
            from ..ops import pallas_kernels as pk
            out = pk.band_flash_attention_or_none(
                q._data, k._data, v._data, 0, named=False)
            if out is not None:
                out = Tensor(out, _internal=True)
        if cache is None:
            # sequence-parallel ring/ulysses attention when a sep axis is
            # active (sep_utils; NEW vs reference — SURVEY.md §5)
            from ..distributed.fleet.meta_parallel.sep_utils import (
                sep_attention_or_none)
            out = sep_attention_or_none(
                q, k, v, causal=causal, dropout_p=self.attn_dropout_prob,
                training=self.training)
        if out is None:
            out, _ = F.scaled_dot_product_attention(
                q, k, v, is_causal=causal,
                dropout_p=self.attn_dropout_prob, training=self.training)
        out = out.transpose((0, 2, 1, 3)).reshape((B, T, self.hidden_size))
        # dropout + residual-add are fused by the caller (GPTDecoderLayer)
        out = self.out_proj(out)
        return out if cache is None else (out, cache)


class GPTMLP(Layer):
    def __init__(self, hidden_size, intermediate_size,
                 hidden_dropout_prob=0.1):
        super().__init__()
        self.fc1 = ColumnParallelLinear(hidden_size, intermediate_size,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(intermediate_size, hidden_size,
                                     input_is_parallel=True)

    def forward(self, x):
        # dropout + residual-add are fused by the caller (GPTDecoderLayer)
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(Layer):
    """Pre-LN transformer decoder block.

    moe_num_experts > 0 swaps the dense MLP for an expert-parallel
    MoELayer (incubate/moe.py, GShard dispatch over the "ep" mesh axis)
    — the GPT-MoE configuration of the reference ecosystem, TPU-native."""

    def __init__(self, hidden_size, num_heads, intermediate_size=None,
                 attn_dropout_prob=0.1, hidden_dropout_prob=0.1,
                 layer_norm_epsilon=1e-5, moe_num_experts=0, moe_top_k=2,
                 moe_capacity_factor=1.25):
        super().__init__()
        inter = intermediate_size or 4 * hidden_size
        self.ln_1 = LayerNorm(hidden_size, epsilon=layer_norm_epsilon)
        self.attn = GPTAttention(hidden_size, num_heads, attn_dropout_prob,
                                 hidden_dropout_prob)
        self.ln_2 = LayerNorm(hidden_size, epsilon=layer_norm_epsilon)
        if moe_num_experts:
            from ..incubate.moe import MoELayer
            self.mlp = MoELayer(hidden_size, inter, moe_num_experts,
                                top_k=moe_top_k,
                                capacity_factor=moe_capacity_factor)
        else:
            self.mlp = GPTMLP(hidden_size, inter, hidden_dropout_prob)
        self.dropout = Dropout(hidden_dropout_prob)

    def _residual_dropout(self, h, residual):
        """Pre-LN residual tail: residual + dropout(h), one fused Pallas
        pass off-mesh (reference: fused_dropout_helper.h
        LaunchResidualDropoutBias); composed ops under GSPMD meshes (the
        sharded step lets XLA own layout) and for gate-rejected shapes."""
        from ..framework import state
        if state.current_mesh() is None:
            from ..incubate.nn.functional import fused_bias_dropout_residual
            return fused_bias_dropout_residual(
                h, residual, None, self.dropout.p, training=self.training,
                mode=self.dropout.mode)
        return residual + self.dropout(h)

    def _fused_block_ok(self):
        """Decoder-block fusion opt-in (FLAGS_fused_block): the attention
        epilogue (residual dropout-add) and ln_2 run as ONE Pallas pass
        (fused_bias_dropout_residual_ln_pair), so the post-attention
        activation never round-trips HBM between the residual add and
        the LN read. Off-mesh only — under GSPMD meshes XLA owns layout
        and fusing by hand would fight the partitioner."""
        from ..framework import state
        from ..framework.flags import flag
        return flag("fused_block") and state.current_mesh() is None

    def forward(self, x, cache=None):
        if cache is None and self._fused_block_ok():
            from ..incubate.nn.functional import (
                fused_bias_dropout_residual_ln_pair)
            a = self.attn(self.ln_1(x))
            # y = ln_2(z), z = x + dropout(a): one pass, two outputs
            y, z = fused_bias_dropout_residual_ln_pair(
                a, x, None, self.ln_2.weight, self.ln_2.bias,
                self.dropout.p, self.ln_2._epsilon, self.training,
                self.dropout.mode)
            x = self._residual_dropout(self.mlp(y), z)
            x = constrain(x, _seq_spec())
            return x
        if cache is None:
            x = self._residual_dropout(self.attn(self.ln_1(x)), x)
        else:
            a, cache = self.attn(self.ln_1(x), cache)
            x = self._residual_dropout(a, x)
        x = self._residual_dropout(self.mlp(self.ln_2(x)), x)
        x = constrain(x, _seq_spec())
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    """Embeddings + N decoder blocks + final LN → hidden states."""

    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, attn_dropout_prob=0.1,
                 hidden_dropout_prob=0.1, layer_norm_epsilon=1e-5,
                 initializer_range=0.02, moe_every_n_layers=0,
                 moe_num_experts=8, moe_top_k=2, moe_capacity_factor=1.25):
        super().__init__()
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.embeddings = GPTEmbeddings(
            vocab_size, hidden_size, max_position_embeddings,
            hidden_dropout_prob, initializer_range)
        # moe_every_n_layers=n: every n-th block's MLP is an MoELayer
        # (GPT-MoE, e.g. n=2 = alternating dense/MoE like GShard)
        self.layers = LayerList([
            GPTDecoderLayer(
                hidden_size, num_heads, intermediate_size,
                attn_dropout_prob, hidden_dropout_prob, layer_norm_epsilon,
                moe_num_experts=(moe_num_experts if moe_every_n_layers
                                 and (i + 1) % moe_every_n_layers == 0
                                 else 0),
                moe_top_k=moe_top_k,
                moe_capacity_factor=moe_capacity_factor)
            for i in range(num_layers)])
        self.ln_f = LayerNorm(hidden_size, epsilon=layer_norm_epsilon)

    def moe_aux_loss(self):
        """Sum of the MoE load-balance losses of the latest forward —
        add `coef * model.moe_aux_loss()` to the training loss. A zero
        scalar Tensor when the model has no MoE blocks, so config-generic
        code can call .numpy() either way."""
        from ..framework.tensor import Tensor
        from ..incubate.moe import MoELayer
        total = None
        for blk in self.layers:
            if isinstance(blk.mlp, MoELayer):
                total = blk.mlp.l_aux if total is None \
                    else total + blk.mlp.l_aux
        if total is None:
            return Tensor(np.zeros((), np.float32), _internal=True)
        return total

    def serving(self):
        return _GPTServing(self)

    def forward(self, input_ids, position_ids=None, caches=None):
        x = self.embeddings(input_ids, position_ids)
        if caches is None:
            for blk in self.layers:
                x = blk(x)
            return self.ln_f(x)
        new_caches = []
        for blk, c in zip(self.layers, caches):
            x, c = blk(x, c)
            new_caches.append(c)
        return self.ln_f(x), new_caches


def _lm_logits(hidden, word_embedding_weight):
    """Tied LM head: logits = h @ W_e^T, vocab dim mp-sharded like the
    reference's parallel_matmul over c_identity/allreduce."""
    from ..ops import math as m
    from jax.sharding import PartitionSpec as P
    logits = m.matmul(hidden, word_embedding_weight, transpose_y=True)
    # batch dim left UNCONSTRAINED: the engine owns the batch layout
    # (dp, or dp×sharding under ZeRO — jit/engine.py _batch_spec); a bare
    # "dp" here conflicted with it and forced SPMD full-rematerialization
    # of every decoder activation (r3 VERDICT)
    return constrain(logits, P(P.UNCONSTRAINED, "sep", "mp"))


class _GPTServing:
    """What `inference/serving/engine.GenerationEngine` asks of a model,
    answered for GPT: every layer keeps all its rows, one key-value head
    a query head, learned positions, the head tied to the embedding."""

    prefix_cache = True
    selfchecks = ("paged", "band_flash_mha")
    window = 0
    moe_layers = moe_top_k = moe_experts = 0

    def __init__(self, gpt):
        self._gpt = gpt
        self.n_layers = len(gpt.layers)
        attn = gpt.layers[0].attn
        self.kv_heads, self.head_dim = attn.num_heads, attn.head_dim
        self.kv_geometry = {
            "full": (self.kv_heads, self.head_dim, self.head_dim)}
        self.layer_kinds = ("full",) * self.n_layers
        self.max_positions = \
            gpt.embeddings.position_embeddings.weight.shape[0]

    def _head(self, hidden):
        return _lm_logits(
            hidden, self._gpt.embeddings.word_embeddings.weight)._data

    def prefill(self, ids, true_len, prefix=None):
        """ids [1, Tb] -> (logits [1, 1, V] of row true_len - 1, k and v
        [1, nh, T, hd] a layer, None). With `prefix` (k, v stacked
        [L, 1, nh, p, hd]) ids are the suffix behind it and the returned
        k/v are prefix + suffix."""
        import jax
        import jax.numpy as jnp
        gpt = self._gpt
        if prefix is None:
            pos = None
            # no rows yet: the layers hand back their own K and V, in the
            # model's dtype (an empty cache is never concatenated)
            empty = Tensor(jnp.zeros(
                (1, self.kv_heads, 0, self.head_dim),
                gpt.embeddings.word_embeddings.weight._data.dtype),
                _internal=True)
            legacy = [(empty, empty)] * self.n_layers
        else:
            pk, pv = prefix
            legacy = [(Tensor(pk[i], _internal=True),
                       Tensor(pv[i], _internal=True))
                      for i in range(self.n_layers)]
            pos = Tensor(jnp.arange(int(ids.shape[1]), dtype=jnp.int32)
                         + jnp.int32(int(pk.shape[3])), _internal=True)
        hidden, kvs = gpt(Tensor(ids, _internal=True), pos, legacy)
        h_last = jax.lax.dynamic_slice(
            hidden._data, (jnp.int32(0), true_len - 1, jnp.int32(0)),
            (1, 1, gpt.hidden_size))
        logits = self._head(Tensor(h_last, _internal=True))
        return logits, [c[0]._data for c in kvs], \
            [c[1]._data for c in kvs], None

    def decode(self, last, views):
        import jax.numpy as jnp
        # new token's absolute position == tokens already resident;
        # clamped so a slot that hit the wall indexes a real row
        pos = jnp.minimum(views[0].lens, self.max_positions - 1)[:, None]
        hidden, _ = self._gpt(Tensor(last, _internal=True),
                              Tensor(pos.astype(jnp.int32), _internal=True),
                              views)
        return self._head(hidden), None


class GPTForPretraining(Layer):
    def __init__(self, gpt: GPTModel):
        super().__init__()
        self.gpt = gpt

    def serving(self):
        return _GPTServing(self.gpt)

    def forward(self, input_ids, position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        return _lm_logits(hidden, self.gpt.embeddings.word_embeddings.weight)

    def to_pipeline(self, num_stages, seg_method="layer:GPTDecoderLayer",
                    **pipe_kwargs) -> "GPTForPipeline":
        """Partitioner hand-off (r4 VERDICT item 3): rebuild this model as
        a GPTForPipeline with `num_stages` stages and COPY the weights
        across, so an auto-parallel plan that chose pp>1 can be applied to
        the already-built eager model (the reference's partitioner slices
        the serialized program instead —
        distributed/auto_parallel/partitioner.py:846)."""
        from functools import partial as _partial

        from ..incubate.moe import MoELayer
        if any(isinstance(b.mlp, MoELayer) for b in self.gpt.layers):
            raise NotImplementedError(
                "to_pipeline for MoE blocks is not supported yet — "
                "expert-parallel GPT shards over the ep axis instead "
                "(hybrid_configs['ep_degree'])")
        g = self.gpt
        emb = g.embeddings
        blk = g.layers[0]
        pipe = GPTForPipeline(
            vocab_size=g.vocab_size, hidden_size=g.hidden_size,
            num_layers=len(g.layers), num_heads=blk.attn.num_heads,
            intermediate_size=blk.mlp.fc1.weight.shape[1],
            max_position_embeddings=emb.position_embeddings.weight.shape[0],
            attn_dropout_prob=blk.attn.attn_dropout_prob,
            hidden_dropout_prob=blk.dropout.p,
            layer_norm_epsilon=getattr(g.ln_f, "_epsilon", 1e-5),
            num_stages=num_stages, seg_method=seg_method, **pipe_kwargs)
        # structural weight copy: run_function = [embed, blocks..., ln, head]
        # where the head shares the embed object (tied weights both here
        # and in GPTForPipeline, so one copy covers both ends)
        srcs = [emb] + list(g.layers) + [g.ln_f]
        copied = set()
        for src, dst in zip(srcs, pipe.run_function):
            dst_layer = dst.args[0] if isinstance(dst, _partial) else dst
            sd = src.state_dict()
            for name, p in dst_layer.named_parameters():
                if name not in sd:
                    raise RuntimeError(
                        f"to_pipeline weight copy: {type(dst_layer).__name__}"
                        f".{name} has no counterpart in "
                        f"{type(src).__name__} — the pipeline layout "
                        "drifted from the eager model; a silent skip here "
                        "would leave the parameter at random init")
                p.set_value(np.asarray(sd[name].numpy()))
                copied.add(id(p))
        uncovered = [n for n, p in pipe.named_parameters()
                     if id(p) not in copied]
        if uncovered:
            raise RuntimeError(
                f"to_pipeline weight copy left parameters at random init: "
                f"{uncovered}")
        return pipe

    def generate(self, input_ids, max_new_tokens=16):
        """Greedy decode with per-layer KV caches (inference path)."""
        from ..ops import creation as cr, manipulation as mp, math as m
        caches = None
        ids = input_ids
        out = input_ids
        pos0 = 0
        for _ in range(max_new_tokens):
            if caches is None:
                B, T = ids.shape
                zeros = [(cr.zeros((B, blk.attn.num_heads, 0,
                                    blk.attn.head_dim), "float32"),
                          cr.zeros((B, blk.attn.num_heads, 0,
                                    blk.attn.head_dim), "float32"))
                         for blk in self.gpt.layers]
                hidden, caches = self.gpt(ids, None, zeros)
                pos0 = T
            else:
                import jax.numpy as jnp
                pos = Tensor(np.asarray([pos0], np.int32), _internal=True)
                hidden, caches = self.gpt(ids, pos, caches)
                pos0 += 1
            logits = _lm_logits(
                hidden[:, -1:], self.gpt.embeddings.word_embeddings.weight)
            nxt = m.argmax(logits, axis=-1).astype("int64")
            ids = nxt
            out = mp.concat([out, nxt], axis=1)
        return out


class GPTPretrainingCriterion(Layer):
    """Masked next-token CE; class dim may be mp-sharded
    (reference: mp_layers.py:249 ParallelCrossEntropy)."""

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        from ..ops import math as m
        loss = self.ce(logits, labels)              # [B, T]
        if loss_mask is not None:
            mask = loss_mask.reshape(loss.shape).astype(loss.dtype)
            return m.sum(loss * mask) / m.clip(m.sum(mask), 1e-6, None)
        return m.mean(loss)


# ---------------------------------------------------------------------------
# pipeline variant


class _EmbeddingPipe(GPTEmbeddings):
    """Embedding stage; also serves as the tied LM head on the last stage
    (SharedLayerDesc re-uses this very object)."""

    def forward(self, input_ids):
        return super().forward(input_ids)


def _head_forward(emb_layer: _EmbeddingPipe, hidden):
    return _lm_logits(hidden, emb_layer.word_embeddings.weight)


class _LNPipe(LayerNorm):
    pass


class GPTForPipeline(PipelineLayer):
    """GPT as an ordered LayerDesc list for 1F1B pipeline execution, tied
    embeddings shared between first and last stage (reference:
    pp_layers.py SharedLayerDesc + fleetx GPTForPretrainingPipe)."""

    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None,
                 max_position_embeddings=1024, attn_dropout_prob=0.1,
                 hidden_dropout_prob=0.1, layer_norm_epsilon=1e-5,
                 initializer_range=0.02, num_stages=None, topology=None,
                 seg_method="layer:GPTDecoderLayer", recompute_interval=0,
                 **kwargs):
        descs = [
            SharedLayerDesc(
                "embed", _EmbeddingPipe, forward_func=None,
                shared_weight_attr="word_embeddings.weight",
                vocab_size=vocab_size, hidden_size=hidden_size,
                max_position_embeddings=max_position_embeddings,
                hidden_dropout_prob=hidden_dropout_prob,
                initializer_range=initializer_range),
        ]
        for _ in range(num_layers):
            descs.append(LayerDesc(
                GPTDecoderLayer, hidden_size=hidden_size,
                num_heads=num_heads, intermediate_size=intermediate_size,
                attn_dropout_prob=attn_dropout_prob,
                hidden_dropout_prob=hidden_dropout_prob,
                layer_norm_epsilon=layer_norm_epsilon))
        descs.append(LayerDesc(_LNPipe, hidden_size,
                               epsilon=layer_norm_epsilon))
        descs.append(SharedLayerDesc(
            "embed", _EmbeddingPipe, forward_func=_head_forward,
            shared_weight_attr="word_embeddings.weight",
            vocab_size=vocab_size, hidden_size=hidden_size,
            max_position_embeddings=max_position_embeddings,
            hidden_dropout_prob=hidden_dropout_prob,
            initializer_range=initializer_range))
        criterion = GPTPretrainingCriterion()
        super().__init__(layers=descs, num_stages=num_stages,
                         topology=topology,
                         loss_fn=lambda out, lab: criterion(out, lab),
                         seg_method=seg_method,
                         recompute_interval=recompute_interval, **kwargs)


# ---------------------------------------------------------------------------
# configs

GPT_CONFIGS = {
    # test-scale
    "gpt-tiny": dict(vocab_size=128, hidden_size=64, num_layers=2,
                     num_heads=4, intermediate_size=256,
                     max_position_embeddings=128),
    # GPT-2 124M
    "gpt2-small": dict(vocab_size=50304, hidden_size=768, num_layers=12,
                       num_heads=12, intermediate_size=3072,
                       max_position_embeddings=1024),
    # BASELINE config 5: GPT-3 1.3B
    "gpt3-1.3b": dict(vocab_size=50304, hidden_size=2048, num_layers=24,
                      num_heads=16, intermediate_size=8192,
                      max_position_embeddings=2048),
}


def _make(name, pretraining=True, **overrides):
    cfg = dict(GPT_CONFIGS[name])
    cfg.update(overrides)
    model = GPTModel(**cfg)
    return GPTForPretraining(model) if pretraining else model


def gpt_tiny(**kw):
    return _make("gpt-tiny", **kw)


def gpt2_small(**kw):
    return _make("gpt2-small", **kw)


def gpt3_1p3b(**kw):
    return _make("gpt3-1.3b", **kw)

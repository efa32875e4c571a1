"""Compiled for a described v5e, with no chip attached: the serving path's
Pallas kernel at the benchmark's widths.

The TPU's compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached. Nothing runs; what the chip's
compiler refuses (a misaligned block, an index map Mosaic cannot lower, a
program that does not fit) is refused here, and `memory_analysis()` says
whether XLA had to copy a buffer the program meant to update in place.

The topology is described inside a fixture of THIS file only, so one
xdist worker loads the TPU's library and every worker collects the same
tests. Keep further such compiles in this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels as pk

# gpt3-1.3b as the served cells run it, all 24 layers: every head of a slot
# in one block (128 key rows in bf16, 256 in int8)
L, B, H, T, D = 24, 24, 16, 1024, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _decode_chain(q, kc, vc, ks, vs, lens, nk, nv):
    """What `_decode_fn` does to the cache: every layer's kernel call in
    turn on the one stacked buffer."""
    blk = pk._paged_block(T, H, D, kc.dtype, interpret=False)
    assert pk._paged_heads(H, blk, D, kc.dtype.itemsize) == H
    for layer in range(L):
        q, kc, vc, ks, vs = pk._paged_decode(
            q, kc, vc, lens, nk, nv, ks, vs, layer=layer, block_k=blk,
            interpret=False)
    return q, kc, vc, ks, vs


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_chain_updates_the_cache_in_place(one_chip, kv_dtype):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quantized = kv_dtype == "int8"
    tok = sds((B, H, 1, D), jnp.bfloat16)
    cache = sds((L, B, H, T, D), jnp.dtype(kv_dtype))
    scale = sds((L, B, H, T), jnp.float32) if quantized else None
    compiled = jax.jit(_decode_chain, donate_argnums=(1, 2, 3, 4)).lower(
        tok, cache, cache, scale, scale, sds((B,), jnp.int32), tok, tok
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == L
    # no instruction but the kernels (and the plumbing round them) has a
    # cache stack as its result; a copy of the scales' stack would show in
    # the temporaries below
    stack = "%s[%d,%d,%d,%d,%d]" % ("s8" if quantized else "bf16",
                                    L, B, H, T, D)
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) "
                     r"([\w\-]+)\(", line)
        if m and stack in m.group(2):
            assert m.group(3) in ("custom-call", "parameter", "tuple",
                                  "get-tuple-element", "bitcast"), line
    ma = compiled.memory_analysis()
    cache_bytes = 2 * L * B * H * T * D * jnp.dtype(kv_dtype).itemsize
    scale_bytes = 2 * L * B * H * T * 4 if quantized else 0
    # donated in, aliased through every call, out: one buffer each
    assert ma.alias_size_in_bytes >= cache_bytes + scale_bytes
    # a copy XLA had to insert would be a layer of K or V at the least
    assert ma.temp_size_in_bytes < cache_bytes // (2 * L) // 2


# -- the decoder family's decode executable (models/decoder.py) -------------

#: Trinity-Mini's widths, cut to one window layer and one full layer
WIDE = {
    "hidden_size": 2048, "num_hidden_layers": 2, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention"],
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "sliding_window": 2048, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "intermediate_size": 6144, "num_experts": 128, "num_experts_per_tok": 8,
    "moe_intermediate_size": 1024, "num_shared_experts": 1,
    "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "vocab_size": 200192, "max_position_embeddings": 131072}
SLOTS, DEPTH = 48, 4096


def test_decoder_decode_step_updates_both_cache_stacks_in_place(
        one_chip, monkeypatch):
    """`GenerationEngine._decode_fn` of a dense-window layer and an
    expert-full layer at the published widths, compiled for the v5e: the
    two grouped-query kernel calls and the grouped expert products are
    there, every byte of both cache stacks is aliased through, the
    program's temporaries are a few megabytes, and no XLA op reads or
    writes an array of a cache stack's size (PR 27's property, kept for
    two stacks)."""
    from paddle_tpu.framework.random import RNG
    from paddle_tpu.inference.serving.engine import GenerationEngine
    from paddle_tpu.models.decoder import DecoderConfig, DecoderLM
    net = DecoderLM(DecoderConfig.from_hf(WIDE), "bfloat16", abstract=True)
    eng = GenerationEngine(net, max_batch=SLOTS, max_seq_len=DEPTH,
                           prefill_buckets=(1024,), kv_dtype="bfloat16")
    # the gates ask the backend; the compile below is for the chip
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    compiled = eng._jit_decode.lower(
        [sds(p._data) for p in eng._weights],
        [sds(b._data) for b in eng._buffers], sds(RNG.key),
        tuple(sds(a) for a in eng.kv.state()), sds(eng._last),
        jax.ShapeDtypeStruct((SLOTS,), bool, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert text.count('name="paged_gqa_decode"') >= 1 or \
        "paged_gqa_decode" in text
    assert len(re.findall(r"ragged-dot-none\S* = ", text)) == 3
    ma = compiled.memory_analysis()
    by_kind = eng.kv.nbytes_by_kind()
    assert by_kind == {"full": 2 * SLOTS * 4 * DEPTH * 128 * 2,
                       "window": 2 * SLOTS * 4 * 2048 * 128 * 2}
    assert ma.alias_size_in_bytes >= sum(by_kind.values())
    assert ma.temp_size_in_bytes < 32 << 20
    # every instruction whose result has a cache stack's shape is one of
    # the two kernels (or the parameter / tuple plumbing round them)
    stacks = ("bf16[1,%d,4,%d,128]" % (SLOTS, DEPTH),
              "bf16[1,%d,4,2048,128]" % SLOTS)
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) "
                     r"([\w\-]+)\(", line)
        if m and any(s in m.group(2) for s in stacks):
            assert m.group(3) in ("custom-call", "parameter", "tuple",
                                  "get-tuple-element", "bitcast"), line


# MiMo-V2.5's widths (benchmarks/perf/configs/mimo-v2.5.json) on a full
# dense layer and three window expert layers: the two kinds of stack agree
# on nothing but the slots
MIMO = {
    "hidden_size": 4096, "num_hidden_layers": 4,
    "hybrid_layer_pattern": [0, 1, 1, 1], "moe_layer_freq": [0, 1, 1, 1],
    "num_attention_heads": 64, "num_key_value_heads": 4, "head_dim": 192,
    "v_head_dim": 128, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192, "swa_v_head_dim": 128,
    "sliding_window": 128, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "attention_value_scale": 0.707, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "layernorm_epsilon": 1e-05,
    "intermediate_size": 16384, "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "vocab_size": 2048, "max_position_embeddings": 1048576}


def test_decode_step_at_unequal_stacks_copies_neither_cache_nor_weight(
        one_chip, monkeypatch):
    """`GenerationEngine._decode_fn` at 4 full-layer and 8 ring heads, a
    192-wide key (kept by column) and a 128-wide value, a sink on the ring
    and 16 of 256 experts held, compiled for the v5e: each kind's kernel
    call bears its own name, both stacks are aliased through with no op of
    a stack's size beside the kernels, and no weight is relaid out inside
    the step (left to itself XLA copies bf16[4096, 12288] a layer to split
    the q projection into 192-wide heads: `models/decoder._attention`)."""
    from paddle_tpu.framework.random import RNG
    from paddle_tpu.inference.serving.engine import GenerationEngine
    from paddle_tpu.models.decoder import DecoderConfig, DecoderLM
    slots, depth = 192, 1024
    net = DecoderLM(DecoderConfig.from_hf(MIMO, experts_held=(0, 16)),
                    "bfloat16", abstract=True)
    # the engine holds two slots; the step is lowered at the cell's 192, so
    # that the stacks are too large for XLA to stage in fast memory (at a
    # few tens of MB it copies a ring stack there and back)
    eng = GenerationEngine(net, max_batch=2, max_seq_len=depth,
                           prefill_buckets=(512,), kv_dtype="bfloat16")
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")

    def sds(a, slots_at=None):
        shape = tuple(a.shape)
        if slots_at is not None:
            shape = shape[:slots_at] + (slots,) + shape[slots_at + 1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    state = eng.kv.state()
    cache = tuple(sds(a, 1) for a in state[:4]) + (sds(state[4], 0),)
    compiled = eng._jit_decode.lower(
        [sds(p._data) for p in eng._weights],
        [sds(b._data) for b in eng._buffers], sds(RNG.key), cache,
        sds(eng._last, 0),
        jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "paged_kv_ring_decode" in text and "paged_kv_rows_decode" in text
    # the full layer's call walks a work list: its operands open with the
    # grid's dynamic bound, then row, live, layer and the list's (slot,
    # block) entries, one a block of every slot
    blk = pk._gqa_cols_block(depth, 4, 192, 128, 2, interpret=False)
    entries = slots * depth // blk
    (operands,) = re.findall(              # the one full layer's call
        r"%paged_kv_rows_decode\S* = .* custom-call\(.*"
        r"operand_layout_constraints=\{(.*?)\}, output_to", text)
    assert re.sub(r"\{[\d,]*\}", "", operands).split(", ")[:6] == [
        "s32[]", "s32[%d]" % slots, "s32[%d]" % slots, "s32[1]",
        "s32[%d]" % entries, "s32[%d]" % entries]
    # a held share: three products a layer on the held experts' rows alone
    assert len(re.findall(r"held_grouped_dot\S* = ", text)) == 9
    assert "ragged-dot" not in text
    assert eng.kv.k_cols == ("full", "window")
    assert [a.shape for a in cache[:4]] == [
        (1, slots, 4, 192, depth), (1, slots, 4, depth, 128),
        (3, slots, 8, 192, 128), (3, slots, 8, 128, 128)]
    ma = compiled.memory_analysis()
    nbytes = slots * 4 * depth * 320 * 2 + 3 * slots * 8 * 128 * 320 * 2
    assert ma.alias_size_in_bytes >= nbytes
    # the expert layers' rows and the projections of 192 tokens: well
    # under the smallest stack (151 MB); the shapes below say the rest
    assert ma.temp_size_in_bytes < 64 << 20
    stacks = ("bf16[1,%d,4,192,%d]" % (slots, depth),
              "bf16[1,%d,4,%d,128]" % (slots, depth),
              "bf16[3,%d,8,192,128]" % slots, "bf16[3,%d,8,128,128]" % slots)
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) "
                     r"([\w\-]+)\(", line)
        if m and any(s in m.group(2) for s in stacks):
            assert m.group(3) in ("custom-call", "parameter", "tuple",
                                  "get-tuple-element", "bitcast"), line
        if m and m.group(3) == "copy":       # no weight relaid out
            assert "[4096,12288]" not in m.group(2) \
                and "[12288,4096]" not in m.group(2), line


# Kimi-VL-A3B's language model (benchmarks/perf/configs/kimi-vl-a3b.json) on
# the dense layer and two expert layers: latent attention, a cache of one
# 576-wide row a token
KIMI = {
    "hidden_size": 2048, "num_hidden_layers": 3, "num_attention_heads": 16,
    "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "rope_theta": 800000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "intermediate_size": 11264, "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "first_k_dense_replace": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "n_group": 1,
    "vocab_size": 2048, "max_position_embeddings": 131072}


def _kimi_engine(monkeypatch, depth, buckets):
    from paddle_tpu.inference.serving.engine import GenerationEngine
    from paddle_tpu.models.decoder import DecoderConfig, DecoderLM
    net = DecoderLM(DecoderConfig.from_hf(KIMI, experts_held=(0, 8)),
                    "bfloat16", abstract=True)
    # the engine holds two slots; the steps are lowered at the cell's 48
    eng = GenerationEngine(net, max_batch=2, max_seq_len=depth,
                           prefill_buckets=buckets, kv_dtype="bfloat16")
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")
    return eng


def _lowered_args(eng, one_chip, slots):
    from paddle_tpu.framework.random import RNG

    def sds(a, slots_at=None):
        shape = tuple(a.shape)
        if slots_at is not None:
            shape = shape[:slots_at] + (slots,) + shape[slots_at + 1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    state = eng.kv.state()
    cache = tuple(sds(a, 1) for a in state[:-1]) + (sds(state[-1], 0),)
    return ([sds(p._data) for p in eng._weights],
            [sds(b._data) for b in eng._buffers], sds(RNG.key), cache,
            sds(eng._last, 0))


def _no_op_of_a_stacks_size(text, stacks, but=()):
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) "
                     r"([\w\-]+)\(", line)
        if m and any(s in m.group(2) for s in stacks):
            assert m.group(3) in ("custom-call", "parameter", "tuple",
                                  "get-tuple-element", "bitcast") + but, line


def test_latent_decode_step_holds_no_copy_of_a_latent_stack(
        one_chip, monkeypatch):
    """`GenerationEngine._decode_fn` of a latent-attention model at the
    published widths and the cell's 48 slots x 16 384 rows, compiled for
    the v5e: one `paged_latent_decode` call a layer, both arrays of the
    latent stack (the latents by row, the rotary parts by column) aliased
    through with no op of a stack's size beside the kernels, temporaries
    of a few megabytes, and no weight relaid out inside the step."""
    slots, depth = 48, 16384
    eng = _kimi_engine(monkeypatch, depth, (2048,))
    assert eng.kv._fields == ("c", "kr", "lens")
    args = _lowered_args(eng, one_chip, slots)
    assert [a.shape for a in args[3][:2]] == [
        (3, slots, depth, 512), (3, slots, 64, depth)]
    compiled = eng._jit_decode.lower(
        *args, jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"paged_latent_decode\S* = ", text)) == 3
    assert len(re.findall(r"held_grouped_dot\S* = ", text)) == 6
    assert "ragged-dot" not in text
    ma = compiled.memory_analysis()
    nbytes = 3 * slots * depth * 576 * 2
    assert ma.alias_size_in_bytes >= nbytes
    assert ma.temp_size_in_bytes < 32 << 20
    _no_op_of_a_stacks_size(text, ("bf16[3,%d,%d,512]" % (slots, depth),
                                   "bf16[3,%d,64,%d]" % (slots, depth)))
    for line in text.splitlines():          # wq is read where it lies
        m = re.match(r"\s*%?([\w.\-]+) = (\S+) copy\(", line)
        if m:
            assert "[2048,3072]" not in m.group(2) \
                and "[3072,2048]" not in m.group(2), line


def test_latent_prefill_expands_and_inserts_in_place(one_chip, monkeypatch):
    """`GenerationEngine._prefill_fn` at an 8 192-row bucket: the band
    kernel runs at one query head a key head of a 192-wide key (512 query
    rows a step), the prompt's rows enter both arrays of the stack in
    place, and nothing of a stack's size is made beside them."""
    slots, depth, bucket = 48, 16384, 8192
    eng = _kimi_engine(monkeypatch, depth, (bucket,))
    args = _lowered_args(eng, one_chip, slots)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = eng._jit_prefill.lower(
        *args, jax.ShapeDtypeStruct((1, bucket), jnp.int32,
                                    sharding=one_chip), i32, i32).compile()
    text = compiled.as_text()
    assert len(re.findall(r"prefill_kv_band_flash\S* = ", text)) == 3
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 3 * slots * depth * 576 * 2
    assert ma.temp_size_in_bytes < 3 << 30
    _no_op_of_a_stacks_size(
        text, ("bf16[3,%d,%d,512]" % (slots, depth),
               "bf16[3,%d,64,%d]" % (slots, depth)),
        but=("fusion", "dynamic-update-slice"))


@pytest.mark.parametrize("bucket", [128, 256, 512, 768, 1024])
def test_a_gpt_prompt_takes_the_band_kernel_under_its_prefills_name(
        one_chip, bucket):
    """gpt3-1.3b's 16 heads of 128 at each prefill bucket of its two
    cells that the block rule takes: the band kernel at one query head a
    key head compiles with the rule's blocks (768 rows: ONE block a head),
    on bf16 operands, and — handed no name — is called after the jitted
    function round it, which is what `prefill_flash_fwd_roofline`'s
    pattern `^custom-call\\._prefill_fn\\.` reads."""
    blocks = pk._band_blocks(bucket, False, 0, 1)
    assert blocks == {768: (768, 768), 1024: (512, 512)}.get(
        bucket, (bucket, bucket))

    def _prefill_fn(q, k, v):
        return pk._band_flash(q, k, v, 0, *blocks, interpret=False,
                              named=False)

    x = jax.ShapeDtypeStruct((1, H, bucket, D), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(_prefill_fn).lower(x, x, x).compile().as_text()
    assert len(re.findall(
        r"%%_prefill_fn\S* = bf16\[%d,1,%d,%d\]\S* custom-call\("
        r"[^)]*\), custom_call_target=\"tpu_custom_call\""
        % (H, bucket, D), text)) == 1
    assert "f32[%d,%d,%d]" % (H, bucket, D) not in text

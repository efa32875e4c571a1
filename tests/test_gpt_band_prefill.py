"""GPT's serving prefill attends through the band kernel (PR 35).

A prompt on the generation server takes ONE attention kernel whatever the
family: `ops/pallas_kernels.band_flash_attention_or_none`, of which GPT is
the one query head a key head, window 0 case. The trainer (no cache at
the call site) keeps the flash pair with its lse, dropout and backward.

  (a) the block rule `_band_blocks` is a rule of (T, G, window): for what
      the three decoder-family cells dispatch it returns the parent's
      blocks, pinned here as literals; T a multiple of 128 that is no
      multiple of 512 gets blocks that tile it; under 128 rows: None;
  (b) the kernel at G = 1, window 0, d_k = d_v against the masked float32
      einsum at the interpret-mode sizes, padding rows included;
  (c) `_GPTServing.prefill` on a small bf16 GPT returns bf16 K and V,
      logits near the einsum path's, and counts `band_flash` where a
      forward of the same model without a cache (the trainer's call
      site) counts `flash`;
  (d) a short served run's greedy tokens equal the einsum path's.

CPU, interpret mode: nothing here is a device number.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.inference.serving import (ContinuousBatcher,
                                          GenerationEngine, Request)
from paddle_tpu.models import gpt_tiny
from paddle_tpu.ops import pallas_kernels as pk

# -- (a) the block rule -------------------------------------------------------

# (T, G, window) -> (query rows, key rows), as the parent commit returns
# them for every prefill bucket of the three decoder-family cells
_LONGMIX = [(T, 8, w) for T in (1024, 2048, 4096, 8192, 14336)
            for w in (0, 2048)]                   # trinity-mini: 32 / 4 heads
_LONGGEN = [(T, G, w) for T in (512, 1024, 2048, 3072)
            for G, w in ((16, 0), (8, 128))]      # mimo-v2.5: 64 / 4, 64 / 8
_DOCLONG = [(T, 1, 0) for T in (2048, 4096, 8192, 12288, 15360)]  # kimi: MLA
_PARENT = {8: {0: (128, 512), 2048: (128, 512), 128: (128, 128)},
           16: {0: (64, 512)}, 1: {0: (512, 512)}}


@pytest.mark.parametrize("T,G,window", _LONGMIX + _LONGGEN + _DOCLONG)
def test_the_rule_returns_the_parents_blocks_for_the_decoder_cells(
        T, G, window):
    assert pk._band_blocks(T, False, window, G) == _PARENT[G][window]


@pytest.mark.parametrize("T,want", [(128, (128, 128)), (256, (256, 256)),
                                    (640, (640, 640)), (1280, (256, 256)),
                                    (512, (512, 512)), (768, (768, 768)),
                                    (1024, (512, 512))])
def test_gpts_buckets_get_blocks_that_tile_them(T, want):
    bq, bk = pk._band_blocks(T, False, 0, 1)
    assert (bq, bk) == want
    assert T % bq == 0 and T % bk == 0 and bq % 128 == 0 and bk % 128 == 0


@pytest.mark.parametrize("T", [32, 64, 96, 200])
def test_under_a_tile_or_off_the_tiles_the_rule_takes_nothing(T):
    assert pk._band_blocks(T, False, 0, 1) is None


@pytest.mark.parametrize("G,window", [(1, 0), (2, 0), (4, 256), (8, 128),
                                      (8, 2048), (16, 0), (64, 0)])
def test_every_multiple_of_128_is_tiled_whatever_the_heads(G, window):
    for T in range(128, 4097, 128):
        bq, bk = pk._band_blocks(T, False, window, G)
        assert T % bq == 0 and T % bk == 0
        assert G * bq <= 1024 or bq == 16
        # one block a head only under two key blocks' rows
        assert (bk <= 512 and bq <= 512) or (bq == bk == T < 1024)


# -- (b) the kernel at one query head a key head ------------------------------


def _randn(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _masked_einsum(q, k, v):
    """The kernels' own float32 oracle at one query head a key head."""
    T = q.shape[2]
    ok = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    return pk._gqa_oracle(q[:, :, None], k, v, ok)[:, :, 0]


@pytest.mark.parametrize("T,true_len", [(8, 8), (16, 11), (32, 32),
                                        (64, 40)])
def test_band_kernel_at_one_query_head_a_key_head(T, true_len):
    H, D = 4, 16
    q, k, v = (_randn(i + T, 1, H, T, D) for i in range(3))
    # rows from true_len on are a bucket's padding: computed like any
    # other row, and no real row sees them
    pad = (jnp.arange(T) >= true_len)[None, None, :, None]
    k, v = jnp.where(pad, 7.0, k), jnp.where(pad, -3.0, v)
    got = pk.band_flash_attention_or_none(q, k, v, 0, named=False)
    want = _masked_einsum(q, k, v)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    real = _masked_einsum(q[:, :, :true_len], k[:, :, :true_len],
                          v[:, :, :true_len])
    assert np.abs(np.asarray(got)[:, :, :true_len]
                  - np.asarray(real)).max() < 1e-5


def test_band_kernel_on_bf16_operands_accumulates_in_float32():
    H, T, D = 2, 32, 16
    q, k, v = (_randn(i, 1, H, T, D, dtype=jnp.bfloat16) for i in range(3))
    got = pk.band_flash_attention_or_none(q, k, v, 0, named=False)
    assert got.dtype == jnp.bfloat16
    want = _masked_einsum(q, k, v)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want)).max() < 3e-2


# -- (c), (d) the model and the server ----------------------------------------

VOCAB = 64


def _tiny():
    paddle.seed(0)
    m = gpt_tiny(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                 num_heads=4, intermediate_size=64,
                 max_position_embeddings=64)
    m.eval()
    return m


@pytest.fixture(scope="module")
def bf16_gpt():
    m = _tiny()
    m.to(dtype="bfloat16")
    return m


def _prefill(model, ids, true_len):
    sv = model.serving()
    with paddle.no_grad():
        return sv.prefill(jnp.asarray(ids, jnp.int32)[None],
                          jnp.int32(true_len))


def test_prefill_keeps_k_and_v_in_bf16_and_counts_the_band_kernel(bf16_gpt):
    ids = np.random.RandomState(3).randint(0, VOCAB, (16,))
    pk.attention_path_counts(reset=True)
    logits, ks, vs, stats = _prefill(bf16_gpt, ids, 13)
    paths = pk.attention_path_counts(reset=True)
    assert paths["band_flash"] == 2 and paths["flash"] == 0 \
        and paths["xla_sdpa"] == 0
    assert stats is None and logits.shape == (1, 1, VOCAB)
    assert len(ks) == len(vs) == 2
    for a in ks + vs:
        assert a.dtype == jnp.bfloat16 and a.shape == (1, 4, 16, 8)
    # the einsum path of the same weights: the tolerance is bf16's, on
    # logits of spread ~ 0.05 (a rounding of K, V or of a probability
    # moves a logit by a few thousandths)
    set_flags({"FLAGS_use_flash_attention": False})
    try:
        want, ks_e, vs_e, _ = _prefill(bf16_gpt, ids, 13)
        paths = pk.attention_path_counts(reset=True)
        # (the einsum's count is per traced primitive, not per layer)
        assert paths["xla_sdpa"] >= 1 and paths["band_flash"] == 0
    finally:
        set_flags({"FLAGS_use_flash_attention": True})
    assert ks_e[0].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(ks[0], np.float32),
                          np.asarray(ks_e[0], np.float32))
    assert np.abs(np.asarray(logits, np.float32)
                  - np.asarray(want, np.float32)).max() < 2e-2


@pytest.mark.parametrize("training", [False, True])
def test_a_forward_without_a_cache_still_counts_flash(bf16_gpt, training):
    """The trainer's call site (`cache is None`), in eval mode and in
    train mode (dropout drawn inside the flash kernel; on the CPU that
    takes the emulator's flag)."""
    ids = paddle.to_tensor(
        np.random.RandomState(4).randint(0, VOCAB, (1, 16)).astype(np.int64))
    set_flags({"FLAGS_flash_dropout_interpret": True})
    pk.attention_path_counts(reset=True)
    bf16_gpt.train() if training else bf16_gpt.eval()
    try:
        out = bf16_gpt(ids)
    finally:
        bf16_gpt.eval()
        set_flags({"FLAGS_flash_dropout_interpret": False})
    paths = pk.attention_path_counts(reset=True)
    assert paths["flash_dropout" if training else "flash"] == 2
    assert paths["band_flash"] == 0 and paths["xla_sdpa"] == 0
    assert tuple(out.shape) == (1, 16, VOCAB)


def test_an_unaligned_prompt_and_a_decode_row_keep_their_paths(bf16_gpt):
    # 13 rows: no whole 8-row blocks, so the flash forward's small-shape
    # path as before (on the chip: chat's 32 and 64 buckets)
    ids = np.random.RandomState(5).randint(0, VOCAB, (13,))
    pk.attention_path_counts(reset=True)
    _, ks, _, _ = _prefill(bf16_gpt, ids, 13)
    paths = pk.attention_path_counts(reset=True)
    assert paths["band_flash"] == 0 and paths["flash"] == 2
    assert ks[0].dtype == jnp.bfloat16


@pytest.mark.parametrize("lens", [(8, 16, 5), (16, 11, 8)])
def test_served_greedy_tokens_equal_the_einsum_paths(lens):
    m = _tiny()
    rs = np.random.RandomState(sum(lens))
    prompts = [rs.randint(0, VOCAB, (n,)).astype(np.int64) for n in lens]

    def serve():
        eng = GenerationEngine(m, max_batch=2, max_seq_len=32,
                               prefill_buckets=(8, 16))
        b = ContinuousBatcher(eng)
        reqs = [b.submit(Request(prompt=p, max_new_tokens=6))
                for p in prompts]
        b.run_until_idle()
        return [r.tokens for r in reqs]

    pk.attention_path_counts(reset=True)
    got = serve()
    assert pk.attention_path_counts(reset=True)["band_flash"] == 4
    set_flags({"FLAGS_use_flash_attention": False})
    try:
        want = serve()
        paths = pk.attention_path_counts(reset=True)
        assert paths["band_flash"] == 0 and paths["flash"] == 0
    finally:
        set_flags({"FLAGS_use_flash_attention": True})
    assert got == want and all(len(t) == 6 for t in got)

"""The grouped product of a held share of the experts
(`ops/pallas_kernels.held_grouped_dot`) in interpret mode against
`jax.lax.ragged_dot` on the rows of the held groups, its work list, its
tile rule at the served cells' shapes, and `incubate/moe.grouped_experts`,
which picks it for a layer that holds fewer experts than its router
scores (`pt_moe_path_total{path}` says which path a layer took).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate import moe
from paddle_tpu.models import decoder as dec
from paddle_tpu.ops import pallas_kernels as pk


def _routed_sizes(T, k, E, held, seed):
    """Rows each of the first `held` experts gets when T tokens choose k
    of E at random: the sorted array's held groups, the tail after them."""
    rs = np.random.RandomState(seed)
    chosen = np.stack([rs.choice(E, k, replace=False) for _ in range(T)])
    return np.bincount(chosen.reshape(-1), minlength=E)[:held].tolist()


CASES = {
    # 48 tokens x 8 assignments, 16 of 256 experts held: ~24 rows in 384
    "decode_tail": (384, 256, 128, _routed_sizes(48, 8, 256, 16, 0),
                    (128, 128)),
    # group 1 runs over the boundary of the first two 16-row tiles
    "straddles_tiles": (64, 128, 128, [5, 20, 3], (16, 128)),
    "empty_groups": (64, 128, 128, [0, 7, 0, 0, 9, 0], (16, 128)),
    "no_held_row": (64, 128, 128, [0, 0, 0, 0], (16, 128)),
    "every_row_held": (64, 128, 128, [16, 9, 23, 16], (16, 128)),
    # every group in the one tile of all the rows, as at a decode step
    "one_tile_of_all_rows": (96, 128, 128, [3, 0, 5, 2, 7, 1], (96, 128)),
    # a prompt's rows: 2 048 in 8-row tiles, 16 of 256 experts held
    "prefill_rows": (2048, 128, 128, _routed_sizes(256, 8, 256, 16, 1),
                     (8, 128)),
    # doclong's expert width, 1408 = 11 x 128, as the depth of the down
    # product, and as the width of gate and up
    "k_11x128": (96, 1408, 128, [10, 0, 17, 4], (32, 128)),
    "n_11x128": (96, 128, 1408, [10, 0, 17, 4], (32, 1408)),
    # several column tiles
    "column_tiles": (64, 512, 384, [9, 30, 2], (16, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_held_rows_match_ragged_dot(case):
    m, K, N, sizes, tiles = CASES[case]
    rs = np.random.RandomState(m + K + N)
    x = jnp.asarray(rs.randn(m, K), jnp.bfloat16)
    w = jnp.asarray(rs.randn(len(sizes), K, N) / np.sqrt(K), jnp.bfloat16)
    s = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda x, w, s: pk.held_grouped_dot(
        x, w, s, tiles=tiles, interpret=True))(x, w, s)
    want = jax.lax.ragged_dot(x, w, s, preferred_element_type=jnp.float32)
    assert got.shape == (m, N) and got.dtype == jnp.bfloat16
    h = sum(sizes)
    # float32 sums rounded once to bfloat16: within an ulp of the value
    np.testing.assert_allclose(np.asarray(got[:h], np.float32),
                               np.asarray(want[:h]), rtol=2 ** -7,
                               atol=2 ** -7)


def test_float32_operands_give_float32_sums():
    m, K, N, sizes, _ = CASES["straddles_tiles"]
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(m, K), jnp.float32)
    w = jnp.asarray(rs.randn(len(sizes), K, N), jnp.float32)
    s = jnp.asarray(sizes, jnp.int32)
    got = pk.held_grouped_dot(x, w, s, tiles=(8, 128), interpret=True)
    want = jax.lax.ragged_dot(x, w, s, precision=jax.lax.Precision.HIGHEST)
    h = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:h]), np.asarray(want[:h]),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("sizes,tm,visits", [
    # (group, tile) in turn; group 1 in tiles 0 and 1, no visit of the
    # empty group 2 nor of tile 3, which holds no held row
    ([5, 20, 0, 3], 16, [(0, 0), (1, 0), (1, 1), (3, 1)]),
    ([0, 0, 0], 16, []),
    ([16, 16, 16, 16], 16, [(0, 0), (1, 1), (2, 2), (3, 3)]),
    ([40], 16, [(0, 0), (0, 1), (0, 2)]),
])
def test_the_work_list_visits_each_held_row_tile_of_a_group_once(
        sizes, tm, visits):
    m = 64
    off, group, tile, n = pk._held_work(jnp.asarray(sizes, jnp.int32), m,
                                        tm)
    assert list(np.asarray(off)) == list(np.cumsum([0] + sizes))
    assert group.shape == tile.shape == (m // tm + len(sizes) - 1,)
    n = int(n)
    assert list(zip(np.asarray(group)[:n].tolist(),
                    np.asarray(tile)[:n].tolist())) == visits
    assert (np.asarray(tile) < m // tm).all()


@pytest.mark.parametrize("m,K,N,tiles", [
    # longgen's decode, 192 slots x 8: gate and up, then down. Its ~100
    # held rows are one row tile, so each held expert is streamed once
    (1536, 4096, 2048, (128, 1024)),
    (1536, 2048, 4096, (128, 2048)),
    # doclong's decode, 48 slots x 6: the experts whole
    (288, 2048, 1408, (96, 1408)),
    (288, 1408, 2048, (96, 2048)),
    # prompts: a 2 048-token bucket of longgen, a 12 288-token one of doclong
    (16384, 4096, 2048, (256, 1024)),
    (73728, 1408, 2048, (256, 2048)),
])
def test_the_tile_rule_at_the_served_shapes(m, K, N, tiles):
    assert pk.held_grouped_tiles(m, K, N, 2, False) == tiles
    assert K * tiles[1] * 2 <= pk._HELD_W_BLOCK


def test_a_shape_no_tile_fits_takes_ragged_dot():
    # 148 rows: over one tile, and no multiple of 16 divides them
    assert pk.held_grouped_tiles(148, 128, 128, 2, False) is None
    assert pk.held_grouped_tiles(96, 128, 128, 2, False) == (96, 128)
    # the emulator takes 8-row tiles of whole experts
    assert pk.held_grouped_tiles(16, 64, 32, 4, True) == (8, 32)
    assert pk.held_grouped_tiles(148, 64, 32, 4, True) is None


def _layer(T=12, k=4, E=16, held=4, first=4, d=64, f=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    chosen = jax.vmap(lambda key: jax.random.choice(
        key, E, (k,), replace=False))(jax.random.split(ks[1], T))
    w = jax.random.uniform(ks[2], (T, k), jnp.float32)
    eg = 0.1 * jax.random.normal(ks[3], (held, d, f))
    eu = 0.1 * jax.random.normal(ks[4], (held, d, f))
    ed = 0.1 * jax.random.normal(ks[5], (held, f, d))
    return x, chosen.astype(jnp.int32), w, eg, eu, ed, first, E


@pytest.mark.parametrize("first,held", [(0, 4), (4, 4), (12, 4), (0, 2)])
def test_grouped_experts_gives_the_same_float32_output_on_both_paths(
        first, held):
    x, chosen, w, eg, eu, ed, _, E = _layer(first=first, held=held)
    before = pk.moe_path_totals()
    a, sa = moe.grouped_experts(x, chosen, w, eg, eu, ed, first=first)
    b, sb = moe.grouped_experts(x, chosen, w, eg, eu, ed, first=first,
                                experts=E)
    after = pk.moe_path_totals()
    assert after["ragged_dot"] - before["ragged_dot"] == 1
    assert after["held_grouped"] - before["held_grouped"] == 1
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    assert int(np.asarray(sa).sum()) > 0
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                               atol=1e-6)
    assert np.abs(np.asarray(a)).max() > 1e-2


@pytest.mark.parametrize("held,path", [(4, "held_grouped"),
                                       (16, "ragged_dot")])
def test_the_path_counter_names_the_path_a_layer_took(held, path):
    """`moe_layer` hands the router's width to `grouped_experts`: a share
    of the experts takes the kernel, all of them `ragged_dot`."""
    x, chosen, w, eg, eu, ed, first, E = _layer(held=held, first=0)
    mc = dec.MoEConfig(E, 4, 32, 0, True, 1.0, experts_held=(0, held))
    p = {"router": 0.2 * jax.random.normal(jax.random.PRNGKey(9), (64, E)),
         "expert_bias": jnp.zeros((E,), jnp.float32),
         "e_gate": eg, "e_up": eu, "e_down": ed}
    before = pk.moe_path_totals()
    jax.jit(lambda p, x: dec.moe_layer(mc, p, x)).lower(p, x)
    after = pk.moe_path_totals()
    assert {k: after[k] - before[k] for k in after} == {
        "held_grouped": int(path == "held_grouped"),
        "ragged_dot": int(path == "ragged_dot")}


def test_a_shape_the_rule_does_not_take_stays_on_ragged_dot():
    """37 tokens x 4 assignments = 148 rows, no whole number of 8-row
    tiles: the held share takes `ragged_dot`, and says so."""
    x, chosen, w, eg, eu, ed, first, E = _layer(T=37)
    before = pk.moe_path_totals()
    out, _ = moe.grouped_experts(x, chosen, w, eg, eu, ed, first=first,
                                 experts=E)
    after = pk.moe_path_totals()
    assert after["ragged_dot"] - before["ragged_dot"] == 1
    assert after["held_grouped"] == before["held_grouped"]
    assert out.shape == (37, 64)

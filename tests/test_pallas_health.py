"""The TPU self-checks (ops/pallas_kernels.pallas_selfcheck): on backend
`tpu` a kernel that fails to lower, compile or match its XLA oracle RAISES
out of the compile entry point — nothing turns the error into an XLA path.
Only a kernel's own flag switches it (and its check) off.

All tests run on CPU; the TPU backend is simulated by patching
`jax.default_backend` as seen from pallas_kernels, and the kernels run in
interpret mode (or are replaced) where the check body itself is exercised."""
from unittest import mock

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def _fresh_selfcheck_state(monkeypatch):
    monkeypatch.setattr(pk, "_SELFCHECKED", set())


@pytest.fixture
def on_tpu():
    with mock.patch.object(pk.jax, "default_backend", return_value="tpu"):
        yield


def _boom(*a, **kw):
    raise RuntimeError("Mosaic failed to compile TPU kernel")


def test_noop_off_tpu(monkeypatch):
    for name in ("_check_flash", "_check_flash_dropout", "_check_paged"):
        monkeypatch.setattr(pk, name, lambda: pytest.fail("checked on cpu"))
    pk.pallas_selfcheck(needs_prng=True, needs_paged=True)
    assert pk._SELFCHECKED == set()


@pytest.mark.parametrize("check,kwargs", [
    ("_check_flash", {}),
    ("_check_flash_dropout", {"needs_prng": True}),
    ("_check_paged", {"needs_prng": False, "needs_paged": True}),
])
def test_compile_error_propagates(on_tpu, monkeypatch, check, kwargs):
    for name in ("_check_flash", "_check_flash_dropout", "_check_paged"):
        monkeypatch.setattr(pk, name, _boom if name == check
                            else (lambda: None))
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        pk.pallas_selfcheck(**kwargs)
    # a failed check is not remembered as passed: the next entry point
    # raises again instead of proceeding on a broken kernel
    assert check.replace("_check_", "") not in pk._SELFCHECKED
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        pk.pallas_selfcheck(**kwargs)


def test_error_reaches_the_entry_points(on_tpu, monkeypatch):
    """make_train_step / GenerationEngine are where a user meets it."""
    from paddle_tpu.inference.serving import GenerationEngine
    from paddle_tpu.jit.engine import make_train_step
    from paddle_tpu.models import gpt_tiny
    monkeypatch.setattr(pk, "_check_flash", _boom)
    net = gpt_tiny()
    opt = paddle.optimizer.AdamW(parameters=net.parameters())
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        make_train_step(net, lambda o, l: o.sum(), opt)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        GenerationEngine(net, max_batch=2, max_seq_len=32,
                         prefill_buckets=(8,))


def test_passed_checks_run_once(on_tpu, monkeypatch):
    calls = []
    monkeypatch.setattr(pk, "_check_flash", lambda: calls.append("flash"))
    monkeypatch.setattr(pk, "_check_flash_dropout",
                        lambda: calls.append("dropout"))
    monkeypatch.setattr(pk, "_check_paged", lambda: calls.append("paged"))
    pk.pallas_selfcheck(needs_prng=False)
    pk.pallas_selfcheck()
    pk.pallas_selfcheck(needs_prng=False, needs_paged=True)
    pk.pallas_selfcheck(needs_paged=True)
    assert calls == ["flash", "dropout", "paged"]


def test_flag_off_skips_that_kernels_check(on_tpu, monkeypatch):
    monkeypatch.setattr(pk, "_check_flash", _boom)
    monkeypatch.setattr(pk, "_check_flash_dropout", _boom)
    monkeypatch.setattr(pk, "_check_paged", _boom)
    prior = paddle.get_flags(["FLAGS_use_flash_attention"])
    paddle.set_flags({"FLAGS_use_flash_attention": False})
    try:
        # the paged kernels have no flag: an entry point that does not
        # name them does not check them
        pk.pallas_selfcheck(needs_prng=True)
    finally:
        paddle.set_flags(prior)


class TestCheckBodies:
    """The value checks themselves, with the kernels emulated (interpret
    mode) or replaced: a correct kernel passes, a wrong one raises."""

    @staticmethod
    def _interpreted(fn, pos):
        def call(*a, **kw):
            a = list(a)
            if "interpret" in kw:
                kw["interpret"] = True
            else:
                a[pos] = True
            return fn(*a, **kw)
        return call

    def test_flash_check_passes_and_catches_mismatch(self, monkeypatch):
        real = pk._flash
        monkeypatch.setattr(pk, "_flash", self._interpreted(real, 5))
        pk._check_flash()
        monkeypatch.setattr(
            pk, "_flash",
            lambda q, k, v, *a: pk._xla_attention(q, k, v, True) * 1.05)
        with pytest.raises(pk.PallasSelfCheckError, match="XLA oracle"):
            pk._check_flash()

    def test_dropout_check_catches_wrong_keep_rate(self, monkeypatch):
        # the emulator cannot run the on-chip PRNG, so stand in a kernel
        # with the right statistics, then one that drops nothing
        def fake(q, k, v, seed, causal, interpret, p):
            keep = pk.jax.random.bernoulli(
                pk.jax.random.PRNGKey(0), 1.0 - p,
                (q.shape[0], q.shape[1], q.shape[2], k.shape[2]))
            w = pk.jax.nn.softmax(pk.jnp.einsum(
                "bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5, axis=-1)
            w = pk.jnp.where(keep, w / (1.0 - p), 0.0)
            return pk.jnp.einsum("bhqk,bhkd->bhqd", w, v)

        monkeypatch.setattr(pk, "_flash", fake)
        pk._check_flash_dropout()
        monkeypatch.setattr(
            pk, "_flash",
            lambda q, k, v, *a: 2.0 * pk._xla_attention(q, k, v, False))
        with pytest.raises(pk.PallasSelfCheckError, match="dropout"):
            pk._check_flash_dropout()

    def test_paged_check_passes_and_catches_mismatch(self, monkeypatch):
        real = pk._paged_decode
        monkeypatch.setattr(pk, "_paged_decode",
                            self._interpreted(real, None))
        pk._check_paged()

        def wrong(*a, **kw):
            out, ko, vo, ks, vs = real(*a, **{**kw, "interpret": True})
            return out + 0.1, ko, vo, ks, vs
        monkeypatch.setattr(pk, "_paged_decode", wrong)
        with pytest.raises(pk.PallasSelfCheckError, match="einsum oracle"):
            pk._check_paged()


def test_dispatch_error_is_not_an_xla_path(on_tpu, monkeypatch):
    """A kernel that raises while it is traced on backend tpu raises out
    of the dispatch gate; `None` (= take the XLA path) is for flags and
    ineligible shapes only, and the path counter is not bumped for it."""
    rs = np.random.RandomState(0)
    q = paddle.to_tensor(rs.randn(1, 2, 128, 64).astype(np.float32))
    monkeypatch.setattr(pk, "_flash_op", _boom)
    monkeypatch.setattr(pk, "flash_block_sizes", lambda *a: (128, 128))
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        pk.flash_attention_or_none(q, q, q, None, True)
    mask = paddle.to_tensor(np.zeros((128, 128), np.float32))
    assert pk.flash_attention_or_none(q, q, q, mask, True) is None

"""chip_smoke.py's phase bodies at gpt_tiny size on the CPU, Pallas in
interpret mode — the same functions, checks and path-counter assertions
the chip run makes at GPT-2-small widths, so a broken phase is found here
before chip time is spent on it. `python chip_smoke.py` itself must refuse
to run without a TPU."""
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import gpt_tiny

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret_kernels():
    """Route the CPU run through the Pallas kernels (interpret mode) the
    way the chip routes through Mosaic."""
    names = ["FLAGS_flash_dropout_interpret", "FLAGS_paged_flash_interpret"]
    prior = paddle.get_flags(names)
    paddle.set_flags({n: True for n in names})
    yield
    paddle.set_flags(prior)


def test_trainer_phase_tiny(interpret_kernels):
    rep = chip_smoke.trainer_phase(
        lambda: gpt_tiny(max_position_embeddings=64), batch=2, seq_len=32,
        steps=5)
    assert len(rep["losses"]) == 6 and rep["compiles"] == 1
    assert rep["attn_paths"]["flash_dropout"] > 0
    # the Pallas fused AdamW is a TPU path; the CPU traces the jnp rule
    assert rep["update_paths"]["xla_adamw"] > 0


def test_server_phase_tiny(interpret_kernels):
    rep = chip_smoke.server_phase(gpt_tiny, max_batch=4, max_seq_len=64,
                                  buckets=(8, 16, 32), kv_dtype="float32")
    assert rep["decode_compiles"] == 1 and rep["prefix_hits"] >= 1
    assert rep["attn_paths"]["paged_flash"] > 0
    assert rep["oracle_max_gap_sigma"] == 0.0   # f32 on the CPU: exact


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert '"ok"' not in r.stdout

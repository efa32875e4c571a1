"""Every loop over a compiled step heartbeats (docs/RESILIENCE.md, "Ticks
come for free"): `StepTelemetry` ticks `resilience.health` at each engine
dispatch, with telemetry on and with `PADDLE_TPU_TELEMETRY=0`, so the
launcher's hang detector sees a hand-written train loop, an eval loop, the
static executor, a `to_static` layer and the server's decode loop make
progress whether or not anything else in the loop ticks."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, static
from paddle_tpu.inference.serving import InferenceServer
from paddle_tpu.jit.engine import make_eval_step, make_train_step
from paddle_tpu.models import gpt_tiny
from paddle_tpu.observability import tracing
from paddle_tpu.resilience import health


@pytest.fixture
def heartbeat(tmp_path, monkeypatch):
    """A writer that writes at every tick, so ticks can be counted."""
    monkeypatch.setenv(health.ENV_INTERVAL, "0")
    hb = health.configure(str(tmp_path), rank=0)
    yield hb
    health.reset()


@pytest.fixture(params=[True, False], ids=["telemetry_on", "telemetry_off"])
def telemetry(request):
    was = tracing.enabled()
    tracing.enable(request.param)
    yield request.param
    tracing.enable(was)


def _xy(n=8):
    rs = np.random.RandomState(0)
    return (paddle.to_tensor(rs.rand(n, 16).astype(np.float32)),
            paddle.to_tensor(rs.rand(n, 16).astype(np.float32)))


def _train_loop():
    net = nn.Linear(16, 16)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=net.parameters())
    step = make_train_step(net, nn.MSELoss(), opt)
    x, y = _xy()
    return lambda: step([x], [y])


def _eval_loop():
    net = nn.Linear(16, 16)
    net.eval()
    step = make_eval_step(net, nn.MSELoss())
    x, y = _xy()
    return lambda: step([x], [y])


def _to_static_loop():
    layer = paddle.jit.to_static(nn.Linear(16, 16))
    x, _ = _xy()
    return lambda: layer(x)


def _executor_loop():
    paddle.enable_static()
    static.reset_default_programs()
    x = static.data("x", [8, 16], "float32")
    y = nn.Linear(16, 4)(x)
    exe = static.Executor()
    a = np.random.RandomState(0).rand(8, 16).astype(np.float32)
    return lambda: exe.run(feed={"x": a}, fetch_list=[y])


@pytest.mark.parametrize("build", [_train_loop, _eval_loop, _to_static_loop,
                                   _executor_loop],
                         ids=["jit_train", "jit_eval", "to_static",
                              "static_executor"])
def test_compiled_step_loop_heartbeats(build, telemetry, heartbeat):
    paddle.seed(0)
    try:
        step = build()
        step()                                  # compile
        before = heartbeat.ticks_written
        assert before >= 1
        for _ in range(3):
            step()
        # a loop with no tick of its own: the dispatch is the heartbeat
        assert heartbeat.ticks_written >= before + 3
        assert health.read_heartbeat(heartbeat.path)["pid"]
    finally:
        paddle.disable_static()
        static.reset_default_programs()


def test_serving_loop_heartbeats(telemetry, heartbeat):
    """The decode loop ticks once a dispatch (prefill or decode) and has
    no tick of its own beside it."""
    paddle.seed(0)
    m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 intermediate_size=64, max_position_embeddings=64)
    m.eval()
    srv = InferenceServer(m, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,)).start()
    try:
        before = heartbeat.ticks_written
        out = srv.submit(np.arange(1, 6), max_new_tokens=6).result(120)
        assert len(out) == 6
        # one prefill + five decode programs, one tick each
        assert heartbeat.ticks_written - before == 6
    finally:
        srv.stop()

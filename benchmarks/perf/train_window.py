"""The trainer's window (traffic kind `train_window`).

Set-up builds ONE object — the compiled step `jit.engine.make_train_step`
returns, with its state — loads the weights made from the seed, drives it
through its first three steps on rows that all differ (their losses, the
first gradient's norms from AdamW's first moment and the parameters'
change are the evidence `correct` is decided on), and hands that same
object and feed to the window.

The window: opens at a measured instant with the device drained; steps are
dispatched whole, at most `in_flight` ahead of the device, until the host
clock has passed `--seconds`; the last step is waited for; the rate is the
tokens of the steps completed between the two instants over the measured
elapsed time. Nothing is counted that had not finished, and the nominal
`--seconds` divides nothing.
"""
from __future__ import annotations

import collections
import gc

import numpy as np

FIRST_STEPS = 3


def run_units(dispatch, wait, clock, seconds, in_flight=2):
    """Dispatch whole units until `seconds` have passed on `clock`, never
    more than `in_flight` ahead of completion; wait for all of them.
    Returns (units completed, measured elapsed)."""
    pending = collections.deque()
    n = 0
    t_open = clock()
    while clock() - t_open < seconds:
        pending.append(dispatch())
        n += 1
        if len(pending) > in_flight:
            wait(pending.popleft())
    while pending:
        wait(pending.popleft())
    return n, clock() - t_open


def _norms(ref, named, minus=None):
    """{leaf: L2 norm} of {leaf: device array} (minus another such dict),
    the leaves split as the reference `ref` splits them; one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        a = ref.split_leaves(a)
        b = ref.split_leaves(b) if b is not None else None
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)
            - (0.0 if b is None else b[k].astype(jnp.float32)))))
            for k, x in a.items()}

    return {k: float(v) for k, v in f(named, minus).items()}


def first_batches(ctx):
    tr = ctx.traffic
    B, T = int(tr["batch"]), int(tr["seq_len"])
    rows = ctx.family.reference.tokens(ctx.seed, int(tr["rows"]), T + 1,
                                       int(ctx.cfg["vocab_size"]))
    return rows, [(rows[i * B:(i + 1) * B, :-1], rows[i * B:(i + 1) * B, 1:])
                  for i in range(FIRST_STEPS)]


def build(ctx):
    """(step, optimizer, network, initial weights by program name)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.engine import make_train_step
    from paddle_tpu.models import GPTPretrainingCriterion

    tr = ctx.traffic
    weights = ctx.family.reference.make_weights(ctx.cfg, ctx.seed,
                                                tr["amp_dtype"])
    net = ctx.family.build_model(ctx.cfg, train=True)
    if tr.get("hybrid"):
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(tr["hybrid"])
        fleet.init(is_collective=True, strategy=strategy)
        fleet.distributed_model(net)
    crit = GPTPretrainingCriterion()
    hp = tr["optimizer"]
    opt = paddle.optimizer.AdamW(
        parameters=net.parameters(), learning_rate=hp["lr"],
        beta1=hp["beta1"], beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"])
    net, opt = paddle.amp.decorate(net, opt, level=tr["amp_level"],
                                   dtype=tr["amp_dtype"])
    start = ctx.family.load_weights(net, weights, keep=True)
    step = make_train_step(net, lambda o, l: crit(o, l), opt)
    return step, opt, net, start


def run(ctx):
    import jax
    from paddle_tpu.io import DataLoader, Dataset

    tr = ctx.traffic
    B, T = int(tr["batch"]), int(tr["seq_len"])
    step, opt, net, start = build(ctx)
    ctx.mark("step built")
    step = ctx.wrap_step(step)          # tests plant faults here
    rows, _ = first_batches(ctx)

    class Tokens(Dataset):
        def __len__(self):
            return 1 << 30

        def __getitem__(self, i):
            return rows[i % len(rows)]

    loader = DataLoader(Tokens(), batch_size=B, shuffle=False,
                        num_workers=0,
                        prefetch_to_device=int(tr["prefetch_to_device"]))
    it = iter(loader)

    def dispatch():
        ids = next(it)
        loss, _ = step([ids[:, :-1]], [ids[:, 1:]])
        return loss._data

    ref = ctx.family.reference
    named = {ctx.family.program_leaf(n): p
             for n, p in net.named_parameters()}
    evidence = {"losses": []}
    try:
        for k in range(FIRST_STEPS):
            evidence["losses"].append(float(np.asarray(dispatch(),
                                                       np.float32)))
            if k == 0:
                m1 = {n: opt._get_accumulators(p)["moment1"]
                      for n, p in named.items()}
                evidence["grad_norms"] = {
                    n: v / (1.0 - float(tr["optimizer"]["beta1"]))
                    for n, v in _norms(ref, m1).items()}
        evidence["change_norms"] = _norms(
            ref, {n: p._data for n, p in named.items()}, start)
        del start
        ctx.mark("first steps done")
        for _ in range(int(tr["warm_steps"])):
            last = dispatch()
        jax.block_until_ready(last)
        ctx.open_window()

        traced = {"on": False, "done": not ctx.trace}
        t_open = ctx.clock()

        def dispatch_traced():
            now = ctx.clock() - t_open
            if not traced["done"]:
                if not traced["on"] and now >= float(tr["trace_after_s"]):
                    ctx.trace_start()
                    traced["on"] = True
                elif traced["on"] and now >= float(tr["trace_after_s"]) \
                        + float(tr["trace_seconds"]):
                    ctx.trace_stop()
                    traced["on"], traced["done"] = False, True
            return dispatch()

        n, elapsed = run_units(dispatch_traced, jax.block_until_ready,
                               ctx.clock, ctx.seconds,
                               int(tr["in_flight"]))
        if traced["on"]:
            ctx.trace_stop()
        ctx.close_window()
    finally:
        it.close()
    rate = n * B * T / elapsed / ctx.chips
    flops = ctx.family.work.train_flops_per_token(ctx.cfg, T)
    ctx.harness.update({
        "steps": n, "elapsed_s": elapsed, "step_ms": elapsed / n * 1e3,
        "mfu": 100.0 * rate * flops / ctx.peaks["flops"]})
    ctx.counts.update({"chips": ctx.chips})
    del step, opt, net, loader, it
    gc.collect()
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_tokens_per_s_per_chip": rate},
            "evidence": evidence}


def compare(ctx, evidence):
    """Run the reference over the first steps, in float32 throughout
    (parameters, moments and update), and compare."""
    tr = ctx.traffic
    ref = ctx.family.reference
    w = ref.make_weights(ctx.cfg, ctx.seed, tr["amp_dtype"])
    _, batches = first_batches(ctx)
    want = ref.train_steps(ctx.cfg, w, batches, tr["optimizer"],
                           int(tr["reference_rows_per_block"]))
    return ref.compare_training(evidence, want)

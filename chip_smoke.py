"""Chip smoke: the two main paths, once each, on the accelerator.

    python chip_smoke.py

Runs in ONE process (a chip belongs to one process at a time) and refuses
to run without a TPU. It drives, through the entry points a user calls and
at GPT-2-small's published widths (12 layers, d=768, 12 heads):

  trainer  gpt2_small + AdamW + amp.decorate(O2, bf16) + make_train_step,
           fed by io.DataLoader(prefetch_to_device=2), B=16 T=512
  server   inference.serving.InferenceServer over gpt2_small().eval(),
           max_batch=8, max_seq_len=512, prefill buckets (32, 128, 256),
           eight prompts of mixed length, two of them sharing a prefix

and, when the host has four chips or more, two trainer steps under
fleet.init(dp_degree=4) + fleet.distributed_model. Weights are random, made
from a seed. Every phase checks its own output (finite falling loss that
starts near ln(vocab); generated tokens that a dense XLA forward agrees
with) and which kernels were traced. Any failure ends the run non-zero with
its traceback. This is not a benchmark: it prints set-up (compile) seconds,
persistent-cache hits/misses and peak HBM, never a throughput.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The phase bodies are plain functions of a model constructor and shapes, so
tests/test_chip_smoke.py runs them at gpt_tiny size on the CPU (Pallas in
interpret mode) before chip time is spent on them.
"""
from __future__ import annotations

import collections
import glob
import json
import math
import os
import tempfile

import numpy as np


def require_tpu():
    """The device JAX reports, or SystemExit when it is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "chip_smoke: needs a TPU, but jax.devices()[0].platform is %r "
            "— not run" % dev.platform)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _device_label():
    import jax
    return "%s x%d" % (jax.devices()[0].device_kind, len(jax.devices()))


def _peak_hbm():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()}


class SmokeError(Exception):
    """A phase ran but its output is wrong."""


def _require(ok, what, evidence):
    # not `assert`: python -O would strip every check and pass the smoke
    if not ok:
        raise SmokeError("%s: %s" % (what, evidence))


_builds = collections.Counter()     # "jit(fn)" -> executables built
_listening = False


def _on_duration(event, _secs, fun_name=None, **_kw):
    if event.endswith("backend_compile_duration"):
        _builds[fun_name] += 1


def _executables_built():
    """Executables jax has built so far, by jitted function: one count per
    XLA compile or persistent-cache load. A python-level trace counter
    cannot see a second lowering of the same trace (jit re-lowers when an
    argument's committed-to-device bit changes); this event does."""
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return dict(_builds)


def _repeated_batches(vocab, batch, seq_len):
    """A DataLoader that yields the SAME seeded batch every step (sample i
    is drawn from seed i mod batch), so the loss must fall."""
    from paddle_tpu.io import DataLoader, Dataset

    class Tokens(Dataset):
        def __len__(self):
            return 1 << 20

        def __getitem__(self, i):
            rs = np.random.RandomState(i % batch)
            return rs.randint(0, vocab, (seq_len + 1,)).astype(np.int64)

    return DataLoader(Tokens(), batch_size=batch, shuffle=False,
                      num_workers=0, prefetch_to_device=2)


def trainer_phase(model_ctor, batch, seq_len, steps=5, hybrid=None):
    """Compile one train step and run `steps` more on a repeated batch.

    hybrid: fleet hybrid_configs (e.g. {"dp_degree": 4}) — the step then
    compiles GSPMD-sharded over the mesh fleet builds from jax.devices().
    Returns a report dict; raises SmokeError when a check fails."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit import compile_cache
    from paddle_tpu.jit.engine import make_train_step
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.observability import tracing
    from paddle_tpu.ops import pallas_kernels as pk

    paddle.seed(0)
    net = model_ctor()
    if hybrid:
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(hybrid)
        fleet.init(is_collective=True, strategy=strategy)
        fleet.distributed_model(net)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    net, opt = paddle.amp.decorate(net, opt, level="O2", dtype="bfloat16")
    step = make_train_step(net, lambda o, l: crit(o, l), opt)

    vocab = net.gpt.embeddings.word_embeddings.weight.shape[0]
    comp = tracing.COMPILE_SECONDS.labels("jit_train")
    comp0, cc0 = comp.value, compile_cache.totals()
    attn0, upd0 = pk.attention_path_totals(), pk.update_path_totals()
    built0 = _executables_built()

    losses = []
    it = iter(_repeated_batches(vocab, batch, seq_len))
    try:
        for _ in range(1 + steps):
            ids = next(it)
            x, y = ids[:, :-1], ids[:, 1:]
            loss, _ = step([x], [y])
            losses.append(float(loss.numpy()))
    finally:
        it.close()

    cc1 = compile_cache.totals()
    report = {
        "losses": [round(v, 4) for v in losses],
        "compiles": _delta(_executables_built(), built0).get(
            "jit(step_fn)", 0),
        "compile_s": round(comp.value - comp0, 2),
        "cache": {"hits": cc1[0] - cc0[0], "misses": cc1[1] - cc0[1]},
        "attn_paths": _delta(pk.attention_path_totals(), attn0),
        "update_paths": _delta(pk.update_path_totals(), upd0),
        "peak_hbm_bytes": _peak_hbm(),
    }
    if hybrid:
        mesh = net._pt_mesh
        p0 = net.parameters()[0]._data
        report["mesh"] = dict(mesh.shape)
        report["param_devices"] = sorted(
            s.device.id for s in p0.addressable_shards)
        report["param_shard_shape"] = [list(p0.shape), list(
            p0.addressable_shards[0].data.shape)]
        report["batch_devices"] = sorted(
            s.device.id for s in x._data.addressable_shards)
    print("trainer [%s]: %s" % (_device_label(), json.dumps(report)),
          flush=True)

    ap, up = report["attn_paths"], report["update_paths"]
    _require(all(math.isfinite(v) for v in losses), "loss not finite",
             losses)
    _require(abs(losses[0] - math.log(vocab)) < 1.0,
             "first loss not near ln(vocab)=%.3f" % math.log(vocab), losses)
    _require(losses[-1] < losses[0], "loss did not fall", losses)
    _require(report["compiles"] == 1, "train step not compiled exactly once",
             report)
    _require(ap["flash"] + ap["flash_dropout"] > 0
             and ap["xla_sdpa"] == 0 and ap["xla_chunked"] == 0,
             "attention not on the flash kernel", ap)
    if hybrid:
        n = int(np.prod(list(report["mesh"].values())))
        _require(len(report["param_devices"]) == n
                 and len(report["batch_devices"]) == n,
                 "state or batch not on all %d devices" % n, report)
    elif jax.default_backend() == "tpu":
        # off-mesh on TPU the Pallas fused AdamW is the default update
        # (FLAGS_use_fused_optimizer); under a mesh XLA owns the update
        _require(up["pallas_fused_adamw"] > 0,
                 "fused AdamW kernel not traced", up)
    return report


def _prompts(rs, vocab, buckets, max_seq_len):
    """Eight (prompt, max_new_tokens): at least one per prefill bucket, and
    two that share their first buckets[0] tokens (the second must hit the
    prefix cache and take the suffix-prefill executable)."""
    b0, b1, b2 = buckets

    def toks(n):
        return rs.randint(1, vocab, (n,)).astype(np.int64)

    head = toks(b0)
    out = [(toks(b0 - 3), 8),                           # bucket 0
           (toks(b1 - 5), 6),                           # bucket 1
           (toks(b2 - 7), 5),                           # bucket 2
           (np.concatenate([head, toks(5)]), 7),        # stores `head`
           (np.concatenate([head, toks(3)]), 8),        # prefix hit
           (toks(2), 4),
           (toks(b0), 3),
           (toks(b2), 6)]
    _require(all(len(p) + n <= max_seq_len for p, n in out),
             "prompts do not fit max_seq_len", max_seq_len)
    return out


def _dense_gaps(model, seqs, n_prompt):
    """Teacher-forced oracle: one dense XLA forward (flash off) over each
    prompt+generated sequence; for every generated token, how far its
    logit sits below that position's maximum, in units of the row's
    standard deviation (0 = the oracle picks the same token)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.engine import make_eval_step

    width = -(-max(len(s) for s in seqs) // 8) * 8
    ids = np.zeros((len(seqs), width), np.int64)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s          # right padding: exact under causal
    prior = paddle.get_flags(["FLAGS_use_flash_attention"])
    paddle.set_flags({"FLAGS_use_flash_attention": False})
    try:
        _, outs = make_eval_step(model)([paddle.to_tensor(ids)])
    finally:
        paddle.set_flags(prior)
    logits = np.asarray(outs[0].numpy(), np.float32)
    gaps = []
    for i, s in enumerate(seqs):
        for pos in range(n_prompt[i] - 1, len(s) - 1):
            row = logits[i, pos]
            gaps.append(float((row.max() - row[s[pos + 1]]) / row.std()))
    return gaps


def server_phase(model_ctor, max_batch, max_seq_len, buckets,
                 kv_dtype="bfloat16"):
    """Start an InferenceServer, answer eight requests, stop it, and check
    the answers against a dense forward of the same weights."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import InferenceServer
    from paddle_tpu.jit import compile_cache
    from paddle_tpu.observability import flight, tracing
    from paddle_tpu.ops import pallas_kernels as pk

    paddle.seed(0)
    model = model_ctor()
    model.eval()
    vocab = model.gpt.embeddings.word_embeddings.weight.shape[0]
    work = _prompts(np.random.RandomState(0), vocab, buckets, max_seq_len)

    engines = ("serve_prefill", "serve_suffix", "serve_decode")
    comp0 = {e: tracing.COMPILE_SECONDS.labels(e).value for e in engines}
    cc0, attn0 = compile_cache.totals(), pk.attention_path_totals()
    built0 = _executables_built()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_flight_") as fdir:
        flight.configure(fdir)       # a dead serving loop leaves a bundle
        try:
            srv = InferenceServer(model, max_batch=max_batch,
                                  max_seq_len=max_seq_len,
                                  prefill_buckets=buckets,
                                  kv_dtype=kv_dtype)
            srv.start()
            try:
                handles = [srv.submit(p, max_new_tokens=n)
                           for p, n in work]
                answers = [h.result(timeout=900) for h in handles]
            finally:
                srv.stop()
            bundles = glob.glob(os.path.join(fdir, "crash", "*"))
        finally:
            flight.reset()
    eng = srv.engines[0]
    cc1 = compile_cache.totals()
    built = _delta(_executables_built(), built0)
    report = {
        "kv_dtype": kv_dtype,
        "tokens": [len(a) for a in answers],
        "prefill_compiles": built.get("jit(_prefill_fn)", 0),
        "suffix_compiles": built.get("jit(_suffix_fn)", 0),
        "decode_compiles": built.get("jit(_decode_fn)", 0),
        "traces": [eng.prefill_compiles, eng.suffix_prefill_compiles,
                   eng.decode_compiles],
        "prefix_hits": eng.prefix_cache.hits,
        "compile_s": round(sum(
            tracing.COMPILE_SECONDS.labels(e).value - comp0[e]
            for e in engines), 2),
        "cache": {"hits": cc1[0] - cc0[0], "misses": cc1[1] - cc0[1]},
        "attn_paths": _delta(pk.attention_path_totals(), attn0),
        "crash_bundles": len(bundles),
        "peak_hbm_bytes": _peak_hbm(),
    }
    # the oracle: a cold bucket-0 request, the prefix-hit request (suffix
    # executable) and the longest-generating one, through a dense forward
    picked = (0, 4, 1)
    seqs = [np.concatenate([work[i][0], np.asarray(answers[i], np.int64)])
            for i in picked]
    gaps = _dense_gaps(model, seqs, [len(work[i][0]) for i in picked])
    report["oracle_max_gap_sigma"] = round(max(gaps), 4)
    report["oracle_exact"] = "%d/%d" % (sum(g == 0.0 for g in gaps),
                                        len(gaps))
    print("server [%s]: %s" % (_device_label(), json.dumps(report)),
          flush=True)

    ap = report["attn_paths"]
    _require(report["tokens"] == [n for _, n in work]
             and all(0 <= t < vocab for a in answers for t in a),
             "a request was not answered in full", report)
    _require(report["decode_compiles"] == 1
             and report["prefill_compiles"] <= len(buckets),
             "decode must compile once, prefill once per bucket", report)
    _require(report["prefix_hits"] >= 1 and report["suffix_compiles"] >= 1,
             "the shared prefix was not reused", report)
    # a prompt takes the band kernel where its shape is eligible (a value
    # row of whole lane tiles on the chip), else the flash forward
    _require(ap["paged_flash"] > 0 and ap["xla_paged"] == 0
             and ap["flash"] + ap["band_flash"] > 0 and ap["xla_sdpa"] == 0,
             "attention not on the Pallas kernels", ap)
    _require(report["crash_bundles"] == 0, "the serving loop crashed",
             bundles)
    # a kernel that attended the wrong keys lands sigmas below the dense
    # maximum (the top of ~50k logits is ~4 sigma out); reduced-precision
    # near-ties stay far inside a quarter sigma
    _require(max(gaps) <= 0.25,
             "generated tokens disagree with the dense forward (sigmas "
             "below its maximum)", gaps)
    return report


def main():
    device = require_tpu()

    import jax
    import jaxlib
    from importlib import metadata

    # importing the package places the compile cache
    from paddle_tpu.jit import compile_cache
    from paddle_tpu.models import gpt2_small

    print("device: %s" % json.dumps(device))
    print("versions: jax %s jaxlib %s libtpu %s"
          % (jax.__version__, jaxlib.__version__,
             metadata.version("libtpu")))
    print("compile cache: %s" % compile_cache.cache_dir(), flush=True)

    trainer_phase(gpt2_small, batch=16, seq_len=512, steps=5)
    server_phase(gpt2_small, max_batch=8, max_seq_len=512,
                 buckets=(32, 128, 256))
    if device["count"] >= 4:
        trainer_phase(gpt2_small, batch=16, seq_len=512, steps=2,
                      hybrid={"dp_degree": 4})
    hits, misses = compile_cache.totals()
    print("compile cache totals [%s]: hits=%d misses=%d"
          % (_device_label(), hits, misses))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

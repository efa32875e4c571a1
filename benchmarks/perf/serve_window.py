"""The server's windows (traffic kinds `serve_open_loop`, `serve_closed_loop`).

One general generator reads a traffic file: length distributions, a rate or
a number of clients. Every seed gets the SAME multiset of prompt lengths,
output lengths and (open loop) inter-arrival gaps — the quantiles of the
stated distributions — in another order, so the seed never changes the work.

Open loop (independent users): request i is due at t_open + offset_i, the
generator thread submits it then through `InferenceServer.submit`, and
every latency is timed from the DUE instant, so a stall shows in the wait
of the requests behind it. The window is the n = round(rate x seconds)
arrivals, n / rate long; its tails are over all n, those that finish after
the last arrival included; one that fails or never comes counts as missing.

Closed loop (callers that wait): `clients` requests are always outstanding.
The window opens at the completion instant of the `lead_in_completions`-th
request — by then every slot is full — and closes at the first completion
instant at or after `--seconds` later; the rate is the prompt and generated
tokens of the requests completed between those two instants over the
measured time between them.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

def _quantiles(spec, n):
    """n values: the (i + .5) / n quantiles of the stated distribution."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(v) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "exponential":
        x = -np.log1p(-u)
        x = x / x.mean() * spec["mean"]
        return x
    else:
        raise ValueError("unknown distribution %r" % spec["dist"])
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_requests(traffic, seed, n, vocab):
    """n requests [(prompt ids, max_new_tokens)] and, for an open loop,
    their due offsets [n] (seconds after the window opens)."""
    rs = np.random.RandomState([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 11])
    plen = rs.permutation(_quantiles(traffic["prompt_len"], n))
    olen = rs.permutation(_quantiles(traffic["output_len"], n))
    reqs = [(rs.randint(1, vocab, (int(p),)).astype(np.int64), int(o))
            for p, o in zip(plen, olen)]
    offsets = None
    if traffic.get("rate_per_s"):
        gaps = rs.permutation(_quantiles(
            {"dist": "exponential", "mean": 1.0 / traffic["rate_per_s"]}, n))
        offsets = np.cumsum(gaps)
    return reqs, offsets


class Spans:
    """Harness spans round the engine's two host calls."""

    def __init__(self, engine, clock):
        self.prefill, self.decode = [], []     # (t0, t1[, prompt len])
        self.submitted = []                    # every handle, submit order
        self._wrap(engine, clock)

    def _wrap(self, engine, clock):
        prefill, decode = engine.prefill, engine.decode

        def timed_prefill(slot, prompt):
            t0 = clock()
            out = prefill(slot, prompt)
            self.prefill.append((t0, clock(), len(prompt)))
            return out

        def timed_decode():
            t0 = clock()
            out = decode()
            self.decode.append((t0, clock()))
            return out

        engine.prefill, engine.decode = timed_prefill, timed_decode


def _record(handle, due, n_prompt, max_new):
    """One request's measured life, from the program's own clock fields."""
    r = handle.request
    ok = handle.done() and handle._error is None \
        and len(r.tokens) == max_new and r.ttft_s is not None
    rec = {"due": due, "submit": r.submit_ts, "ok": bool(ok),
           "n_prompt": n_prompt, "n_out": len(r.tokens)}
    if ok:
        rec["first"] = r.submit_ts + r.ttft_s
        rec["done"] = r.submit_ts + r.latency_s
    return rec


def _p(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx):
    from paddle_tpu.inference.serving import InferenceServer

    tr, cfg = ctx.traffic, ctx.cfg
    vocab = int(cfg["vocab_size"])
    model = ctx.family.build_model(cfg, train=False,
                                   dtype=tr["weights_dtype"])
    ctx.mark("model built")
    ctx.family.load_weights(model, ctx.family.reference.make_weights(
        cfg, ctx.seed, tr["weights_dtype"]))
    ctx.mark("weights loaded")
    buckets = tuple(tr["prefill_buckets"])
    srv = InferenceServer(
        model, max_batch=int(tr["max_batch"]),
        max_seq_len=int(tr["max_seq_len"]), prefill_buckets=buckets,
        kv_dtype=tr["kv_dtype"], workers=1,
        prefix_cache_bytes=int(tr["prefix_cache_bytes"]))
    engine = srv.engines[0]
    ctx.wrap_engine(engine)             # tests plant faults here
    spans = Spans(engine, ctx.clock)
    srv.start()
    ctx.mark("server started")
    try:
        # warm every executable this traffic uses: one prompt per bucket,
        # two tokens each (prefill + decode)
        rs = np.random.RandomState(1)
        top = int(tr["max_seq_len"]) - 2
        warm = [_submit(srv, spans, (rs.randint(1, vocab, (min(b, top),)), 2))
                for b in buckets]
        for h in warm:
            h.result(timeout=1100)
        ctx.mark("executables warm")
        if tr["kind"] == "serve_open_loop":
            out = _open_loop(ctx, srv, spans, vocab)
        else:
            out = _closed_loop(ctx, srv, spans, vocab)
    finally:
        srv.stop(timeout=120.0)
    del srv, engine, model, spans
    gc.collect()
    return out


def _submit(srv, spans, req):
    h = srv.submit(req[0], max_new_tokens=req[1])
    spans.submitted.append(h)
    return h


def _traced(ctx, t_open, wait_until):
    """In a traced run, trace `trace_seconds` of the window from
    `trace_after_s` on; the caller's thread only waits meanwhile."""
    tr = ctx.traffic
    if not ctx.trace:
        return None
    wait_until(t_open + float(tr["trace_after_s"]))
    t0 = ctx.clock()
    ctx.trace_start()
    wait_until(t0 + float(tr["trace_seconds"]))
    ctx.trace_stop()
    return t0, ctx.clock()


def _open_loop(ctx, srv, spans, vocab):
    tr = ctx.traffic
    rate = float(tr["rate_per_s"])
    n_lead = int(round(rate * float(tr["lead_in_s"])))
    n = max(1, int(round(rate * ctx.seconds)))
    reqs, offsets = make_requests(tr, ctx.seed, n, vocab)
    lead, lead_off = make_requests(tr, ctx.seed + 1, max(n_lead, 1), vocab)
    lead, lead_off = lead[:n_lead], lead_off[:n_lead]
    lead_len = n_lead / rate
    handles, late = [], []

    def sleep_until(t):
        while True:
            d = t - ctx.clock()
            if d <= 0:
                return
            time.sleep(min(d, 0.05))

    t_start = ctx.clock() + 0.05
    t_open = t_start + lead_len

    def generate():
        for req, off in zip(lead, lead_off):
            sleep_until(t_start + off)
            _submit(srv, spans, req)
        for req, off in zip(reqs, offsets):
            sleep_until(t_open + off)
            late.append(ctx.clock() - (t_open + off))
            handles.append(_submit(srv, spans, req))

    gen = threading.Thread(target=generate, name="perf-loadgen", daemon=True)
    gen.start()
    sleep_until(t_open)
    ctx.open_window(t_open)
    traced = _traced(ctx, t_open, sleep_until)
    gen.join(timeout=ctx.seconds + 120)
    window_s = n / rate
    # wait for every answer that is due, a minute past the close if need be
    deadline = t_open + window_s + 60.0
    for h in handles:
        h._event.wait(max(0.0, deadline - ctx.clock()))
    ctx.close_window()
    recs = [_record(h, t_open + off, len(r[0]), r[1])
            for h, off, r in zip(handles, offsets, reqs)]
    ok = [r for r in recs if r["ok"]]
    failed = n - len(ok)
    miss = (n / rate + 60.0) * 1e3      # one that never came: the full wait
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok] + [miss] * failed
    tpot = [(r["done"] - r["first"]) / max(r["n_out"] - 1, 1) * 1e3
            for r in ok] + [miss] * failed
    e2e = {"ttft_p95_ms": _p(ttft, 95), "tpot_p95_ms": _p(tpot, 95)}
    t_close = t_open + window_s
    _serve_numbers(ctx, spans, ok, t_open, t_close, traced)
    ctx.harness.update({
        "gen_late_p95_ms": _p(late, 95) * 1e3,
        "ttft_p50_ms": _p(ttft, 50), "tpot_p50_ms": _p(tpot, 50),
        "out_tokens_per_s": sum(r["n_out"] for r in ok) / window_s,
        "drain_s": max([r["done"] for r in ok] + [t_close]) - t_close,
        "requests": n})
    return {"attempted": n, "failed": failed, "end_to_end": e2e,
            "evidence": _evidence(ctx, handles, reqs, recs)}


def _closed_loop(ctx, srv, spans, vocab):
    tr = ctx.traffic
    pool, _ = make_requests(tr, ctx.seed, int(tr["pool"]), vocab)
    lead_in = int(tr["lead_in_completions"])
    out, done = {}, []                 # handle -> request index; records
    nxt = 0
    for _ in range(int(tr["clients"])):
        out[_submit(srv, spans, pool[nxt % len(pool)])] = nxt
        nxt += 1
    t_open = t_close = None
    traced, trace_t0 = None, None
    handles, reqs = [], []
    while t_close is None:
        time.sleep(0.002)
        for h in [h for h in out if h.done()]:
            i = out.pop(h)
            req = pool[i % len(pool)]
            rec = _record(h, None, len(req[0]), req[1])
            rec["done"] = rec.get("done", ctx.clock())
            done.append(rec)
            handles.append(h)
            reqs.append(req)
            if t_open is None and len(done) == lead_in:
                t_open = rec["done"]
                ctx.open_window(t_open)
            elif t_open is not None and rec["done"] >= t_open + ctx.seconds:
                t_close = rec["done"]
                break
            out[_submit(srv, spans, pool[nxt % len(pool)])] = nxt
            nxt += 1
        if ctx.trace and t_open is not None and traced is None:
            now = ctx.clock()
            if trace_t0 is None and now >= t_open + float(tr["trace_after_s"]):
                trace_t0 = now
                ctx.trace_start()
            elif trace_t0 is not None and \
                    now >= trace_t0 + float(tr["trace_seconds"]):
                ctx.trace_stop()
                traced = (trace_t0, now)
    if trace_t0 is not None and traced is None:
        ctx.trace_stop()
        traced = (trace_t0, ctx.clock())
    ctx.close_window()
    # the clients stop here; what is outstanding drains before the engine
    # is freed (the loop leaves only when idle) and is not counted
    for h in list(out):
        h._event.wait(120.0)
    inwin = [(r, h, q) for r, h, q in zip(done, handles, reqs)
             if t_open < r["done"] <= t_close]
    ok = [r for r, _, _ in inwin if r["ok"]]
    elapsed = t_close - t_open
    tokens = sum(r["n_prompt"] + r["n_out"] for r in ok)
    _serve_numbers(ctx, spans, ok, t_open, t_close, traced)
    ctx.harness.update({
        "requests": len(inwin), "elapsed_s": elapsed,
        "tpot_p50_ms": _p([(r["done"] - r["first"]) / (r["n_out"] - 1) * 1e3
                           for r in ok], 50) if ok else None})
    return {"attempted": len(inwin), "failed": len(inwin) - len(ok),
            "end_to_end": {"serve_tokens_per_s": tokens / elapsed},
            "evidence": _evidence(ctx, [h for _, h, _ in inwin],
                                  [q for _, _, q in inwin],
                                  [r for r, _, _ in inwin])}


def _serve_numbers(ctx, spans, ok, t_open, t_close, traced):
    """Per-layer numbers the harness itself can take: spans round prefill
    and decode inside the window, occupancy, model FLOPs, and the counts
    the kernels' work functions need (over the traced part, if any)."""
    tr, cfg = ctx.traffic, ctx.cfg
    slots = int(tr["max_batch"])
    pre = [s for s in spans.prefill if t_open <= s[0] < t_close]
    dec = [s for s in spans.decode if t_open <= s[0] < t_close]
    elapsed = t_close - t_open
    h = ctx.harness
    if pre:
        h["prefill_ms_p50"] = _p([(b - a) * 1e3 for a, b, _ in pre], 50)
        h["prefill_loop_share"] = 100.0 * sum(b - a for a, b, _ in pre) \
            / elapsed
    if dec:
        h["decode_step_ms_p50"] = _p([(b - a) * 1e3 for a, b in dec], 50)
    # requests in submit order meet prefills in call order (one queue, one
    # worker): the k-th prefill of the run admitted the k-th submit
    waits = [(s[0] - hd.request.submit_ts) * 1e3
             for s, hd in zip(spans.prefill, spans.submitted)
             if t_open <= s[0] < t_close]
    if waits:
        h["queue_wait_p50_ms"] = _p(waits, 50)

    def live_rows(t):
        rows = 0.0
        for r in ok:
            if r["first"] <= t < r["done"]:
                frac = (t - r["first"]) / max(r["done"] - r["first"], 1e-9)
                rows += r["n_prompt"] + 1 + frac * (r["n_out"] - 1)
        return rows

    # occupancy from the program's own counters, open to close: tokens the
    # decode steps gave live requests over the slots those steps carried
    n_dec = sum(1 for s in spans.decode if ctx.t_open <= s[0] < ctx.t_closed)
    if n_dec:
        live = ctx.registry_delta("pt_serve_tokens_total") \
            - ctx.registry_delta("pt_serve_admitted_total")
        h["occupancy"] = 100.0 * live / (n_dec * slots)
        h["decode_steps"], h["decode_tokens"] = n_dec, live
    # model FLOPs of all tokens the window's requests had processed
    flops, forward = 0.0, ctx.family.work.forward_flops
    for r in ok:
        p, o = r["n_prompt"], r["n_out"]
        flops += forward(cfg, p, p * (p + 1) / 2.0)
        flops += forward(cfg, o - 1, (o - 1) * (p + o / 2.0))
    if ok:
        h["mfu"] = 100.0 * flops / elapsed / ctx.peaks["flops"]
    if traced:
        lo, hi = traced
        tdec = [s for s in spans.decode if lo <= s[0] < hi]
        tpre = [s for s in spans.prefill if lo <= s[0] < hi]
        if tdec:
            ctx.counts["live_rows_mean"] = float(np.mean(
                [live_rows(a) for a, _ in tdec]))
        if tpre:
            from paddle_tpu.inference.serving.cache import bucket_for
            bs = [bucket_for(n, tuple(tr["prefill_buckets"]))
                  for _, _, n in tpre]
            ctx.counts["prefill_bucket_mean"] = float(np.mean(bs))
            ctx.counts["prefill_bucket_mean_sq"] = float(
                np.mean(np.square(bs)))


def _evidence(ctx, handles, reqs, recs):
    """A sample, drawn from the seed, of the requests the window finished,
    the longest in it: each as prompt + served tokens."""
    fin = [i for i, r in enumerate(recs) if r["ok"]]
    if not fin:
        return {"seqs": [], "n_prompt": []}
    k = min(int(ctx.traffic["check_requests"]), len(fin))
    rs = np.random.RandomState([int(ctx.seed) & 0x7FFFFFFF, 13])
    longest = max(fin, key=lambda i: recs[i]["n_prompt"] + recs[i]["n_out"])
    rest = [i for i in fin if i != longest]
    pick = [longest] + list(rs.permutation(rest)[:k - 1])
    seqs = [np.concatenate([np.asarray(reqs[i][0], np.int64),
                            np.asarray(handles[i].request.tokens, np.int64)])
            for i in pick]
    return {"seqs": seqs, "n_prompt": [len(reqs[i][0]) for i in pick]}


def compare(ctx, evidence):
    """The widest gap by which a served token's float32 reference logit
    lies below the reference's best (in row standard deviations)."""
    if not evidence["seqs"]:
        return {"served_gap_max": float("inf")}
    ref = ctx.family.reference
    w = ref.make_weights(ctx.cfg, ctx.seed, ctx.traffic["weights_dtype"])
    gaps = ref.served_gaps(ctx.cfg, w, evidence["seqs"],
                           evidence["n_prompt"])
    return {"served_gap_max":
            float("inf") if np.isnan(gaps).any() else max(gaps)}

"""Jaxpr step-cost pass: the static half of the profiling subsystem.

Walks a lowered train/serve step and produces a **step card** — what the
program costs before it ever runs: estimated FLOPs, HBM bytes touched,
the collective inventory with operand sizes, and a dominant-equation
ranking (with XLA's own cost analysis attached when the backend exposes
it). `tools/ptdoctor.py profile` renders the card next to the runtime
span breakdown so "where SHOULD the time go" and "where DID it go" sit
in one report.

Also home of the ROADMAP-item-5 **exposed-collective** ptlint rule
(DeepCompile, arxiv 2504.09983): a collective (psum / all_gather /
reduce_scatter / all_to_all / ppermute) with no *independent*
overlappable compute (dot_general / conv / scan) adjacent to it in the
jaxpr's dataflow order. Such a collective serializes against the
program around it — the static precondition every comm/compute overlap
optimization needs to find its targets. Findings report through the
existing findings/baseline machinery (suppressible, fingerprinted).

FLOP estimates are the standard static counts (2·prod(out)·K for
contractions, 2·prod(out)·K_window for convs, prod(out) for elementwise
arithmetic); they rank equations and size MFU expectations — they are
not a bench.
"""
from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Sequence

from .findings import Finding
from .jaxpr_pass import JAXPR_RULES, _nbytes, _walk_jaxprs

__all__ = [
    "COLLECTIVE_PRIMITIVES", "OVERLAPPABLE_PRIMITIVES",
    "exposed_collective_findings", "fused_hbm_estimate",
    "memory_analysis", "paged_decode_cost", "step_card",
    "step_card_from_jaxpr", "write_step_card",
]

#: primitives that move data across devices (jax lax.parallel lowerings;
#: psum_invariant is what psum traces to under shard_map's check_vma=True)
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "reduce_scatter", "psum_scatter",
})

#: compute heavy enough for a scheduler to hide a collective behind
OVERLAPPABLE_PRIMITIVES = frozenset({
    "dot_general", "conv_general_dilated", "scan",
})

# elementwise arithmetic counted at 1 FLOP per output element for the
# dominant-eqn ranking; movement/layout prims count 0
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "pow", "integer_pow", "neg", "abs",
    "erf", "cos", "sin",
})


def _aval(v):
    return getattr(v, "aval", None)


def _out_elems(eqn) -> int:
    n = 0
    for ov in eqn.outvars:
        a = _aval(ov)
        if a is not None and getattr(a, "shape", None) is not None:
            n += int(math.prod(a.shape or (1,)))
    return n


def _eqn_flops(eqn) -> int:
    """Static FLOP estimate for one equation (0 for pure data movement)."""
    name = eqn.primitive.name
    if name == "dot_general":
        out = _aval(eqn.outvars[0])
        lhs = _aval(eqn.invars[0])
        if out is None or lhs is None:
            return 0
        (lhs_c, _rhs_c), _batch = eqn.params.get(
            "dimension_numbers", (((), ()), ((), ())))
        k = 1
        for d in lhs_c:
            k *= int(lhs.shape[d])
        return 2 * int(math.prod(out.shape or (1,))) * k
    if name == "conv_general_dilated":
        out = _aval(eqn.outvars[0])
        rhs = _aval(eqn.invars[1])
        if out is None or rhs is None:
            return 0
        dn = eqn.params.get("dimension_numbers")
        o_feat = getattr(dn, "rhs_spec", None)
        # rhs_spec[0] is the out-feature dim of the kernel; per output
        # element the window costs prod(rhs.shape) / out_features MACs
        out_feats = int(rhs.shape[o_feat[0]]) if o_feat else 1
        per_out = int(math.prod(rhs.shape or (1,))) // max(out_feats, 1)
        return 2 * int(math.prod(out.shape or (1,))) * per_out
    if name in _ELEMENTWISE:
        return _out_elems(eqn)
    return 0


def _eqn_bytes(eqn) -> int:
    """Upper-bound HBM traffic: every operand read + every result
    written once (what the program costs UNFUSED; XLA fusion only
    improves on it)."""
    n = 0
    for v in list(eqn.invars) + list(eqn.outvars):
        a = _aval(v)
        if a is not None and getattr(a, "shape", None) is not None:
            n += _nbytes(a.shape, a.dtype)
    return n


# primitives a fusing compiler (or a hand-written megakernel) keeps in
# registers between producer and consumer — the elementwise arithmetic
# set plus the free movement/layout prims that ride along in a fusion
_FUSABLE = _ELEMENTWISE | frozenset({
    "broadcast_in_dim", "convert_element_type", "copy", "iota",
    "reshape", "select_n", "squeeze", "stop_gradient", "transpose",
})


def fused_hbm_estimate(closed_jaxpr) -> int:
    """HBM bytes of the step if every producer→consumer elementwise
    chain were fused into one pass (the megakernel target).

    Same walk as `_eqn_bytes` but: a fusable eqn's operand read is
    elided when its producer is also fusable (the value never left
    registers), and its result write is elided when every consumer is
    fusable and it is not a program output. Non-fusable eqns
    (contractions, convs, scatters, collectives) pay full freight. The
    gap to `hbm_bytes` is the **fusion headroom** `ptdoctor roofline`
    reports — bytes a block-fusion kernel can remove without changing
    any math."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    total = 0
    for jx in _walk_jaxprs(jaxpr):
        producer = {}
        consumers: Dict[int, list] = {}
        for eqn in jx.eqns:
            for v in eqn.outvars:
                producer[id(v)] = eqn
            for v in eqn.invars:
                consumers.setdefault(id(v), []).append(eqn)
        out_ids = {id(v) for v in jx.outvars}
        for eqn in jx.eqns:
            fusable = eqn.primitive.name in _FUSABLE
            for v in eqn.invars:
                a = _aval(v)
                if a is None or getattr(a, "shape", None) is None:
                    continue
                p = producer.get(id(v))
                if (fusable and p is not None
                        and p.primitive.name in _FUSABLE):
                    continue
                total += _nbytes(a.shape, a.dtype)
            for v in eqn.outvars:
                a = _aval(v)
                if a is None or getattr(a, "shape", None) is None:
                    continue
                cs = consumers.get(id(v))
                if (fusable and id(v) not in out_ids and cs
                        and all(c.primitive.name in _FUSABLE for c in cs)):
                    continue
                total += _nbytes(a.shape, a.dtype)
    return total


def paged_decode_cost(batch: int, n_heads: int, t_max: int, head_dim: int,
                      live_len: int, *, block_k: int = 128,
                      quantized: bool = False,
                      dtype_bytes: int = 4) -> dict:
    """Analytic per-decode-step HBM read traffic of the paged KV cache,
    einsum path vs fused Pallas megakernel — the static proof that the
    fused path's bytes scale with LIVE length, not cache capacity.

    One layer's call on the stacked [L, B, H, t_max, D] cache. The einsum
    path reads (and for int8, dequantizes to f32) that layer's full
    [B, H, t_max, D] K and V every step; with `windows` it reads the
    smallest prefill bucket covering max(lens)+1, still shared across
    the whole batch. The megakernel's clamped BlockSpec index map reads
    only each slot's live blocks: ceil((live+1)/block_k)·block_k
    positions per (slot, head). Scales add 4 bytes/position when
    quantized. Both paths update the stacked cache in place (the kernel
    writes back the one append block a (slot, head), the einsum path
    scatters one row); that write, and the q/new-token/output traffic,
    are the same order on both paths and omitted."""
    kv_b = (1 if quantized else dtype_bytes) * head_dim
    if quantized:
        kv_b += 4                       # f32 per-token k/v scale
    per_pos = 2 * kv_b                  # K and V
    live_blocks = -(-min(live_len + 1, t_max) // block_k)
    fused_pos = min(live_blocks * block_k, t_max)
    einsum = batch * n_heads * t_max * per_pos
    fused = batch * n_heads * fused_pos * per_pos
    return {
        "batch": batch, "n_heads": n_heads, "t_max": t_max,
        "head_dim": head_dim, "live_len": live_len, "block_k": block_k,
        "quantized": quantized,
        "einsum_bytes": einsum,
        "fused_bytes": fused,
        "savings_ratio": round(1.0 - fused / einsum, 4) if einsum else 0.0,
    }


def _collective_record(eqn) -> dict:
    a = _aval(eqn.invars[0]) if eqn.invars else None
    shape = list(a.shape) if a is not None else []
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    return {
        "primitive": eqn.primitive.name,
        "shape": shape,
        "dtype": str(a.dtype) if a is not None else "?",
        "bytes": _nbytes(tuple(shape), a.dtype) if a is not None else 0,
        "axes": str(axes),
    }


# -- exposed-collective rule ----------------------------------------------

def _independent(c_eqn, k_eqn) -> bool:
    """No direct dataflow edge between the two eqns (either direction):
    the pair COULD be scheduled concurrently."""
    c_out = {id(v) for v in c_eqn.outvars}
    k_out = {id(v) for v in k_eqn.outvars}
    if any(id(v) in c_out for v in k_eqn.invars):
        return False
    if any(id(v) in k_out for v in c_eqn.invars):
        return False
    return True


def exposed_collective_findings(closed_jaxpr, label: str, *,
                                window: int = 3,
                                min_bytes: int = 1 << 16
                                ) -> List[Finding]:
    """Collectives with nothing to hide behind.

    For each collective eqn moving >= `min_bytes` (small psums — loss
    scalars, norm terms — are latency noise, not bandwidth), look
    `window` equations to each side in the jaxpr's dataflow order for an
    overlappable compute eqn with NO direct dependence on the
    collective. Found one -> a scheduler could overlap them; found none
    -> the collective is exposed and serializes the program."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    findings: List[Finding] = []
    for jx in _walk_jaxprs(jaxpr):
        eqns = jx.eqns
        for i, eqn in enumerate(eqns):
            if eqn.primitive.name not in COLLECTIVE_PRIMITIVES:
                continue
            rec = _collective_record(eqn)
            if rec["bytes"] < min_bytes:
                continue
            lo, hi = max(0, i - window), min(len(eqns), i + window + 1)
            overlappable = any(
                k != i
                and eqns[k].primitive.name in OVERLAPPABLE_PRIMITIVES
                and _independent(eqn, eqns[k])
                for k in range(lo, hi))
            if overlappable:
                continue
            sev = JAXPR_RULES["exposed-collective"][0]
            findings.append(Finding(
                rule="exposed-collective", severity=sev, path=label,
                line=0,
                message="%s over %s %s (%d bytes, axes %s) has no "
                        "independent overlappable compute within %d "
                        "eqns — it serializes the step; bucket it "
                        "against backward compute or prefetch the next "
                        "microbatch across it"
                        % (rec["primitive"], rec["dtype"], rec["shape"],
                           rec["bytes"], rec["axes"], window),
                snippet="%s:%s%s" % (rec["primitive"], rec["dtype"],
                                     rec["shape"])))
    return findings


# -- step card -------------------------------------------------------------

def step_card_from_jaxpr(closed_jaxpr, label: str = "<step>", *,
                         top_n: int = 10) -> dict:
    """Static cost accounting of one traced step (see module doc)."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    total_flops = 0
    total_bytes = 0
    n_eqns = 0
    collectives: List[dict] = []
    ranked: List[dict] = []
    for jx in _walk_jaxprs(jaxpr):
        for eqn in jx.eqns:
            n_eqns += 1
            fl = _eqn_flops(eqn)
            by = _eqn_bytes(eqn)
            total_flops += fl
            total_bytes += by
            if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
                collectives.append(_collective_record(eqn))
            if fl or by:
                out = _aval(eqn.outvars[0]) if eqn.outvars else None
                ranked.append({
                    "primitive": eqn.primitive.name,
                    "out_shape": list(out.shape) if out is not None
                    else [],
                    "flops": fl,
                    "bytes": by,
                })
    ranked.sort(key=lambda r: (r["flops"], r["bytes"]), reverse=True)
    fused_bytes = fused_hbm_estimate(jaxpr)
    card = {
        "label": label,
        "eqns": n_eqns,
        "flops": total_flops,
        "hbm_bytes": total_bytes,
        # hbm_bytes with every elementwise producer→consumer chain
        # fused — the delta is the fusion headroom megakernels attack
        "hbm_bytes_fused": fused_bytes,
        # bytes/flop: > ~1 means the step is bandwidth-shaped even
        # before fusion; the MFU ceiling is memory, not the MXU
        "arithmetic_intensity": round(total_flops / total_bytes, 3)
        if total_bytes else None,
        "arithmetic_intensity_fused": round(total_flops / fused_bytes, 3)
        if fused_bytes else None,
        "collectives": {
            "count": len(collectives),
            "bytes": sum(c["bytes"] for c in collectives),
            "inventory": collectives,
        },
        "dominant_eqns": ranked[:top_n],
    }
    return card


def step_card(step_call, inputs, labels, *, label: str = "<train_step>",
              top_n: int = 10, with_xla: bool = True) -> dict:
    """Step card for a compiled train step via its `analysis_handle`
    (jit/engine.py:make_train_step). When the backend exposes
    `compiled.cost_analysis()`, XLA's own totals ride along under
    `xla_cost` for calibration of the static estimate; the executable
    memory analysis (argument/output/temp/generated-code bytes, or the
    aval-size estimate where the backend lacks memory_analysis()) rides
    under `memory` and is banked into the memprof gauges so /statusz
    and the OOM bundle carry it too. `device_kind` pins which peak-
    table row `ptdoctor roofline` should read offline."""
    handle = getattr(step_call, "analysis_handle", None)
    if handle is None:
        raise ValueError(
            "step has no analysis_handle — build it with "
            "jit.engine.make_train_step")
    args = handle["pack"](inputs, labels)
    traced = handle["jitted"].trace(*args)
    card = step_card_from_jaxpr(traced.jaxpr, label, top_n=top_n)
    compiled = _compile(traced) if with_xla else None
    if with_xla:
        card["xla_cost"] = _xla_cost(compiled)
    card["memory"] = memory_analysis(traced, compiled)
    try:
        from ..observability import memprof
        card["device_kind"] = memprof.device_kind()
        memprof.bank_executable(label, card["memory"])
    except Exception:
        card.setdefault("device_kind", None)
    return card


def _compile(traced):
    try:
        return traced.lower().compile()
    except Exception:
        return None


def _xla_cost(compiled) -> Optional[dict]:
    """XLA cost analysis of the compiled step, when the backend offers
    it (dict of flops/bytes accessed/optimal seconds; None elsewhere)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        keep = {}
        for k, v in ca.items():
            # totals only — the per-operand "bytes accessedN{}" keys are
            # noise at this granularity
            if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "transcendentals")
                    or "optimal" in k):
                keep[k] = v
        return keep or None
    except Exception:
        return None


def memory_analysis(traced, compiled=None) -> Optional[dict]:
    """Executable memory attribution for one traced step.

    Source "xla" when `compiled.memory_analysis()` is reachable
    (argument/output/temp/generated-code section sizes of the actual
    executable); source "avals" elsewhere (CPU backend) — the
    invar/outvar aval footprints of the traced jaxpr, which bound the
    argument/output sections but cannot see XLA's temp allocations
    (reported 0, honestly)."""
    if compiled is not None:
        try:
            ma = compiled.memory_analysis()
            if isinstance(ma, (list, tuple)):
                ma = ma[0] if ma else None
            if ma is not None:
                args_b = int(getattr(ma, "argument_size_in_bytes", 0) or 0)
                out_b = int(getattr(ma, "output_size_in_bytes", 0) or 0)
                temp_b = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
                gen_b = int(getattr(ma, "generated_code_size_in_bytes", 0)
                            or 0)
                if args_b or out_b or temp_b or gen_b:
                    return {"source": "xla", "args_bytes": args_b,
                            "out_bytes": out_b, "temp_bytes": temp_b,
                            "gen_code_bytes": gen_b,
                            "total_bytes": args_b + out_b + temp_b + gen_b}
        except Exception:
            pass
    try:
        jaxpr = getattr(traced.jaxpr, "jaxpr", traced.jaxpr)

        def _tot(vs):
            n = 0
            for v in vs:
                a = _aval(v)
                if a is not None and getattr(a, "shape", None) is not None:
                    n += _nbytes(a.shape, a.dtype)
            return n

        args_b = _tot(jaxpr.invars)
        out_b = _tot(jaxpr.outvars)
        return {"source": "avals", "args_bytes": args_b,
                "out_bytes": out_b, "temp_bytes": 0, "gen_code_bytes": 0,
                "total_bytes": args_b + out_b}
    except Exception:
        return None


def write_step_card(card: dict, path: str) -> str:
    with open(path, "w") as f:
        json.dump(card, f, indent=1)
    return path

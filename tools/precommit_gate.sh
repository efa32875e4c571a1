#!/usr/bin/env bash
# Tier-1 gate: the EXACT suite the driver scores (ROADMAP.md "Tier-1
# verify"), runnable locally before a commit. Exit code is pytest's;
# DOTS_PASSED prints the pass-dot count for comparison against the
# previous round's baseline.
#
#   tools/precommit_gate.sh            # full tier-1
#   tools/precommit_gate.sh tests/test_resilience.py   # subset, same env
set -o pipefail
cd "$(dirname "$0")/.."

TARGET="${@:-tests/}"
LOG="${PRECOMMIT_GATE_LOG:-/tmp/_t1.log}"
rm -f "$LOG"

# The persistent compilation cache is on by default and lives in the
# checkout (.jax_cache). The smokes below count retraces and compiles, so
# they must not depend on what an earlier run left there: switch it off
# with jax's own variable. The compile-cache smoke turns it back on over
# a directory of its own.
export JAX_ENABLE_COMPILATION_CACHE=false

# Static-analysis gate (docs/STATIC_ANALYSIS.md): ptlint over paddle_tpu/
# must report zero unsuppressed findings. --train-step also traces the
# reference train step and runs the jaxpr rules (donation, sharding,
# exposed-collective, ...) over it. Cheapest check — runs first so a
# lint failure doesn't cost a full tier-1 round.
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/ptlint.py --train-step paddle_tpu/
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    echo "PTLINT=FAILED (rc=$lint_rc — fix the findings or suppress with a reason via --update-baseline)"
    exit "$lint_rc"
fi
echo "PTLINT=ok"
timeout -k 10 1800 env JAX_PLATFORMS=cpu python -m pytest $TARGET -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"

# Observability smoke (docs/OBSERVABILITY.md): a 2-step fit with
# telemetry on must produce a parseable journal + metrics snapshot and
# exactly ONE retrace (the first compile; a second one in a fixed-shape
# loop is a retrace bug).
if [ "$rc" -eq 0 ]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.observability import read_journal

d = tempfile.mkdtemp(prefix="pt_obs_smoke_")
paddle.seed(0)
net = nn.Linear(8, 4)
model = paddle.Model(net)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss())
X = np.random.RandomState(0).rand(16, 8).astype("float32")
Y = np.zeros((16, 1), np.int64)
model.fit([(X[i], Y[i]) for i in range(16)], batch_size=8, epochs=1,
          verbose=0, telemetry_dir=d)

evs = read_journal(os.path.join(d, "journal-rank0.jsonl"))  # valid JSONL
assert evs[0]["event"] == "run_start" and evs[-1]["event"] == "run_end", evs
snap = json.load(open(os.path.join(d, "metrics.json")))     # valid JSON
series = snap["metrics"]["pt_jit_retraces_total"]["series"]
retraces = {s["labels"]["engine"]: s["value"] for s in series}
assert retraces.get("jit_train") == 1.0, retraces
print("OBSERVABILITY_SMOKE=ok (2-step fit: retraces=1, journal %d events)"
      % len(evs))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "OBSERVABILITY_SMOKE=FAILED (rc=$smoke_rc)"
        rc=$smoke_rc
    fi
fi

# Compile-cache smoke (docs/PERFORMANCE.md "Compile cache & input
# pipeline"): the SAME 2-step gpt-tiny fit twice, fresh process each
# time, sharing one JAX_COMPILATION_CACHE_DIR. The warm run must
# reload executables from disk: journal says compile_cache (hits >= 1),
# retraces == 0, and compile wall time drops vs the cold run. (The
# observability smoke above keeps the no-cache contract honest:
# retraces == 1 when the cache is off.)
if [ "$rc" -eq 0 ]; then
    CC_DIR="$(mktemp -d /tmp/pt_cc_smoke_XXXXXX)"
    cc_smoke_run() {
        timeout -k 10 180 env JAX_PLATFORMS=cpu \
            JAX_ENABLE_COMPILATION_CACHE=true \
            JAX_COMPILATION_CACHE_DIR="$CC_DIR/cache" \
            PT_CC_SMOKE_DIR="$CC_DIR" \
            PT_CC_SMOKE_ROLE="$1" \
            python - <<'EOF'
import glob, json, os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny
from paddle_tpu.jit import compile_cache
from paddle_tpu.observability import read_journal, tracing

role = os.environ["PT_CC_SMOKE_ROLE"]
root = os.environ["PT_CC_SMOKE_DIR"]
paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=32)
model = paddle.Model(m)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=m.parameters()),
              GPTPretrainingCriterion())
ids = np.random.RandomState(0).randint(0, 64, (4, 17)).astype(np.int64)
tdir = os.path.join(root, "telemetry_" + role)
model.fit([(ids[i, :-1], ids[i, 1:]) for i in range(4)], batch_size=2,
          epochs=1, verbose=0, telemetry_dir=tdir)

hits, misses = compile_cache.totals()
retraces = tracing.RETRACES.labels("jit_train").value
compile_s = tracing.COMPILE_SECONDS.labels("jit_train").value
evs = []
for p in sorted(glob.glob(os.path.join(tdir, "journal-*.jsonl"))):
    evs.extend(read_journal(p))
assert compile_cache.enabled(), "cache not configured"
if role == "cold":
    assert misses >= 1 and retraces >= 1, (hits, misses, retraces)
    with open(os.path.join(root, "cold.json"), "w") as f:
        json.dump({"compile_s": compile_s}, f)
else:
    cold = json.load(open(os.path.join(root, "cold.json")))
    cc_evs = [e for e in evs if e["event"] == "compile_cache"]
    assert hits >= 1 and misses == 0, (hits, misses)
    assert retraces == 0, retraces
    assert cc_evs and cc_evs[0]["hits"] >= 1, cc_evs
    assert not any(e["event"] == "retrace" for e in evs), evs
    assert compile_s < cold["compile_s"], (compile_s, cold)
    print("COMPILE_CACHE_SMOKE=ok (warm restart: hits=%d retraces=0 "
          "compile %.2fs -> %.2fs)" % (hits, cold["compile_s"], compile_s))
EOF
    }
    cc_smoke_run cold && cc_smoke_run warm
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "COMPILE_CACHE_SMOKE=FAILED (rc=$smoke_rc, logs in $CC_DIR)"
        rc=$smoke_rc
    else
        rm -rf "$CC_DIR"
    fi
fi

# Flash-attention smoke (docs/PERFORMANCE.md): a 2-step GPT-2-tiny fit
# with interpret-mode flash dropout enabled must trace the Pallas path
# (attn_paths.flash_dropout > 0, nothing on xla_sdpa), keep grads/loss
# finite, and route eval forwards onto the dropout-free flash kernel.
if [ "$rc" -eq 0 ]; then
    timeout -k 10 180 env JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny
from paddle_tpu.ops.pallas_kernels import attention_path_counts

paddle.seed(0)
set_flags({"FLAGS_flash_dropout_interpret": True})
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=32,
             attn_dropout_prob=0.1, hidden_dropout_prob=0.0)
crit = GPTPretrainingCriterion()
opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=m.parameters())
ids = np.random.RandomState(0).randint(0, 64, (2, 17)).astype(np.int64)
x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

attention_path_counts(reset=True)
losses = []
for _ in range(2):
    loss = crit(m(x), y)
    loss.backward()
    g = m.gpt.embeddings.word_embeddings.weight.grad
    assert g is not None and np.isfinite(g.numpy()).all()
    opt.step()
    opt.clear_grad()
    losses.append(float(loss.numpy()))
counts = attention_path_counts()
assert counts.get("flash_dropout", 0) > 0, counts
assert counts.get("xla_sdpa", 0) == 0, counts
assert all(np.isfinite(l) for l in losses), losses

m.eval()
attention_path_counts(reset=True)
m(x)
ev = attention_path_counts()
assert ev.get("flash", 0) > 0 and ev.get("flash_dropout", 0) == 0, ev
print("FLASH_SMOKE=ok (2-step fit: train=%d flash_dropout traces, "
      "eval=%d flash traces, losses=%s)"
      % (counts["flash_dropout"], ev["flash"],
         ["%.3f" % l for l in losses]))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "FLASH_SMOKE=FAILED (rc=$smoke_rc)"
        rc=$smoke_rc
    fi
fi

# Checkpoint smoke (docs/CHECKPOINT.md): save two epochs, corrupt a blob
# of the newest, and resume — the loader must quarantine the corrupt dir
# and fall back to the last-good checkpoint without raising.
if [ "$rc" -eq 0 ]; then
    timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import glob, os, tempfile
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.checkpoint import engine, store
from paddle_tpu.observability import REGISTRY

root = tempfile.mkdtemp(prefix="pt_ckpt_smoke_")
paddle.seed(0)
net = nn.Linear(4, 2)
want = {k: np.asarray(v.numpy()) for k, v in net.state_dict().items()}
for ep in (0, 1):
    engine.save_checkpoint(os.path.join(root, f"epoch_{ep}"), net, None,
                           meta={"epoch": ep})

blob = sorted(glob.glob(os.path.join(root, "epoch_1", "blobs", "*.bin")))[0]
with open(blob, "r+b") as f:       # bit rot in the newest checkpoint
    b = f.read(1); f.seek(0); f.write(bytes([b[0] ^ 0x01]))

before = REGISTRY.counter("pt_ckpt_corrupt_total", "").value
used, meta = engine.load_latest(
    [os.path.join(root, "epoch_1"), os.path.join(root, "epoch_0")],
    net, None)
assert used == os.path.join(root, "epoch_0"), used
assert meta.get("epoch") == 0, meta
assert os.path.isdir(os.path.join(root, "epoch_1") + ".corrupt")
assert REGISTRY.counter("pt_ckpt_corrupt_total", "").value == before + 1
for k, v in net.state_dict().items():
    np.testing.assert_array_equal(np.asarray(v.numpy()), want[k])
assert store.is_complete(os.path.join(root, "epoch_0"))
print("CHECKPOINT_SMOKE=ok (corrupt epoch_1 quarantined, resumed epoch_0)")
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "CHECKPOINT_SMOKE=FAILED (rc=$smoke_rc)"
        rc=$smoke_rc
    fi
fi

# Dist smoke (docs/RESILIENCE.md "Distributed failures"): a 2-rank
# launch where chaos SIGKILLs rank 1 mid-run must gang-restart exactly
# once, auto-resume from the last-good checkpoint, and finish rc=0.
if [ "$rc" -eq 0 ]; then
    DIST_DIR="$(mktemp -d /tmp/pt_dist_smoke_XXXXXX)"
    timeout -k 10 240 env JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        PADDLE_TPU_CHAOS="kill_rank:1:2" \
        PADDLE_TPU_GANG_GRACE_S=2 \
        PT_GANG_CKPT="$DIST_DIR/ckpt" \
        PT_DIST_OUT="$DIST_DIR/out.json" \
        python -m paddle_tpu.distributed.launch \
            --nproc_per_node 2 --max_restarts 1 \
            --log_dir "$DIST_DIR/logs" \
            tests/dist_worker.py gang > "$DIST_DIR/launch.log" 2>&1
    smoke_rc=$?
    restarts=$(python - "$DIST_DIR/logs/metrics-launch.json" <<'EOF'
import json, sys
try:
    data = json.load(open(sys.argv[1]))
    print(int(data["metrics"]["pt_gang_restarts_total"]["series"][0]["value"]))
except Exception:
    print(-1)
EOF
)
    # forensics (docs/OBSERVABILITY.md "Post-mortem & crash forensics"):
    # the launcher must have merged a cross-rank timeline, the killed rank
    # must have left exactly ONE crash bundle, and ptdoctor must render
    # the run dir without error.
    bundles=$(ls -d "$DIST_DIR"/logs/crash/*/ 2>/dev/null | wc -l)
    doctor_rc=1
    if [ -d "$DIST_DIR/logs" ]; then
        python tools/ptdoctor.py summary "$DIST_DIR/logs" \
            > "$DIST_DIR/ptdoctor.log" 2>&1
        doctor_rc=$?
    fi
    if [ "$smoke_rc" -eq 0 ] && [ "$restarts" = "1" ] \
            && [ -f "$DIST_DIR/logs/timeline.jsonl" ] \
            && [ "$bundles" = "1" ] && [ "$doctor_rc" -eq 0 ]; then
        echo "DIST_SMOKE=ok (2 ranks, rank 1 killed, gang_restarts=1, timeline + 1 crash bundle, ptdoctor ok)"
        rm -rf "$DIST_DIR"
    else
        echo "DIST_SMOKE=FAILED (rc=$smoke_rc gang_restarts=$restarts bundles=$bundles ptdoctor_rc=$doctor_rc, logs in $DIST_DIR)"
        tail -20 "$DIST_DIR/launch.log"
        [ -f "$DIST_DIR/ptdoctor.log" ] && tail -20 "$DIST_DIR/ptdoctor.log"
        [ "$smoke_rc" -ne 0 ] && rc=$smoke_rc || rc=1
    fi
fi

# Elastic smoke (docs/RESILIENCE.md "Elastic topology changes"): rank 1
# dies in EVERY round (dead_rank chaos), so after one budgeted gang
# restart the launcher must shrink-to-fit to world=1 WITHOUT exhausting
# the budget; the survivor resumes from the last-good sharded checkpoint
# saved at world=2 (restore-with-reshard) and finishes rc=0; ptdoctor
# must report the topology change.
if [ "$rc" -eq 0 ]; then
    EL_DIR="$(mktemp -d /tmp/pt_elastic_smoke_XXXXXX)"
    timeout -k 10 240 env JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        PADDLE_TPU_CHAOS="dead_rank:1" \
        PADDLE_TPU_GANG_GRACE_S=2 \
        PT_GANG_CKPT="$EL_DIR/ckpt" \
        PT_DIST_OUT="$EL_DIR/out.json" \
        python -m paddle_tpu.distributed.launch \
            --nproc_per_node 2 --max_restarts 1 \
            --log_dir "$EL_DIR/logs" \
            tests/dist_worker.py degraded > "$EL_DIR/launch.log" 2>&1
    smoke_rc=$?
    shrinks=$(python - "$EL_DIR/logs/metrics-launch.json" <<'EOF'
import json, sys
try:
    data = json.load(open(sys.argv[1]))
    print(int(data["metrics"]["pt_gang_shrinks_total"]["series"][0]["value"]))
except Exception:
    print(-1)
EOF
)
    final=$(python - "$EL_DIR/out.json.0" <<'EOF'
import json, sys
try:
    d = json.load(open(sys.argv[1]))
    # the survivor finished at world=1 having resumed past the restored
    # epoch: start>0 proves the world-2 checkpoint fed the world-1 run
    ok = d["world"] == 1 and d["start"] > 0 and d["resharded"] >= 1
    print("ok" if ok else d)
except Exception as e:
    print("err:%s" % e)
EOF
)
    doctor_topo=1
    if [ -d "$EL_DIR/logs" ]; then
        python tools/ptdoctor.py summary "$EL_DIR/logs" \
            > "$EL_DIR/ptdoctor.log" 2>&1 \
            && grep -qi "shrink" "$EL_DIR/ptdoctor.log" \
            && grep -q "2 -> 1" "$EL_DIR/ptdoctor.log"
        doctor_topo=$?
    fi
    if [ "$smoke_rc" -eq 0 ] && [ "$shrinks" = "1" ] \
            && [ "$final" = "ok" ] && [ "$doctor_topo" -eq 0 ]; then
        echo "ELASTIC_SMOKE=ok (dead rank 1, gang_shrinks=1, resumed at world=1 from resharded ckpt, ptdoctor topology ok)"
        rm -rf "$EL_DIR"
    else
        echo "ELASTIC_SMOKE=FAILED (rc=$smoke_rc gang_shrinks=$shrinks final=$final ptdoctor_topo=$doctor_topo, logs in $EL_DIR)"
        tail -20 "$EL_DIR/launch.log"
        [ -f "$EL_DIR/ptdoctor.log" ] && tail -20 "$EL_DIR/ptdoctor.log"
        [ "$smoke_rc" -ne 0 ] && rc=$smoke_rc || rc=1
    fi
fi

# Profile smoke (docs/OBSERVABILITY.md "Spans & step profiling"): a
# 2-step gpt-tiny fit with telemetry on must journal nested step spans
# whose children (feed/compile/dispatch/host) cover >= 90% of measured
# step wall time with sane durations, write a static step card, and
# `ptdoctor profile` must render the breakdown with rc 0.
if [ "$rc" -eq 0 ]; then
    PROF_DIR="$(mktemp -d /tmp/pt_prof_smoke_XXXXXX)"
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        PT_PROF_SMOKE_DIR="$PROF_DIR" python - <<'EOF'
import os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.analysis import step_card, write_step_card
from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny
from paddle_tpu.observability import read_journal

d = os.environ["PT_PROF_SMOKE_DIR"]
paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=32)
model = paddle.Model(m)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=m.parameters()),
              GPTPretrainingCriterion())
ids = np.random.RandomState(0).randint(0, 64, (4, 17)).astype(np.int64)
model.fit([(ids[i, :-1], ids[i, 1:]) for i in range(4)], batch_size=2,
          epochs=1, verbose=0, telemetry_dir=d)

x, y = paddle.to_tensor(ids[:2, :-1]), paddle.to_tensor(ids[:2, 1:])
card = step_card(model._train_step_fn, [x], [y], label="gpt_tiny_train")
write_step_card(card, os.path.join(d, "step_card.json"))
assert card["flops"] > 0 and card["eqns"] > 0, card

evs = read_journal(os.path.join(d, "journal-rank0.jsonl"))
sp = [e for e in evs if e["event"] == "span"]
steps = [e for e in sp if e["name"] == "step"]
assert len(steps) == 2, [e["name"] for e in sp]
assert all(0 < e["dur_ms"] < 120000 for e in sp), sp
kids = [e for e in sp if e.get("parent") == "step"]
assert {"feed", "compile", "dispatch", "host"} <= \
    {e["name"] for e in kids}, kids
step_total = sum(e["dur_ms"] for e in steps)
child_total = sum(e["dur_ms"] for e in kids)
assert child_total >= 0.9 * step_total, (child_total, step_total)
print("PROFILE_SMOKE=ok (2-step fit: %d spans, step decomposition "
      "%.1f%% covered, step card flops=%d)"
      % (len(sp), 100.0 * child_total / step_total, card["flops"]))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        python tools/ptdoctor.py profile "$PROF_DIR" \
            > "$PROF_DIR/profile.log" 2>&1 \
            && grep -q "step decomposition" "$PROF_DIR/profile.log" \
            && grep -q "step card" "$PROF_DIR/profile.log"
        smoke_rc=$?
    fi
    if [ "$smoke_rc" -ne 0 ]; then
        echo "PROFILE_SMOKE=FAILED (rc=$smoke_rc, logs in $PROF_DIR)"
        [ -f "$PROF_DIR/profile.log" ] && tail -10 "$PROF_DIR/profile.log"
        rc=$smoke_rc
    else
        grep -h "critical path" "$PROF_DIR/profile.log"
        rm -rf "$PROF_DIR"
    fi
fi

# Memprof smoke (docs/OBSERVABILITY.md "Memory forensics & roofline"):
# a 2-step gpt-tiny fit must bank executable memory attribution into
# the step card (`memory` block with an honest source tag) and the HBM
# sample history, `ptdoctor roofline` must join card + spans and name a
# limiter with rc 0, and a chaos oom:1 drill must walk the whole
# RESOURCE_EXHAUSTED catch path: exactly ONE crash bundle whose
# memory.json carries a non-empty live-buffer table.
if [ "$rc" -eq 0 ]; then
    MEM_DIR="$(mktemp -d /tmp/pt_mem_smoke_XXXXXX)"
    timeout -k 10 180 env JAX_PLATFORMS=cpu \
        PT_MEM_SMOKE_DIR="$MEM_DIR" python - <<'EOF'
import os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.analysis import step_card, write_step_card
from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny
from paddle_tpu.observability import memprof

d = os.environ["PT_MEM_SMOKE_DIR"]
paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=32)
model = paddle.Model(m)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=m.parameters()),
              GPTPretrainingCriterion())
ids = np.random.RandomState(0).randint(0, 64, (4, 17)).astype(np.int64)
model.fit([(ids[i, :-1], ids[i, 1:]) for i in range(4)], batch_size=2,
          epochs=1, verbose=0, telemetry_dir=d)

x, y = paddle.to_tensor(ids[:2, :-1]), paddle.to_tensor(ids[:2, 1:])
card = step_card(model._train_step_fn, [x], [y], label="gpt_tiny_train")
write_step_card(card, os.path.join(d, "step_card.json"))
mem = card.get("memory")
assert mem and mem.get("source") in ("xla", "avals"), mem
assert mem.get("total_bytes", 0) > 0, mem
assert memprof.executable_bank().get("gpt_tiny_train"), \
    memprof.executable_bank()
hist = memprof.hbm_history()
assert hist and all(s.get("in_use", 0) > 0 for s in hist), hist
print("MEMPROF_SMOKE fit=ok (memory source=%s total=%d bytes, "
      "%d hbm samples)"
      % (mem["source"], mem["total_bytes"], len(hist)))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        python tools/ptdoctor.py roofline "$MEM_DIR" \
            > "$MEM_DIR/roofline.log" 2>&1 \
            && grep -q "limiter:" "$MEM_DIR/roofline.log"
        smoke_rc=$?
    fi
    if [ "$smoke_rc" -eq 0 ]; then
        timeout -k 10 180 env JAX_PLATFORMS=cpu \
            PADDLE_TPU_CHAOS=oom:1 \
            PT_MEM_SMOKE_DIR="$MEM_DIR/oom_drill" python - <<'EOF'
import glob
import json
import os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import GPTPretrainingCriterion, gpt_tiny

d = os.environ["PT_MEM_SMOKE_DIR"]
paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=32)
model = paddle.Model(m)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=m.parameters()),
              GPTPretrainingCriterion())
ids = np.random.RandomState(0).randint(0, 64, (4, 17)).astype(np.int64)
try:
    model.fit([(ids[i, :-1], ids[i, 1:]) for i in range(4)], batch_size=2,
              epochs=1, verbose=0, telemetry_dir=d)
    raise SystemExit("chaos oom:1 did not raise")
except Exception as e:
    assert "RESOURCE_EXHAUSTED" in str(e), e

bundles = sorted(glob.glob(os.path.join(d, "crash", "*", "MANIFEST.json")))
assert len(bundles) == 1, bundles
manifest = json.load(open(bundles[0]))
assert manifest["reason"] == "oom", manifest
mem = json.load(open(os.path.join(os.path.dirname(bundles[0]),
                                  "memory.json")))
assert mem.get("engine") == "jit_train", mem
bufs = (mem.get("buffers") or {}).get("groups") or []
assert bufs and all(b["total_bytes"] > 0 for b in bufs), mem.get("buffers")
print("MEMPROF_SMOKE oom_drill=ok (1 bundle, %d live-buffer groups, "
      "engine=%s)" % (len(bufs), mem["engine"]))
EOF
        smoke_rc=$?
    fi
    if [ "$smoke_rc" -ne 0 ]; then
        echo "MEMPROF_SMOKE=FAILED (rc=$smoke_rc, logs in $MEM_DIR)"
        [ -f "$MEM_DIR/roofline.log" ] && tail -10 "$MEM_DIR/roofline.log"
        rc=$smoke_rc
    else
        echo "MEMPROF_SMOKE=ok ($(grep -h 'limiter:' "$MEM_DIR/roofline.log" \
            | head -1 | sed 's/^ *//'))"
        rm -rf "$MEM_DIR"
    fi
fi

# Serving smoke (docs/SERVING.md): 4 staggered requests through the
# threaded InferenceServer must all complete with their full token
# budget, the decode step must compile exactly ONCE (a second trace in
# the fixed-shape decode loop is a retrace bug), two staggered requests
# sharing a system prompt must make the second admission a prefix-cache
# HIT whose TTFT beats a cold admission's, and the gpt2 bench must emit
# valid gated JSON rows where continuous batching beats static
# sequential batching and the prefix/int8 multipliers hold.
if [ "$rc" -eq 0 ]; then
    timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import time
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference.serving import InferenceServer
from paddle_tpu.models import gpt_tiny
from paddle_tpu.observability.tracing import RETRACES

paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=64)
m.eval()
rs = np.random.RandomState(0)
with InferenceServer(m, max_batch=4, max_seq_len=64,
                     prefill_buckets=(8, 16)) as srv:
    handles = []
    for n in (3, 6, 9, 12):   # staggered -> mid-flight slot admission
        handles.append(srv.submit(rs.randint(0, 64, (n,)), max_new_tokens=5))
        time.sleep(0.02)
    toks = [h.result(timeout=120) for h in handles]
    eng = srv.engines[0]
assert all(len(t) == 5 for t in toks), [len(t) for t in toks]
assert eng.decode_compiles == 1, eng.decode_compiles
assert eng.prefill_compiles <= 2, eng.prefill_compiles   # <= n_buckets
# retraces==0 after the first compile: the counter holds ONLY that one
assert RETRACES.labels("serve_decode").value == 1.0, \
    RETRACES.labels("serve_decode").value
print("SERVING_SMOKE=ok (4 staggered requests complete, decode compiled "
      "once, prefill compiles=%d/2 buckets)" % eng.prefill_compiles)

# shared-prefix reuse (docs/SERVING.md "Prefix cache"): requests
# sharing a 48-token system prompt — after a warmup pass compiles both
# admission paths, a prefix-HIT admission (suffix-only prefill) must
# beat a cold full-bucket admission on TTFT
head = rs.randint(0, 64, (48,))


def req(suffix_len, shared):
    base = head if shared else rs.randint(0, 64, (48,))
    return np.concatenate([base, rs.randint(0, 64, (suffix_len,))])


with InferenceServer(m, max_batch=2, max_seq_len=64,
                     prefill_buckets=(8, 48, 56),
                     prefix_cache_bytes=32 << 20) as srv:
    eng = srv.engines[0]
    # warm: store the shared prefix, compile the cold-56 bucket and the
    # (48, 8) suffix executables — the timed loop reuses all three
    srv.submit(req(4, True), max_new_tokens=2).result(timeout=120)
    srv.submit(req(2, True), max_new_tokens=2).result(timeout=120)
    srv.submit(req(3, False), max_new_tokens=2).result(timeout=120)
    assert eng.prefix_cache.hits == 1, eng.prefix_cache.hits
    miss_t, hit_t = [], []
    for _ in range(3):
        hm = srv.submit(req(3, False), max_new_tokens=2)
        hm.result(timeout=120)
        hh = srv.submit(req(3, True), max_new_tokens=2)
        hh.result(timeout=120)
        assert hm.request.prefix_len == 0, hm.request.prefix_len
        assert hh.request.prefix_len == 48, hh.request.prefix_len
        miss_t.append(hm.request.ttft_s)
        hit_t.append(hh.request.ttft_s)
    hits = eng.prefix_cache.hits
    assert hits == 4, hits
    assert eng.decode_compiles == 1, eng.decode_compiles
assert min(hit_t) < min(miss_t), (hit_t, miss_t)
print("SERVING_SMOKE=ok+prefix (hit ttft %.1fms < miss ttft %.1fms over "
      "%d hits, decode compiled once)"
      % (min(hit_t) * 1e3, min(miss_t) * 1e3, hits))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "SERVING_SMOKE=FAILED (rc=$smoke_rc)"
        rc=$smoke_rc
    fi
fi

# Overload smoke (docs/SERVING.md "SLO admission control"): a burst
# past max_queue_depth on a tiny single-slot engine must shed with a
# positive retry_after_s while everything admitted completes in full,
# the shed ledger must agree across all three surfaces (ShedError
# count == serve_shed journal events == pt_serve_shed_total), the
# replica must stay 200 on /healthz (degraded is not dead — a fresh
# submit after the burst still serves), and shedding must leave ZERO
# crash bundles behind.
if [ "$rc" -eq 0 ]; then
    OV_DIR="$(mktemp -d /tmp/pt_overload_smoke_XXXXXX)"
    timeout -k 10 240 env JAX_PLATFORMS=cpu PT_OV_SMOKE_DIR="$OV_DIR" \
        python - <<'EOF'
import glob
import json
import os
import urllib.request
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.inference.serving import InferenceServer, ShedError, SLOPolicy
from paddle_tpu.inference.serving.slo import DEADLINE_EXPIRED, SHED
from paddle_tpu.models import gpt_tiny
from paddle_tpu.observability import flight
from paddle_tpu.observability import journal as journal_mod

d = os.environ["PT_OV_SMOKE_DIR"]
flight.configure(d, rank=0)
journal_mod.set_journal(journal_mod.RunJournal(d, rank=0))
paddle.seed(0)
m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             intermediate_size=64, max_position_embeddings=64)
m.eval()
rs = np.random.RandomState(0)
# huge budget: only the queue bound actuates -> every shed is queue_full
policy = SLOPolicy(ttft_budget_ms=1e6, max_queue_depth=1)
with InferenceServer(m, max_batch=1, max_seq_len=64, prefill_buckets=(8,),
                     slo=policy, http_port=0) as srv:
    # warm: compile prefill+decode so the burst measures admission
    srv.submit(rs.randint(0, 64, (4,)), max_new_tokens=2).result(timeout=120)
    url = srv._http.url
    handles = [srv.submit(rs.randint(0, 64, (4,)), max_new_tokens=8)
               for _ in range(12)]
    assert urllib.request.urlopen(url + "/healthz",
                                  timeout=10).status == 200
    done, shed = [], []
    for h in handles:
        try:
            done.append(h.result(timeout=120))
        except ShedError as e:
            shed.append(e)
    # degraded is not dead: a post-burst submit still serves, and the
    # probe never flipped the replica to 503
    tail = srv.submit(rs.randint(0, 64, (4,)),
                      max_new_tokens=3).result(timeout=120)
    assert urllib.request.urlopen(url + "/healthz",
                                  timeout=10).status == 200
assert shed, "burst past max_queue_depth shed nothing"
assert done, "burst shed everything -- nothing served"
assert all(e.retry_after_s > 0 for e in shed), \
    [e.retry_after_s for e in shed]
assert all(e.reason == "queue_full" for e in shed), \
    sorted({e.reason for e in shed})
assert all(len(t) == 8 for t in done), [len(t) for t in done]
assert len(tail) == 3, len(tail)
metric_sheds = int(sum(
    SHED.labels(r).value
    for r in ("queue_full", "slo_breach", "brownout", "deadline_expired")))
journal_sheds = sum(
    1
    for p in glob.glob(os.path.join(d, "journal-*.jsonl"))
    for line in open(p)
    if json.loads(line).get("event") == "serve_shed")
assert journal_sheds == len(shed) == metric_sheds, \
    (journal_sheds, len(shed), metric_sheds)
assert DEADLINE_EXPIRED.value == 0.0, DEADLINE_EXPIRED.value
bundles = glob.glob(os.path.join(d, "crash", "*", "MANIFEST.json"))
assert not bundles, bundles
print("OVERLOAD_SMOKE=ok (%d served + %d shed of 12, retry_after>0, "
      "journal==metrics==%d sheds, /healthz 200, 0 crash bundles)"
      % (len(done), len(shed), metric_sheds))
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -ne 0 ]; then
        echo "OVERLOAD_SMOKE=FAILED (rc=$smoke_rc, logs in $OV_DIR)"
        rc=$smoke_rc
    else
        rm -rf "$OV_DIR"
    fi
fi

# HTTP smoke (docs/OBSERVABILITY.md "Live endpoints & trace viewing"):
# a 2-step fit with PADDLE_TPU_HTTP_PORT=0 must publish its ephemeral
# endpoint through endpoint-rank0.json, answer a valid Prometheus
# /metrics exposition (containing pt_span_ms) and a 200 /healthz WHILE
# the fit is stepping, /statusz must parse with rank 0 and the step
# count, and `ptdoctor trace` over the run dir (plus a second synthetic
# rank's journal) must emit a chrome trace with >= 2 tracks.
if [ "$rc" -eq 0 ]; then
    HTTP_DIR="$(mktemp -d /tmp/pt_http_smoke_XXXXXX)"
    timeout -k 10 180 env JAX_PLATFORMS=cpu PADDLE_TPU_HTTP_PORT=0 \
        PT_HTTP_SMOKE_DIR="$HTTP_DIR" python - <<'EOF'
import json, os, re, threading, time, urllib.request
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.observability import journal, spans

d = os.environ["PT_HTTP_SMOKE_DIR"]
paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
model = paddle.Model(net)
model.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters()),
              nn.CrossEntropyLoss())
X = np.random.RandomState(0).rand(16, 8).astype("float32")
Y = np.zeros((16, 1), np.int64)
ds = [(X[i], Y[i]) for i in range(16)]
err = []
def fit():
    try:
        model.fit(ds, batch_size=8, epochs=1, verbose=0, telemetry_dir=d)
    except BaseException as e:
        err.append(e)
t = threading.Thread(target=fit, daemon=True)
t.start()
ep_path = os.path.join(d, "endpoint-rank0.json")
deadline = time.time() + 60
while not os.path.exists(ep_path) and time.time() < deadline and not err:
    time.sleep(0.01)
assert os.path.exists(ep_path), err
url = json.load(open(ep_path))["url"]
# scrape DURING the fit: exposition must never be torn
body = urllib.request.urlopen(url + "/metrics", timeout=5).read().decode()
assert "pt_span_ms" in body, body[:400]
pat = re.compile(r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? \S+)$")
bad = [l for l in body.rstrip("\n").split("\n") if not pat.match(l)]
assert not bad, bad[:3]
assert urllib.request.urlopen(url + "/healthz", timeout=5).status == 200
t.join(120)
assert not t.is_alive() and not err, err
st = json.loads(urllib.request.urlopen(url + "/statusz", timeout=5).read())
assert st["rank"] == 0 and st["train"]["steps_total"] >= 2, st
# a second rank's journal so the exported trace carries >= 2 tracks
j = journal.RunJournal(d, rank=1, filename="journal-rank1.jsonl")
prev = journal.set_journal(j)
spans.record("step", 5.0)
journal.set_journal(prev)
j.close()
print("HTTP_SMOKE=ok (live /metrics+/healthz during fit, "
      "statusz steps=%d)" % st["train"]["steps_total"])
EOF
    smoke_rc=$?
    if [ "$smoke_rc" -eq 0 ]; then
        python tools/ptdoctor.py trace "$HTTP_DIR" \
            > "$HTTP_DIR/trace.log" 2>&1 \
            && PT_HTTP_SMOKE_DIR="$HTTP_DIR" python - <<'EOF'
import json, os
evs = json.load(open(os.path.join(os.environ["PT_HTTP_SMOKE_DIR"],
                                  "trace.json")))["traceEvents"]
tracks = {(e["pid"], e["tid"]) for e in evs if e.get("ph") != "M"}
assert len(tracks) >= 2, tracks
print("HTTP_SMOKE trace: %d events, %d tracks" % (len(evs), len(tracks)))
EOF
        smoke_rc=$?
    fi
    if [ "$smoke_rc" -ne 0 ]; then
        echo "HTTP_SMOKE=FAILED (rc=$smoke_rc, logs in $HTTP_DIR)"
        [ -f "$HTTP_DIR/trace.log" ] && tail -5 "$HTTP_DIR/trace.log"
        rc=$smoke_rc
    else
        rm -rf "$HTTP_DIR"
    fi
fi
exit $rc

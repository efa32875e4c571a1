"""Global flag registry.

TPU-native equivalent of the reference's gflags surface
(/root/reference/paddle/fluid/platform/flags.cc:48- and python get/set at
/root/reference/python/paddle/fluid/framework.py:6461,6485). Flags are plain
typed python values seeded from FLAGS_* environment variables at import.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_FLAGS: Dict[str, Any] = {}


def define_flag(name: str, default, help_str: str = ""):
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _FLAGS[name] = value
    return value


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _FLAGS:
            raise ValueError(f"unknown flag {f!r}")
        out[f] = _FLAGS[key]
    return out


def set_flags(flags: Dict[str, Any]):
    for f, v in flags.items():
        key = f[6:] if f.startswith("FLAGS_") else f
        if key not in _FLAGS:
            raise ValueError(f"unknown flag {f!r}")
        _FLAGS[key] = v


def flag(name: str):
    return _FLAGS[name]


# Core flags (subset of the reference's ~51 exported gflags that are
# meaningful on TPU; stream/cudnn/allocator flags have no XLA analogue).
define_flag("check_nan_inf", False,
            "after each eager op, sync and abort on non-finite outputs "
            "(reference: FLAGS_check_nan_inf, operator.cc:1222)")
define_flag("benchmark", False,
            "block on every eager op result (reference: FLAGS_benchmark)")
define_flag("eager_op_jit", True,
            "compile+cache each eager op as its own XLA executable; "
            "False falls back to op-by-op dispatch without jit")
define_flag("seed", 0, "global random seed when nonzero")
define_flag("allocator_strategy", "xla",
            "accepted for parity; XLA/PJRT owns device memory")
define_flag("tpu_matmul_precision", "default",
            "jax matmul precision: default|high|highest")
define_flag("conv_algo", "auto",
            "convolution lowering: 'auto' (on TPU, 4-D NCHW convs run "
            "through an NHWC-internal layout — XLA-TPU's native conv "
            "layout, avoiding the per-layer relayouts the NCHW dimension "
            "numbers force; elsewhere identical to direct), 'direct' "
            "(lax.conv with the model's own layout) or 'im2col' (patches "
            "+ one MXU matmul; groups=1 only). benchmarks/conv_bench.py "
            "compares the three (BASELINE.md ResNet-50 investigation)")
define_flag("flash_dropout_interpret", False,
            "allow the dropout-enabled flash kernel in interpret mode "
            "(CPU kernel tests only — the emulator is too slow for train "
            "loops; on TPU dropout always stays on the flash path)")
define_flag("sdpa_chunked_threshold", 2048,
            "key length at which the plain XLA sdpa switches to the "
            "blockwise online-softmax path (O(T*block) memory, remat'd "
            "blocks) instead of materialising the [Tq, Tk] score matrix. "
            "This keeps long-context attention viable where the Pallas "
            "flash kernel does not run (CPU, masks, ineligible shapes, "
            "FLAGS_use_flash_attention off). 0 disables")
define_flag("use_flash_attention", True,
            "route F.scaled_dot_product_attention to the Pallas flash "
            "kernel when shapes/backend allow")
define_flag("flash_autotune_blocks", False,
            "one-shot timed sweep of flash-attention (block_q, block_k) "
            "over {128,256,512} per attention shape on TPU; the choice is "
            "cached in-process and persisted to "
            "<PADDLE_TPU_TELEMETRY_DIR>/flash_autotune.json. False pins "
            "the 128x128 defaults. Default off: on the v5e the sweep picked "
            "512x512 every time at the GPT-2 train shape, but at small "
            "shapes (a 256-token prefill) its candidates time within noise "
            "of each other and the pick flipped between two processes, "
            "which changes the HLO and misses the persistent compile cache "
            "(PERF.md, PR 21)")
define_flag("use_fused_optimizer", True,
            "route Adam/AdamW updates to the Pallas fused kernel on TPU "
            "(single HBM pass, in-place via buffer aliasing)")
define_flag("skip_nonfinite_steps", False,
            "compiled/eager train steps whose loss or grads are non-finite "
            "keep the old params + optimizer state (the update is skipped) "
            "instead of poisoning the weights. The skip is selected INSIDE "
            "the compiled step (no host round-trip); pair with "
            "resilience.AnomalyGuard to bound skip streaks (reference: "
            "update_loss_scaling_op's found_inf => zeroed update)")
define_flag("step_watchdog_s", 0.0,
            "when > 0, wrap each compiled-step dispatch in a "
            "resilience.StepWatchdog that dumps all-thread stacks after "
            "this many seconds instead of hanging silently (a dispatch "
            "stuck inside PJRT: a hung device or a collective waiting on a "
            "dead peer). 0 disables")
define_flag("step_watchdog_action", "warn",
            "watchdog behavior on fire: 'warn' (dump diagnostics, keep "
            "waiting) or 'abort' (dump then os._exit(124) so a supervisor "
            "— launcher/elastic manager — restarts the process)")
define_flag("use_fused_dropout_ln", False,
            "route fused bias+dropout+residual+layernorm to the Pallas "
            "kernel when shapes/backend allow. Default off: measured 0.47x "
            "vs XLA's own fusion of this chain on v5e at GPT-2 shapes "
            "(benchmarks/fused_kernels_bench.py r3) — XLA wins; the kernel "
            "stays available for shapes/backends where it does not")
define_flag("paged_flash_interpret", False,
            "allow the paged-decode kernels in Pallas interpret mode off "
            "TPU (CPU parity tests only — the emulator is far too slow "
            "for real serving); without it the CPU takes the einsum of "
            "serving/cache.py (pt_attn_path_total{path=xla_paged})")
define_flag("fused_block", False,
            "decoder-block fusion: GPTDecoderLayer runs the attention "
            "epilogue (residual dropout-add) and the following ln_2 as ONE "
            "Pallas pass, so the post-attention activation never "
            "round-trips HBM between the residual add and the LN read. "
            "Default off pending a measured win at target shapes "
            "(benchmarks/fused_kernels_bench.py decoder_block_tail row); "
            "the unfused path is the parity oracle")

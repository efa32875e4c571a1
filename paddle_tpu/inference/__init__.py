"""Inference predictor: AOT-compiled deploy path.

TPU-native equivalent of the reference's AnalysisPredictor pipeline
(reference: paddle/fluid/inference/api/analysis_predictor.h:86 —
Config → create_predictor → ZeroCopy run; analysis passes in
analysis/ir_pass_manager.cc). Here "analysis + optimization" IS XLA: the
loaded program re-compiles into one jitted executable per input-shape
signature (cached), with optional bf16 autocast and StableHLO export for
offline inspection/deployment (`Predictor.export_stablehlo`) — the
analogue of the reference's serialized optimized program."""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..framework.tensor import Tensor

__all__ = ["Config", "Predictor", "create_predictor", "PredictorPool"]


class Config:
    """reference: inference/api/paddle_analysis_config.h AnalysisConfig."""

    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._prefix = prog_file
        self._bf16 = False
        self._cache: Optional[str] = None
        self._device = None

    # API-compat switches (GPU/MKLDNN knobs map to TPU/XLA decisions)
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device = "device"

    def disable_gpu(self):
        self._device = "cpu"

    def enable_mkldnn_bfloat16(self):
        self._bf16 = True

    def enable_mkldnn(self):
        pass

    def switch_ir_optim(self, flag=True):
        pass  # XLA always optimizes

    def set_cpu_math_library_num_threads(self, n):
        pass

    def enable_memory_optim(self):
        pass

    def model_dir(self):
        return self._prefix

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def params_file(self):
        return (self._prefix or "") + ".pdiparams"


class _ZeroCopyTensor:
    """Handle API (reference: ZeroCopyTensor) — jax arrays are already
    zero-copy device buffers; copy_from_cpu is an async device_put."""

    def __init__(self, name, owner):
        self.name = name
        self._owner = owner

    def copy_from_cpu(self, arr):
        self._owner._feeds[self.name] = np.ascontiguousarray(arr)

    def copy_to_cpu(self):
        return np.asarray(self._owner._results[self.name])

    def shape(self):
        return list(np.shape(self._owner._results.get(
            self.name, self._owner._feeds.get(self.name))))


class Predictor:
    """reference: analysis_predictor.h:86. One compiled executable per
    input-shape signature, kept hot in a cache."""

    def __init__(self, config: Config):
        from ..static.io import load_inference_model
        self._config = config
        program, feed_names, fetch_names = load_inference_model(
            config._prefix)
        self._program = program
        self._feed_names = list(feed_names)
        self._fetch_names = list(fetch_names)
        self._feeds: Dict[str, np.ndarray] = {}
        self._results: Dict[str, np.ndarray] = {}
        self._exec_cache: Dict[Tuple, object] = {}
        caps = {}
        for i, t in program.captured.items():
            caps[program.capture_names[i]] = t._data
        self._captures = caps

    # -- reference API surface ----------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def get_input_handle(self, name) -> _ZeroCopyTensor:
        return _ZeroCopyTensor(name, self)

    def get_output_handle(self, name) -> _ZeroCopyTensor:
        return _ZeroCopyTensor(name, self)

    def _compiled(self, sig):
        if sig in self._exec_cache:
            return self._exec_cache[sig]
        from ..ops.pallas_kernels import pallas_selfcheck
        from ..jit import compile_cache
        compile_cache.configure()
        pallas_selfcheck(needs_prng=False)  # eval: no dropout PRNG
        prog = self._program
        bf16 = self._config._bf16
        cap_names = sorted(self._captures)

        def run(cap_arrs, feed_arrs):
            env = dict(zip(cap_names, cap_arrs))
            env.update(dict(zip(self._feed_names, feed_arrs)))
            if bf16:
                env = {k: (v.astype("bfloat16")
                           if hasattr(v, "dtype") and v.dtype == np.float32
                           else v) for k, v in env.items()}
            for op in prog.ops:
                # in_refs: ("var"|"cap", name) | ("const", value)
                # (program.py:74; captures are named params)
                args = [env[ref] if kind in ("var", "cap") else ref
                        for kind, ref in op.in_refs]
                outs = op.fn(*args, **op.attrs)
                if not isinstance(outs, tuple):
                    outs = (outs,)
                for n, o in zip(op.out_names, outs):
                    env[n] = o
            # fetch names removed by export-time cleanup passes resolve
            # through the artifact's alias table (static/io.py payload)
            from ..static.program import resolve_aliases_into_env
            resolve_aliases_into_env(env, getattr(prog, "aliases", {}))
            outs = [env[n] for n in self._fetch_names]
            if bf16:
                outs = [o.astype(np.float32)
                        if hasattr(o, "dtype") and o.dtype == "bfloat16"
                        else o for o in outs]
            return outs

        exe = jax.jit(run)
        self._exec_cache[sig] = exe
        return exe

    def run(self, inputs: Optional[Sequence] = None):
        """ZeroCopy style (no args, uses handles) or direct list of
        numpy arrays aligned with get_input_names()."""
        if inputs is not None:
            for n, a in zip(self._feed_names, inputs):
                self._feeds[n] = np.asarray(a)
        feed_arrs = [self._feeds[n] for n in self._feed_names]
        sig = tuple((n, a.shape, str(a.dtype))
                    for n, a in zip(self._feed_names, feed_arrs))
        exe = self._compiled(sig)
        cap_arrs = [self._captures[n] for n in sorted(self._captures)]
        outs = exe(cap_arrs, feed_arrs)
        self._results = dict(zip(self._fetch_names,
                                 [np.asarray(o) for o in outs]))
        return [Tensor(o, _internal=True) for o in outs]

    def _share_clone(self) -> "Predictor":
        """Pool member sharing this predictor's loaded program, captured
        weights and compiled-executable cache (all read-only at serve
        time) — only the per-call feed/result dicts are private. A pool
        of N costs one model load and one compile per signature instead
        of N of each."""
        clone = object.__new__(Predictor)
        clone._config = self._config
        clone._program = self._program
        clone._feed_names = list(self._feed_names)
        clone._fetch_names = list(self._fetch_names)
        clone._feeds = {}
        clone._results = {}
        clone._exec_cache = self._exec_cache
        clone._captures = self._captures
        return clone

    def export_stablehlo(self, example_inputs: Sequence[np.ndarray]) -> str:
        """Serialize the compiled computation as StableHLO text — the
        deployable artifact (reference analogue: the optimized
        __model__ emitted by the analysis passes)."""
        feed_arrs = [np.asarray(a) for a in example_inputs]
        cap_arrs = [self._captures[n] for n in sorted(self._captures)]
        sig = tuple((n, a.shape, str(a.dtype))
                    for n, a in zip(self._feed_names, feed_arrs))
        exe = self._compiled(sig)
        lowered = exe.lower(cap_arrs, feed_arrs)
        return lowered.as_text()


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


class PredictorPool:
    """reference: inference/api/paddle_inference_api.h PredictorPool.

    The first member loads the model; the rest are `_share_clone`s —
    weights, program and the compiled-executable cache are shared
    (read-only at serve time), feed/result state is per-member so the
    members stay independently usable from different threads."""

    def __init__(self, config: Config, size: int = 1):
        first = Predictor(config)
        self._preds = [first] + [first._share_clone()
                                 for _ in range(size - 1)]

    def retrieve(self, idx: int) -> Predictor:
        return self._preds[idx]

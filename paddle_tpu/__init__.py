"""paddle_tpu — a TPU-native deep learning framework.

Brand-new implementation (JAX/XLA/Pallas/pjit compute path) providing the
capabilities of the reference PaddlePaddle snapshot surveyed in SURVEY.md.
The top-level namespace mirrors the reference's `paddle` package so user
code ports by changing the import."""
from __future__ import annotations

import os as _os

import jax as _jax

# int64 is the reference's default index/label dtype; enable 64-bit types
# so the API surface matches (floats stay explicitly float32/bfloat16 —
# TPU-first code never emits f64 unless the user asks).
_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache, on by default. $JAX_COMPILATION_CACHE_DIR
# places it — jax reads that variable itself, so no code sets the directory
# then. Unset, the cache lives at one fixed path inside the checkout: a
# directory that moved from run to run would never hit. Set before the
# first compile of the process (compilation_cache.is_cache_used latches its
# verdict then). The thresholds are zeroed because jax skips entries that
# compiled in under a second by default, which is every CPU test program.
# The hit/miss listener and telemetry probe are installed by
# jit.compile_cache.configure() at the first compile entry point.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# dtypes
from .framework.dtype import (bool_ as bool, uint8, int8, int16, int32,  # noqa: A004
                              int64, float16, bfloat16, float32, float64,
                              complex64, complex128, DType as dtype,
                              set_default_dtype, get_default_dtype)
# places & device
from .framework.place import (CPUPlace, CUDAPinnedPlace, CUDAPlace, NPUPlace,
                              TPUPlace, XPUPlace, get_device, set_device,
                              is_compiled_with_cuda, is_compiled_with_rocm,
                              is_compiled_with_npu, is_compiled_with_xpu)
# tensor + modes
from .framework.tensor import Tensor, to_tensor
from .framework.tensor import Parameter  # noqa: F401
from .framework.selected_rows import SelectedRows  # noqa: F401
from .framework.state import no_grad, in_dygraph_mode
from .framework.random import seed, get_rng_state, set_rng_state
from .framework.flags import get_flags, set_flags
from .framework import state as _state

# the whole tensor-op surface lives at top level (reference exposes
# paddle.add, paddle.matmul, ... at package root)
from .tensor import *  # noqa: F401,F403
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import framework  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from . import hapi  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .framework.io import save, load  # noqa: F401
from . import distributed  # noqa: F401
from . import device  # noqa: F401
from . import static  # noqa: F401
from . import amp  # noqa: F401
from . import utils  # noqa: F401
from . import models  # noqa: F401
from . import autograd  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import distribution  # noqa: F401
from . import text  # noqa: F401
from . import incubate  # noqa: F401
from . import resilience  # noqa: F401
from . import observability  # noqa: F401
from . import checkpoint  # noqa: F401
from . import inference  # noqa: F401
from . import onnx  # noqa: F401
from . import quantization  # noqa: F401
from . import linalg  # noqa: F401
from . import fluid  # noqa: F401  (legacy compat namespace)
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import cost_model  # noqa: F401
from .hapi.flops import flops  # noqa: F401

__version__ = "0.1.0"


def enable_static():
    _state.STATE.static_mode = True


def disable_static():
    _state.STATE.static_mode = False


def is_grad_enabled():
    return _state.STATE.grad_enabled


def set_grad_enabled(mode):
    class _Guard:
        def __init__(self, prev):
            self._prev = prev

        def __enter__(self):
            return self

        def __exit__(self, *a):
            _state.STATE.grad_enabled = self._prev
            return False

    prev = _state.STATE.grad_enabled
    _state.STATE.grad_enabled = bool(mode)
    return _Guard(prev)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    from .framework.autograd import grad as _grad
    return _grad(outputs, inputs, grad_outputs, retain_graph, create_graph,
                 only_inputs, allow_unused, no_grad_vars)


def summary(net, input_size=None, dtypes=None, input=None):
    """Parameter-count summary (reference: hapi/model_summary.py)."""
    total = 0
    trainable = 0
    for _, p in net.named_parameters():
        n = p.size
        total += n
        if not p.stop_gradient:
            trainable += n
    print(f"Total params: {total}")
    print(f"Trainable params: {trainable}")
    print(f"Non-trainable params: {total - trainable}")
    return {"total_params": total, "trainable_params": trainable}


def batch(reader, batch_size, drop_last=False):
    """Classic reader batching (reference: python/paddle/batch.py) — turns
    a sample reader into a reader of lists of batch_size samples."""

    def batch_reader():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batch_reader


# -- remaining reference top-level surface -----------------------------------
from . import hub  # noqa: E402,F401
from .hapi import callbacks  # noqa: E402,F401

full_version = __version__
commit = "tpu-native"


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """reference: fluid/layers create_parameter — a standalone trainable
    Parameter outside any Layer."""
    import numpy as _np
    from .nn import initializer as _I
    init = default_initializer
    if init is None and attr is not None:
        init = getattr(attr, "initializer", None)
    if init is None:
        init = _I.Constant(0.0) if is_bias else _I.XavierNormal()
    dt = getattr(dtype, "name", dtype)  # paddle DType or str
    arr = init(tuple(int(s) for s in shape), _np.dtype(str(dt)))
    p = Parameter(arr, name=name or getattr(attr, "name", None))
    if attr is not None and getattr(attr, "trainable", True) is False:
        p.stop_gradient = True
        p.trainable = False
    return p


def enable_dygraph(place=None):
    _state.STATE.static_mode = False


def disable_dygraph():
    _state.STATE.static_mode = True


def in_dynamic_mode():
    return not _state.in_static_mode()


def get_cuda_rng_state():
    """CUDA-compat alias: there is no CUDA here; returns the global TPU/CPU
    PRNG state so checkpoint code keeps working."""
    return get_rng_state()


def set_cuda_rng_state(state_list):
    return set_rng_state(state_list)


def get_cudnn_version():
    return None  # not compiled with cuDNN (TPU build)


def disable_signal_handler():
    pass  # jax installs no paddle-style signal handlers


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def monkey_patch_math_varbase():
    pass  # Tensor dunders are installed at import (tensor/__init__.py)


def monkey_patch_variable():
    pass  # Variable inherits the full Tensor surface


def check_shape(shape):
    for s in shape:
        if s is not None and int(s) < -1:
            raise ValueError(f"illegal dimension {s} in shape {shape}")

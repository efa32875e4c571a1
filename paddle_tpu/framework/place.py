"""Device/place abstraction.

TPU-native equivalent of the reference's Place variant
(/root/reference/paddle/fluid/platform/place.h:26-86) and the device API
(/root/reference/python/paddle/device/__init__.py:41-209). Places map onto JAX
devices; there are no streams/device-contexts to manage — XLA owns scheduling.
"""
from __future__ import annotations

import functools


class Place:
    """Base class of device identities."""

    _kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self._kind}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self._kind == other._kind
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def jax_device(self):
        # ADDRESSABLE devices only: a Place names a process-local device
        # (reference: per-trainer FLAGS_selected_gpus). Under multi-process
        # (jax.distributed), jax.devices() lists the whole cluster and its
        # first entry may belong to another process — committing host data
        # there is impossible.
        import jax
        devs = [d for d in jax.local_devices()
                if _platform_of(d) == self._kind]
        if not devs:
            # fall back to the host CPU (CPUPlace on an accelerator
            # backend must stay host-pinned, e.g. tensor.cpu()); the cpu
            # platform is not in local_devices() when tpu is default
            try:
                me = jax.process_index()
                devs = [d for d in jax.devices("cpu")
                        if d.process_index == me]
            except RuntimeError:
                devs = []
        if not devs:
            devs = jax.local_devices()
        return devs[self.device_id % len(devs)]


def _platform_of(dev) -> str:
    return "tpu" if "tpu" in dev.platform else dev.platform


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    _kind = "tpu"


# The reference is CUDA-first; we accept its spelling and map it to the
# accelerator place so reference-written scripts keep running.
class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class NPUPlace(TPUPlace):
    pass


@functools.lru_cache(maxsize=None)
def _accelerator_available() -> bool:
    # a backend that fails to initialise raises here: that is an error to
    # surface, not a reason to answer CPUPlace
    import jax
    return any(_platform_of(d) == "tpu" for d in jax.devices())


_current_place = None


def _default_place() -> Place:
    return TPUPlace(0) if _accelerator_available() else CPUPlace(0)


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def set_device(device) -> Place:
    """paddle.device.set_device parity: 'tpu', 'tpu:1', 'cpu', 'gpu:0'→tpu."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name in ("cpu",):
        _current_place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        _current_place = TPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = get_place()
    return f"{p._kind}:{p.device_id}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True

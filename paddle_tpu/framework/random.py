"""Global RNG.

TPU-native equivalent of the reference's per-device Generator
(/root/reference/paddle/fluid/framework/generator.h, python `paddle.seed` in
python/paddle/framework/random.py). Randomness is functional (jax PRNG keys):
a process-global key splits once per random op. Under a trace (to_static /
compiled train step), the key is swapped for a traced input by the tracing
wrapper so every execution of the compiled program draws fresh randomness —
the TPU replacement for the reference's stateful curand generators.
"""
from __future__ import annotations

import jax
import numpy as np


class GlobalRNG:
    """Lazily materializes the root PRNG key: building a PRNGKey touches the
    jax backend, and `import paddle_tpu` must never initialize one (the
    launcher parent imports the package and must leave the chip to its
    worker; tests pin the platform after the import)."""

    def __init__(self, seed: int = 0):
        self._seed = seed
        self._key = None

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._key = jax.random.PRNGKey(self._seed)

    def next_key(self):
        key = self.key
        # A GSPMD-compiled train step returns the advanced key committed to
        # its mesh (replicated over all devices). Later EAGER ops mixing
        # that multi-device key with single-device arrays fail jit's
        # committed-device check — normalize to the default device outside
        # traces (8-byte transfer; the compiled step path is untouched:
        # there the key is a tracer).
        if not isinstance(key, jax.core.Tracer):
            devs = getattr(key, "devices", None)
            if devs is not None and len(devs()) > 1:
                key = jax.device_put(key, jax.devices()[0])
        self.key, sub = jax.random.split(key)
        return sub

    def state(self):
        return self.key

    def set_state(self, key):
        self.key = key


RNG = GlobalRNG(0)


def seed(s: int):
    """paddle.seed parity."""
    RNG.manual_seed(int(s))
    np.random.seed(int(s) % (2**32))
    return RNG


def get_rng_state():
    return RNG.state()


def set_rng_state(state):
    RNG.set_state(state)

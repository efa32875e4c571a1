"""Persistent XLA compilation cache: hit/miss accounting.

A fresh process used to pay the full trace+XLA-compile tax for executables
byte-identical to what the previous one already built. jax ships a
persistent compilation cache (keyed on serialized HLO + compile options +
jaxlib version) that turns that tax into a disk read. It is on by default:

  JAX_COMPILATION_CACHE_DIR=/path   set: jax reads it, no code sets a dir
  (unset)                           <checkout>/.jax_cache, a fixed path

`paddle_tpu/__init__` applies that rule and zeroes jax's persistence
thresholds before the first compile (``compilation_cache.is_cache_used``
latches its verdict then). JAX_ENABLE_COMPILATION_CACHE=false is jax's own
off switch.

Accounting: jax emits monitoring events on every cache probe; we fold
``/jax/compilation_cache/cache_hits|cache_misses`` into the metrics
registry (``pt_compile_cache_hits_total`` / ``_misses_total``) and push
a snapshot probe into observability.tracing so StepTelemetry can tell a
*true* retrace (XLA actually compiled) from a warm-cache reload — see
tracing.set_compile_cache_probe. tracing stays stdlib-pure; this module
owns the jax side of the handshake.
"""
from __future__ import annotations

import logging
import threading
from typing import Optional, Tuple

__all__ = ["configure", "enabled", "cache_dir", "totals"]

log = logging.getLogger("paddle_tpu.compile_cache")

_lock = threading.Lock()
_listener_installed = False
_hits = 0
_misses = 0

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def totals() -> Tuple[int, int]:
    """(hits, misses) persistent-cache probes seen by this process."""
    return _hits, _misses


def enabled() -> bool:
    import jax
    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)


def cache_dir() -> Optional[str]:
    import jax
    return jax.config.jax_compilation_cache_dir


def _on_event(event: str, **kw):
    global _hits, _misses
    if event == _HIT_EVENT:
        _hits += 1
        _metric_hits.inc()
    elif event == _MISS_EVENT:
        _misses += 1
        _metric_misses.inc()


def configure() -> bool:
    """Install the hit/miss listener and the tracing probe (once; cheap
    afterwards). Returns True when the cache is live. Called from every
    compile entry point (jit engine, static Executor, inference Predictor,
    serving engine) so the accounting is in place whichever front-end
    compiles first. The directory itself is set at package import."""
    global _listener_installed
    with _lock:
        if not _listener_installed:
            from jax._src import monitoring

            from ..observability import tracing
            monitoring.register_event_listener(_on_event)
            tracing.set_compile_cache_probe(totals)
            _listener_installed = True
            log.info("persistent compilation cache at %s", cache_dir())
    return enabled()


def _counter(name, help_):
    from ..observability import metrics
    return metrics.counter(name, help_)


_metric_hits = _counter(
    "pt_compile_cache_hits_total",
    "Persistent compilation cache hits (executables reloaded from disk)")
_metric_misses = _counter(
    "pt_compile_cache_misses_total",
    "Persistent compilation cache misses (XLA compiled from scratch)")

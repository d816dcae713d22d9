"""Platform/feature probing and backend dispatch — the src/arch/ analog.

The reference probes CPU features once at startup (arch/probe.cc sets
ceph_arch_intel_sse42 etc.) and SIMD code paths branch on the flags
(e.g. crc32c picks the SSE4 implementation).  The TPU-native analog
probes the accelerator and host capabilities once, and the compute
backends consult the flags instead of re-deriving them:

- ``platform``/``device_kind``/``n_devices``: what jax will run on.
- ``x64``: whether 64-bit integer lanes work (the exact straw2 kernel
  needs s64 draws; the CPU backend always has it, TPU does too but the
  probe proves it).
- ``pallas``: whether Pallas TPU kernels can compile here.
- ``native``: the C++ helper library (crush evaluator + GF region
  coder, native/*.cpp) is built and loadable.

Probing jax initializes the backend, so everything is lazy and cached,
and `probe()` never raises (absent features read False).

``configure_compile_cache()`` is the one place that decides where JAX's
persistent compilation cache lives (bench.py, chip_smoke.py and
tests/conftest.py all call it).

CLI: ``python -m ceph_tpu.arch`` prints the probe as one JSON line
(the "ceph features"-style introspection surface).
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

_cache: Dict[str, Any] = {}

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its dir.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the deployment's choice:
    JAX already reads it, so it is left alone.  Otherwise the cache goes
    to the fixed ``<checkout>/.jax_cache`` — the path is part of what a
    later run must find again, so it never moves."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def probe(refresh: bool = False) -> Dict[str, Any]:
    global _cache
    if _cache and not refresh:
        return _cache
    out: Dict[str, Any] = {
        "platform": "none", "device_kind": "", "n_devices": 0,
        "x64": False, "pallas": False, "native": False,
    }
    try:
        from .native import native_available
        out["native"] = bool(native_available())
    except Exception:
        pass
    try:
        import jax
        devs = jax.devices()
        out["platform"] = devs[0].platform
        out["device_kind"] = getattr(devs[0], "device_kind", "")
        out["n_devices"] = len(devs)
    except Exception:
        _cache = out
        return out
    try:
        import jax.numpy as jnp
        import numpy as np
        with jax.enable_x64(True):
            # one-shot capability probe, memoized in _cache
            # lint: allow[jit-cache-hygiene]
            v = jax.jit(lambda a: a * a)(
                jnp.asarray(np.int64(3_000_000_019)))
            out["x64"] = int(v) == 3_000_000_019 ** 2
    except Exception:
        out["x64"] = False
    out["pallas"] = _probe_pallas(out["platform"])
    _cache = out
    return out


def _probe_pallas(platform: str) -> bool:
    """Pallas compiles only on real TPU (the interpreter path on CPU is
    not a production backend)."""
    if platform != "tpu":
        return False
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except Exception:
        return False


def have(feature: str) -> bool:
    return bool(probe().get(feature))


if __name__ == "__main__":
    print(json.dumps(probe()))

"""Where Pallas kernels run, how much VMEM they may use, and where compiled
programs are cached.

The interpret choice follows the platform and has no switch: kernels run
in Pallas interpret mode on the CPU backend (tests, ``JAX_PLATFORMS=cpu``)
and compiled through Mosaic on a TPU. Kernels take ``interpret:
Optional[bool] = None`` and resolve ``None`` here. An interpreted run never
touches a TPU: :func:`interpret_device` places it on the host CPU.

The scoped-VMEM limit handed to Mosaic comes from :data:`VMEM_LIMIT_BYTES`,
keyed by ``device_kind``; a TPU kind the table does not name is an error,
not a default.
"""
from __future__ import annotations

import os
from typing import Optional

#: Scoped-VMEM limit per TPU ``device_kind`` (bytes): what one kernel may
#: allocate, passed to Mosaic as ``vmem_limit_bytes`` and used by the
#: executor's residency gates. TPU v5e: 128 MiB of VMEM per core; 96 MiB
#: leaves the rest to Mosaic's internal scratch.
VMEM_LIMIT_BYTES = {
    "TPU v5 lite": 96 * 2**20,
}

#: The chip interpret mode stands in for: CPU runs gate on its budget.
INTERPRET_TARGET = "TPU v5 lite"


def default_interpret() -> bool:
    """Interpret mode unless JAX's default backend is a TPU."""
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Per-call override (explicit bool) or the platform rule (None)."""
    return default_interpret() if interpret is None else bool(interpret)


def interpret_device():
    """The device interpreted kernels run on: the host CPU, so that an
    interpret-mode reference never executes on a TPU."""
    import jax
    return jax.devices("cpu")[0]


def vmem_limit(device_kind: Optional[str] = None) -> int:
    """Scoped-VMEM bytes for ``device_kind`` (default: JAX's first device;
    on the CPU backend, the chip interpret mode stands in for)."""
    if device_kind is None:
        import jax
        dev = jax.devices()[0]
        device_kind = (dev.device_kind if dev.platform == "tpu"
                       else INTERPRET_TARGET)
    try:
        return VMEM_LIMIT_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM limit known for device kind {device_kind!r} "
            f"(known: {sorted(VMEM_LIMIT_BYTES)})") from None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and
    nothing else is set; otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` and small kernel programs are cached too.
    Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

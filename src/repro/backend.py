"""Backend selection for the WLSH operator stack.

Three backends implement the same operator contract (see core/operator.py):

* ``reference`` — pure jnp (core/lsh.py + core/wlsh.py).  Always available,
  always correct; the oracle every other backend is tested against.
* ``pallas``    — the fused TPU kernels (kernels/featurize + kernels/binning).
  Compiled on a TPU; on the CPU they run in the Pallas interpreter (Python
  emulation — correctness only, not speed).
* ``auto``      — platform-based choice: ``pallas`` when the program is
  placed on a TPU, ``reference`` otherwise.  This is the default everywhere
  so that laptops/CI get the fast jnp path and pods get the fused kernels
  without any config change.

Both choices follow the platform of the devices a program is placed on —
a mesh's devices, or the device of an argument (``platform_of``) — never a
process-wide default, so a program compiled for TPU devices from a CPU host
gets compiled kernels.  Interpret mode on a TPU is refused.

The environment variable ``REPRO_WLSH_BACKEND`` overrides ``auto`` (useful for
forcing the kernel path through CI parity runs).
"""
from __future__ import annotations

import os

import jax

BACKENDS = ("reference", "pallas", "auto")

ENV_VAR = "REPRO_WLSH_BACKEND"


def platform_of(where=None) -> str:
    """Platform of the devices a computation is placed on.

    ``where`` is a Mesh or a committed array; anything else (numpy data, a
    tracer, None) runs on the default device, ``jax.devices()[0]``."""
    if isinstance(where, jax.sharding.Mesh):
        return where.devices.flat[0].platform
    if isinstance(where, jax.Array) and not isinstance(where, jax.core.Tracer):
        return next(iter(where.devices())).platform
    return jax.devices()[0].platform


def resolve_interpret(interpret: bool | None, platform: str) -> bool:
    """Pallas interpret mode for a program on ``platform``: ``None`` means
    interpreted exactly off the TPU.  Asking for the interpreter on a TPU
    raises — it would hide the device behind a Python emulation."""
    if interpret is None:
        return platform != "tpu"
    if interpret and platform == "tpu":
        raise ValueError("Pallas interpret mode requested for a program on "
                         "a TPU; the interpreter is the CPU test path only")
    return bool(interpret)


def resolve_backend(name: str | None = None,
                    platform: str | None = None) -> str:
    """Resolve a backend name to a concrete one ('reference' or 'pallas').

    ``None`` and ``'auto'`` pick by ``platform`` (default: the default
    device's; TPU -> pallas, else reference), unless ``REPRO_WLSH_BACKEND``
    forces a concrete choice.
    """
    if name is None:
        name = "auto"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    if name == "auto":
        env = os.environ.get(ENV_VAR, "").strip().lower()
        if env:
            if env not in BACKENDS or env == "auto":
                raise ValueError(
                    f"{ENV_VAR}={env!r} must be 'reference' or 'pallas'")
            return env
        platform = platform_of() if platform is None else platform
        return "pallas" if platform == "tpu" else "reference"
    return name

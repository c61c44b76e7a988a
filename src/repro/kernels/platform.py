"""The platform chooses how the Pallas kernels and the stage-1 index run.

* Pallas kernels: compiled (Mosaic) on TPU, interpreted on CPU. Every
  kernel wrapper takes ``interpret=None`` and resolves it here, so no
  caller passes a mode. An explicit bool still wins — the chip-compile
  tests lower with ``interpret=False`` on a CPU host.
* Index backend: the Pallas ``"kernel"`` scan on TPU, the numpy scan on
  CPU. The numpy path is the reference the tests compare the kernels
  with; on TPU a stage-1 lookup that ends in a numpy scan is an error.

Any other platform raises: there is no lowering for it, and a silent
fallback would hide that the device path never ran.
"""
from __future__ import annotations

from typing import Optional

import jax


def _platform() -> str:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"unsupported platform {backend!r}: the Pallas kernels compile "
            "for TPU and run interpreted on CPU only")
    return backend


def on_tpu() -> bool:
    return _platform() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True on CPU and False on TPU."""
    if interpret is not None:
        return interpret
    return not on_tpu()


def resolve_backend(backend: Optional[str] = None) -> str:
    """Stage-1 index backend: ``backend`` if given, else ``"kernel"`` on
    TPU and ``"numpy"`` on CPU."""
    if backend is not None:
        if backend not in ("kernel", "numpy"):
            raise ValueError(f"unknown index backend {backend!r}")
        return backend
    return "kernel" if on_tpu() else "numpy"

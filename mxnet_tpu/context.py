"""Device context.

Parity with the reference's ``python/mxnet/context.py`` and the C++
``Context`` struct (include/mxnet/base.h).  On the TPU build, ``tpu(i)``
maps to the i-th JAX accelerator device; ``cpu(i)`` maps to a host
device.  ``gpu(i)`` is accepted as an alias for ``tpu(i)`` so that
reference user scripts run unchanged.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_devices"]


class Context:
    """Device context: (device_type, device_id).

    Usable as a ``with`` scope exactly like the reference
    (python/mxnet/context.py:12-87).
    """

    # matches reference devtype2str {1:'cpu', 2:'gpu', 3:'cpu_pinned'} with tpu added
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None
        self._jax_device = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default, "ctx", None)
        Context._default.ctx = self
        return self

    def __exit__(self, *args):
        Context._default.ctx = self._old_ctx
        return False

    # ------------------------------------------------------------------
    # JAX device resolution
    # ------------------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        gpu/tpu both resolve to the accelerator backend (alias so
        reference scripts with ``mx.gpu()`` work).  An accelerator
        ordinal this process does not have is an error — never a CPU
        device, never another chip: a run that asked for the chip and
        got something else would report its numbers under the chip's
        name.  cpu ids are logical, as in the reference: they wrap
        over the host devices.
        """
        if self._jax_device is not None:
            return self._jax_device
        if self.device_type in ("cpu", "cpu_pinned"):
            # this process's devices: in a multi-process runtime the
            # global list contains peers' unaddressable devices
            devs = _local_cpu_devices()
            self._jax_device = devs[self.device_id % len(devs)]
        else:
            devs = _accelerator_devices()
            if not 0 <= self.device_id < len(devs):
                from .base import MXNetError

                raise MXNetError(
                    f"{self}: this process has {len(devs)} accelerator "
                    f"device(s) (jax default backend "
                    f"{jax.default_backend()!r}); ask for mx.cpu() to "
                    f"run on the host")
            self._jax_device = devs[self.device_id]
        return self._jax_device


def _local_cpu_devices():
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:  # no cpu backend registered (rare)
        devs = [d for d in jax.local_devices() if d.platform == "cpu"]
        return devs or jax.devices("cpu")


def _accelerator_devices(local_only: bool = True):
    devs = jax.local_devices() if local_only else jax.devices()
    return [d for d in devs if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the accelerator device (TPU on this build)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_devices(device_type: str = "tpu") -> int:
    """Per-process (addressable) device count; 0 accelerators when the
    process has none."""
    if device_type in ("cpu", "cpu_pinned"):
        return len(_local_cpu_devices())
    return len(_accelerator_devices())


def current_context() -> Context:
    """The ambient default context (reference: context.py:81-87)."""
    ctx = getattr(Context._default, "ctx", None)
    if ctx is None:
        ctx = Context("cpu", 0)
    return ctx

"""Device placement.

Parity target: paddle's Place hierarchy (reference: paddle/phi/common/place.h:58)
mapped onto JAX/PJRT devices. A ``Place`` names a logical device; the actual
jax.Device is resolved lazily so the module can be imported before the backend
is initialized (and so tests can force the CPU platform first).
"""
from __future__ import annotations

import jax


class Place:
    """Base class for device places."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        if isinstance(other, str):
            try:
                return self == _parse_place(other)
            except ValueError:
                return False
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        devices = [d for d in jax.devices() if d.platform == self.device_type]
        if self.device_id >= len(devices):
            raise RuntimeError(
                f"no {self.device_type}:{self.device_id} device — jax found "
                f"{[str(d) for d in jax.devices()]}")
        return devices[self.device_id]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    """The accelerator place. Named XPUPlace-style `tpu:<i>`."""

    device_type = "tpu"


class CustomPlace(Place):
    def __init__(self, dev_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = dev_type


def _parse_place(spec: str) -> Place:
    spec = spec.lower()
    if ":" in spec:
        kind, _, idx = spec.partition(":")
        idx = int(idx)
    else:
        kind, idx = spec, 0
    if kind in ("cpu",):
        return CPUPlace(idx)
    if kind in ("tpu", "gpu", "xpu", "npu", "accelerator"):  # accelerator aliases
        return TPUPlace(idx)
    return CustomPlace(kind, idx)


_current_place: Place | None = None


def set_device(device) -> Place:
    """paddle.set_device parity (reference: python/paddle/device/__init__.py)."""
    global _current_place
    _current_place = device if isinstance(device, Place) else _parse_place(str(device))
    return _current_place


def get_device() -> str:
    place = _expected_place()
    return f"{place.device_type}:{place.device_id}"


def _expected_place() -> Place:
    global _current_place
    if _current_place is None:
        default = jax.devices()[0]
        _current_place = (CPUPlace(0) if default.platform == "cpu"
                          else TPUPlace(default.id))
    return _current_place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()

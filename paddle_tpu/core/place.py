"""Device places.

TPU-native analog of the reference Place variant
(/root/reference/paddle/fluid/platform/place.h:104 —
 boost::variant<CUDAPlace, XPUPlace, CPUPlace, CUDAPinnedPlace>).

Here the device set is {CPUPlace, XLAPlace(device_id)}; XLAPlace is the
first-class TPU place of the north star.  Instead of a DeviceContext pool with
per-device streams (device_context.h:262 DeviceContextPool), each place simply
resolves to a `jax.Device`; scheduling/streams belong to XLA.
"""
from __future__ import annotations

__all__ = [
    "Place", "CPUPlace", "XLAPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "get_device", "set_device", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_tpu", "device_count", "_current_expected_place",
]


class Place:
    """Base class of all places."""

    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"

    def jax_device(self):
        import jax
        return jax.devices("cpu")[0]


class XLAPlace(Place):
    """The TPU (or any XLA accelerator) place; `device_id` is the local
    ordinal, mirroring CUDAPlace(device_id) in the reference."""

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"XLAPlace({self.device_id})"

    def jax_device(self):
        import jax
        devs = jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r}: this process has {len(devs)} "
                f"{devs[0].platform} device(s)")
        return devs[self.device_id]


# TPUPlace is the user-facing alias; CUDAPlace is accepted for API parity with
# reference scripts and maps onto the accelerator place.
TPUPlace = XLAPlace


class CUDAPlace(XLAPlace):
    def __repr__(self):
        return f"CUDAPlace({self.device_id}) [-> XLAPlace]"


class CUDAPinnedPlace(CPUPlace):
    def __repr__(self):
        return "CUDAPinnedPlace [-> CPUPlace]"


def _accelerator_platform() -> str | None:
    import jax
    plat = jax.default_backend()
    return None if plat == "cpu" else plat


_expected_place = None


def _current_expected_place() -> Place:
    global _expected_place
    if _expected_place is None:
        _expected_place = XLAPlace(0) if _accelerator_platform() else CPUPlace()
    return _expected_place


def set_device(device: str) -> Place:
    """paddle.set_device analog: 'cpu', 'tpu', 'tpu:0', 'gpu:0' (alias)."""
    global _expected_place
    name = device.lower()
    if name == "cpu":
        _expected_place = CPUPlace()
    else:
        idx = 0
        if ":" in name:
            name, idx = name.split(":")
            idx = int(idx)
        _expected_place = XLAPlace(idx)
    return _expected_place


def get_device() -> str:
    p = _current_expected_place()
    if isinstance(p, XLAPlace):
        return f"tpu:{p.device_id}"
    return "cpu"


def device_count() -> int:
    import jax
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return _accelerator_platform() is not None

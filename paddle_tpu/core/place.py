"""Device placement.

The reference has a C++ `Place` class hierarchy (CPUPlace/CUDAPlace/... —
`paddle/fluid/platform/place.h`) plus a DeviceContext pool. On TPU the runtime is PJRT behind JAX:
a Place wraps a `jax.Device`, and "the device context" is XLA's per-device stream — there is
nothing to pool manually. We keep the Place API surface (construction, equality, guard) because
user code and tests use it.
"""
from __future__ import annotations

import threading

_state = threading.local()


class Place:
    device_type: str = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
            and getattr(self, "custom_device_type", None)
            == getattr(other, "custom_device_type", None)
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id,
                     getattr(self, "custom_device_type", None)))

    def __repr__(self):
        custom = getattr(self, "custom_device_type", None)
        kind = f"{self.device_type}/{custom}" if custom else self.device_type
        return f"Place({kind}:{self.device_id})"

    def jax_device(self):
        """The jax.Device this place names. A place whose platform this
        process does not have is an error, never another platform's device:
        a TPU place on a CPU-only host would otherwise compute on the CPU
        and say nothing."""
        import jax

        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"{self!r} needs a {self.device_type!r} device, but jax "
                f"found only {sorted({d.platform for d in jax.devices()})}")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):  # accepted for API parity; maps onto the accelerator
    device_type = "tpu"


class CUDAPinnedPlace(CPUPlace):
    pass


# Vendor places accepted for API parity; this framework targets TPU, so
# accelerator-flavored places map onto the accelerator and the rest onto host.
class NPUPlace(Place):
    device_type = "tpu"


class XPUPlace(Place):
    device_type = "tpu"


class MLUPlace(Place):
    device_type = "tpu"


class IPUPlace(Place):
    device_type = "tpu"


class NPUPinnedPlace(CPUPlace):
    pass


class CustomPlace(Place):
    device_type = "tpu"

    def __init__(self, device_type="custom", device_id=0):
        super().__init__(device_id)
        self.custom_device_type = device_type

    def jax_device(self):
        # registered custom devices resolve to their PJRT platform
        # (paddle_tpu.device.register_custom_device); unregistered ones fall
        # back to the accelerator like the base class
        from ..device import get_registered_custom_device

        plat = get_registered_custom_device(self.custom_device_type)
        if plat is not None:
            import jax

            devs = [d for d in jax.devices() if d.platform == plat]
            if devs:
                return devs[self.device_id % len(devs)]
        return super().jax_device()


def _default_place() -> Place:
    import jax

    plat = jax.default_backend()
    if plat == "cpu":
        return CPUPlace(0)
    if plat == "tpu":
        return TPUPlace(0)
    raise RuntimeError(
        f"jax default backend is {plat!r}; paddle_tpu runs on 'tpu' or 'cpu'")


def set_device(device) -> Place:
    """set_device("tpu"), set_device("tpu:1"), set_device("cpu"), or a Place."""
    if isinstance(device, Place):
        place = device
    else:
        s = str(device).lower()
        if ":" in s:
            kind, _, idx = s.partition(":")
        else:
            kind, idx = s, "0"
        if kind in ("cpu",):
            place = CPUPlace(int(idx))
        elif kind in ("tpu", "gpu", "cuda", "xpu", "npu"):
            place = TPUPlace(int(idx))
        else:
            raise ValueError(f"unknown device {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.device_id}"


def get_place() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        p = _default_place()
        _state.place = p
    return p


def is_compiled_with_cuda() -> bool:  # API parity; TPU build has no CUDA
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True


def is_compiled_with_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def device_count() -> int:
    import jax

    return jax.device_count()

"""Places — device tags.

Analog of platform::Place (ref: paddle/fluid/platform/place.h:26,37,52:
CPUPlace/CUDAPlace/CUDAPinnedPlace). The TPU-native build replaces
CUDAPlace with TPUPlace; DeviceContext/stream management collapses into
XLA's runtime (there is no per-op stream bookkeeping when the whole step is
one compiled computation), so a Place here simply names a `jax.Device`.
"""

import functools

import jax

from paddle_tpu.core.enforce import EnforceNotMet


class Place:
    """Base device tag; wraps a jax.Device."""

    device_kind = None

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        """The jax.Device this place names. A place that names a device
        the process does not have is an error: TPUPlace(0) on a host
        with no chip must not quietly become a CPU device."""
        if self.device_kind == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices() if _matches(d, self)]
        if self.device_id >= len(devs):
            raise EnforceNotMet(
                f"{self!r} names a device this process does not have: "
                f"{len(devs)} {self.device_kind} device(s) visible "
                f"(default platform {jax.devices()[0].platform!r})")
        return devs[self.device_id]


class CPUPlace(Place):
    device_kind = "cpu"


class TPUPlace(Place):
    device_kind = "tpu"


class CUDAPinnedPlace(CPUPlace):  # compat alias: pinned host staging
    pass


def _matches(dev, place):
    plat = dev.platform.lower()
    if place.device_kind == "cpu":
        return plat == "cpu"
    return plat != "cpu"  # any accelerator counts as the TPU place


def is_compiled_with_tpu():
    return any(d.platform.lower() != "cpu" for d in jax.devices())


# fluid compat: code written against the reference checks for CUDA
def is_compiled_with_cuda():
    return False


def default_place():
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace(0)


def device_count():
    return len(jax.devices())


_current = {"device": None}


def set_device(device):
    """'tpu', 'cpu', 'tpu:0' — analog of paddle.set_device."""
    name, _, idx = device.partition(":")
    place = CPUPlace(int(idx or 0)) if name == "cpu" else TPUPlace(int(idx or 0))
    _current["device"] = place
    return place


def get_device():
    return _current["device"] or default_place()


@functools.lru_cache(maxsize=None)
def local_device_count():
    return jax.local_device_count()


def cpu_places(device_count=None):
    """fluid.cpu_places parity (the get_places op's python surface,
    ref operators/controlflow/get_places_op.cc): one CPUPlace per
    requested device (default: all visible)."""
    n = device_count or max(
        len([d for d in jax.devices() if d.platform == "cpu"]), 1)
    return [CPUPlace(i) for i in range(n)]


def tpu_places(device_ids=None):
    """TPU analog of fluid.cuda_places: one TPUPlace per chip."""
    if device_ids is None:
        device_ids = [d.id for d in jax.devices()
                      if d.platform != "cpu"] or [0]
    return [TPUPlace(i) for i in device_ids]


# fluid.cuda_places compat: on this framework the accelerator is a TPU
cuda_places = tpu_places
CUDAPlace = TPUPlace        # fluid.CUDAPlace scripts get the accelerator


def cuda_pinned_places(device_count=None):
    """fluid.cuda_pinned_places parity: pinned host staging places
    (host memory is the staging tier on TPU, CUDAPinnedPlace analog)."""
    n = device_count or max(len(jax.devices()), 1)
    return [CUDAPinnedPlace(i) for i in range(n)]

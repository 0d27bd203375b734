"""Device placement taxonomy.

Mirrors the capability of the reference's Place variant (platform/place.h:26-81
in the reference repo): CPUPlace / CUDAPlace / CUDAPinnedPlace. Here the
accelerator is TPU and the actual placement is delegated to JAX/XLA (PJRT);
a Place mostly selects which jax device a program executes on, and -- for
multi-chip -- which mesh.
"""

from __future__ import annotations

import functools


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            type(self) is type(other) and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        """The jax device this place names. Raises UnavailableError when
        the process has no device of the place's kind — a TPUPlace never
        resolves to a CPU device (tests and CPU runs get their place from
        default_place(), which picks CPUPlace there)."""
        import jax

        devs = [d for d in jax.devices() if self._match(d)]
        if not devs:
            from ..errors import UnavailableError

            raise UnavailableError(
                f"{self!r}: this process has no {self.device_type} device "
                f"(jax.devices() = {jax.devices()})"
            )
        return devs[self.device_id % len(devs)]

    def _match(self, dev) -> bool:
        return True


class CPUPlace(Place):
    device_type = "cpu"

    def _match(self, dev):
        return dev.platform == "cpu"


class TPUPlace(Place):
    device_type = "tpu"

    def _match(self, dev):
        return dev.platform != "cpu"


# Alias kept so fluid-style code written against the reference's CUDAPlace
# (platform/place.h:37) ports by search/replace; on this framework the
# accelerator is always the TPU.
CUDAPlace = TPUPlace


@functools.lru_cache(maxsize=None)
def _has_accelerator() -> bool:
    import jax

    return any(d.platform != "cpu" for d in jax.devices())


def is_compiled_with_tpu() -> bool:
    return _has_accelerator()


def default_place() -> Place:
    return TPUPlace(0) if _has_accelerator() else CPUPlace(0)


def tpu_places(device_ids=None):
    import jax

    devs = [d for d in jax.devices() if d.platform != "cpu"]
    ids = range(len(devs)) if device_ids is None else device_ids
    return [TPUPlace(i) for i in ids]


def cpu_places(device_count=1):
    return [CPUPlace(i) for i in range(device_count)]

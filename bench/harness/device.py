"""The device check: a run measures an accelerator or prints nothing."""
from __future__ import annotations


class NoAccelerator(SystemExit):
    """Raised (as a non-zero exit) when JAX finds no accelerator, or fewer
    chips than the cell asks for."""


def require_accelerator(chips: int, devices=None):
    """The ``chips`` devices the cell runs on. Refuses a CPU backend and too
    few chips with a non-zero exit that names what JAX found; there is no
    fallback."""
    if devices is None:
        import jax
        devices = jax.devices()
    platform = devices[0].platform if devices else "none"
    if platform == "cpu" or not devices:
        raise NoAccelerator(
            f"bench: needs an accelerator, but JAX's devices are on platform "
            f"{platform!r}; no result is printed")
    if len(devices) < chips:
        raise NoAccelerator(
            f"bench: the cell asks for {chips} chips, JAX finds "
            f"{len(devices)} on platform {platform!r}; no result is printed")
    return list(devices[:chips])


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)

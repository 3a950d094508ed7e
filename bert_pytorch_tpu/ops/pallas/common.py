"""Shared helpers for Pallas kernels."""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """False on a TPU, where the kernels are compiled (Mosaic). True on the
    CPU backend, where the same code paths run in the Pallas interpreter so
    the tests can reach them on the virtual CPU mesh (SURVEY.md §4's Gloo
    analog). Any other backend is refused: there the kernels could neither
    compile nor be meant to interpret, and carrying on would hide that."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; the default "
        f"backend is {backend!r}")


def device_report() -> dict:
    """What this process runs on, as the runners log it at start-up and
    stamp it into their records: ``kernels`` says whether the Pallas
    kernels are compiled or interpreted here."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "kernels": "interpreted" if interpret_mode() else "compiled",
    }


def pick_block(size: int, candidates=(512, 256, 128, 64, 32, 16, 8)) -> int:
    """Largest hardware-friendly block that divides ``size``."""
    for c in candidates:
        if size % c == 0 and c <= size:
            return c
    return size

"""The device this process runs on: what JAX reports, the published peaks of
the chips the repo knows, and the start-up check that keeps a serving entry
point from silently landing on the CPU.

A host where libtpu fails to initialise still gives JAX a CPU backend; the
Pallas entry points would then pick interpret mode and ``/healthz`` would
stay green.  ``require_tpu`` turns that into a start-up error unless the
operator asked for the CPU by name (``--platform cpu``).
"""

from __future__ import annotations

# Per-chip peaks keyed by the ``device_kind`` jax reports: (bf16 dense
# FLOP/s, HBM bytes/s).  Source: Google Cloud TPU documentation, the
# "TPU v5e" / "TPU v4" / "TPU v5p" / "TPU v6e" system-architecture pages.
# A v5e chip reports "TPU v5 lite", a v6e "TPU v6 lite".
CHIP_PEAKS: dict[str, tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
}


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` as JAX reports them (initialises
    the backend: call only where the process is meant to own the chip)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory() -> list[dict]:
    """What every local device holds, from ``memory_stats()``: bytes in use,
    their peak since the process began, and the limit.  A backend that keeps
    no such count (the CPU) gives ``None`` for each."""
    import jax

    rows = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        rows.append({"id": d.id, **{key: stats.get(key) for key in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}})
    return rows


def require_tpu(platform: str | None, what: str) -> dict:
    """Start-up gate for entry points that serve: returns
    :func:`device_info`, or raises ``SystemExit`` naming ``--platform cpu``
    when the backend JAX selected is not a TPU and the CPU was not asked for.
    """
    info = device_info()
    if platform is None and info["platform"] != "tpu":
        raise SystemExit(
            f"{what} needs a TPU: JAX selected the {info['platform']!r} "
            f"backend ({info['kind']} x{info['count']}) — libtpu failed to "
            f"initialise or there is no chip here.  To serve from the host "
            f"CPU on purpose pass --platform cpu (serve, warm, fleet).")
    return info

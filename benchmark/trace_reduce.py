"""From a profiler trace (``*.xplane.pb``) to device numbers.

The JAX profiler writes one plane per chip, ``/device:TPU:<i>``, with the
lines ``XLA Modules`` (one event per executed program: here one fused verify
pass) and ``XLA Ops`` (one event per operation inside it). Events carry
``name``, ``start_ns`` and ``duration_ns``.

  busy      the union of the ``XLA Modules`` intervals of a chip, averaged
            over the chips that have such a plane
  idle      1 - busy / window, where the window is the traced interval as
            the host's clock saw it (or, where no window is given, first
            module start to last module end, which leaves out idle time at
            both ends and so is used by the tests only)

A trace with no device plane is an error, never an idle share of 100%.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
MODULES, OPS = "XLA Modules", "XLA Ops"
TOP = 10  # entries of each breakdown list (the contract's limit)


class NoDevicePlane(RuntimeError):
    """The trace holds no ``/device:TPU:*`` plane with module events."""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def _union_ns(intervals: list) -> float:
    """Total length of the union of (start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def op_label(name: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def reduce_file(path: str, window_s: float | None = None,
                device_items: int | None = None) -> dict:
    """Reduce one ``*.xplane.pb``. ``window_s`` is the traced interval on the
    host's clock; ``device_items`` the items the verify service dispatched to
    the device in it (for ``kernel_items_per_s``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips = []  # one dict per device plane that ran a module
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = [(e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[MODULES].events] if MODULES in lines else []
        if not modules:
            continue
        ops: dict = {}
        if OPS in lines:
            for e in lines[OPS].events:
                label = op_label(e.name)
                ops[label] = ops.get(label, 0.0) + e.duration_ns
        chips.append({"plane": plane.name, "modules": sorted(modules),
                      "ops": ops})
    if not chips:
        raise NoDevicePlane(
            f"{path}: no {DEVICE_PLANE}* plane with {MODULES!r} events; "
            "planes: " + ", ".join(p.name for p in data.planes))

    busy_s = sum(_union_ns(c["modules"]) for c in chips) / len(chips) / 1e9
    module_events = sum(len(c["modules"]) for c in chips)
    module_time_s = sum(e - s for c in chips for s, e in c["modules"]) / 1e9
    span_s = (max(c["modules"][-1][1] for c in chips)
              - min(c["modules"][0][0] for c in chips)) / 1e9
    # the host's clock opened the window a little before the profiler did,
    # so the device's own span can only be shorter; hold the window to it
    window_s = max(window_s or 0.0, span_s)

    ops: dict = {}
    gaps: list = []
    for c in chips:
        for label, ns in c["ops"].items():
            ops[label] = ops.get(label, 0.0) + ns
        reach = c["modules"][0][1]
        for start, end in c["modules"][1:]:
            if start > reach:
                gaps.append(start - reach)
            reach = max(reach, end)
    out = {
        "planes": len(chips),
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 100.0 * (1.0 - busy_s / window_s),
        "module_events": module_events,
        "module_time_s": module_time_s,
        "kernel_ms_per_pass": 1e3 * module_time_s / module_events,
        "device_ops": [
            [label, ns / 1e9]
            for label, ns in sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        # what the host did in a gap is not known yet (it needs
        # jax.profiler.TraceAnnotation inside the program): all one name
        "idle_gaps": [["between_modules", ns / 1e9]
                      for ns in sorted(gaps, reverse=True)[:TOP]],
    }
    if device_items:
        out["kernel_items_per_s"] = device_items / module_time_s
    return out

"""Reduce a JAX profiler trace (``.xplane.pb``) to the window's device busy
time, its top device operations and its longest idle gaps.

* The window is the host span ``bench.window`` that ``bench/drive.py``
  opens around the measured window.
* Busy time is the union of the op intervals on each device plane
  (``/device:TPU:<i>``; line ``XLA Ops``, else ``XLA Modules``, else
  every line), clipped to the window and averaged over the devices that
  ran an op in it.
* Top operations sum each op's device time in the window, named
  ``<module>/<op>`` where the ``XLA Modules`` line says which program ran
  it, both cut to their short names (no HLO text, no fingerprint).
* Each idle gap of the first busy device is attributed to the host span
  ``bench.*`` (other than the window itself) that overlaps it most, or to
  ``no bench span``.

:func:`read_planes` turns a trace into plain tuples; :func:`reduce` works on
those alone, so a test can feed it a trace it builds.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"

# plane = (name, [(line name, [(event name, start_ns, end_ns)])])


def read_planes(profile) -> list:
    """Plain tuples of a ``jax.profiler.ProfileData``."""
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in ln.events]) for ln in p.lines])
            for p in profile.planes]


def load(trace_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_planes(ProfileData.from_file(max(files, key=os.path.getmtime)))


def _short(name: str) -> str:
    """``%fusion.6 = s32[...] fusion(...)`` → ``fusion.6``;
    ``jit_f(1711524293086572984)`` → ``jit_f``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.endswith(")") and "(" in name and name[name.rindex("(") + 1:-1].isdigit():
        name = name[:name.rindex("(")]
    return name


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, w0, w1):
    return max(a, w0), min(b, w1)


def _device_lines(lines):
    by_name = dict(lines)
    ops = by_name.get("XLA Ops")
    if ops is None:
        ops = by_name.get("XLA Modules")
    if ops is None:
        ops = [e for _n, evs in lines for e in evs]
    return ops, by_name.get("XLA Modules", [])


def reduce(planes: list, top: int = 10) -> dict:
    """``{"window_s", "busy_s", "devices", "device_ops", "idle_gaps"}``.

    Raises ``ValueError`` when the trace has no ``bench.window`` span.
    """
    host = [(name, a, b) for pname, lines in planes
            if not pname.startswith("/device:")
            for _ln, evs in lines for name, a, b in evs
            if name.startswith(HOST_PREFIX)]
    windows = [(a, b) for name, a, b in host if name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    spans = [(name, a, b) for name, a, b in host
             if name != WINDOW and b > w0 and a < w1]

    busy_per_device = []
    first_busy = None
    op_time: dict[str, float] = {}
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        ops, modules = _device_lines(lines)
        inside = [(n, *_clip(a, b, w0, w1)) for n, a, b in ops]
        inside = [(n, a, b) for n, a, b in inside if b > a]
        if not inside:
            continue
        busy = _union((a, b) for _n, a, b in inside)
        busy_per_device.append(sum(b - a for a, b in busy))
        if first_busy is None:
            first_busy = busy
        mods = sorted((a, b, n) for n, a, b in modules)
        starts = [m[0] for m in mods]
        for n, a, b in inside:
            n = _short(n)
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and mods[i][1] >= a and _short(mods[i][2]) != n:
                n = f"{_short(mods[i][2])}/{n}"
            op_time[n] = op_time.get(n, 0.0) + (b - a)

    gaps = []
    edges = [w0] + [x for iv in (first_busy or []) for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])

    def label(a: float, b: float) -> str:
        best, best_overlap = "no bench span", 0.0
        for name, s, e in spans:
            overlap = min(b, e) - max(a, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        return best

    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": (sum(busy_per_device) / len(busy_per_device) * ns
                   if busy_per_device else 0.0),
        "devices": len(busy_per_device),
        "device_ops": [[n, t * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(a, b), (b - a) * ns] for a, b in gaps[:top]],
    }

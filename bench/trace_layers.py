"""Split a JAX profiler trace's device time in the measured window by the
program's layers, and find how long the device sat idle inside each of the
program's spans.

It reads the planes :mod:`bench.trace_reduce` reads, with each event's
stats: ``(name, start_ns, end_ns, stats)``.

* The window is the longest host span ``bench.window``.
* Program spans are the host events that carry a ``span_id`` stat: the
  annotations that ``TRACER.enable(annotate=jax.profiler.TraceAnnotation)``
  opens around every span of ``repro.obs.trace``.  ``records`` maps a
  ``span_id`` to the tracer's ``(name, args)`` for it, whose attributes
  (a ``stratum``'s ``mode``) are set after the annotation opened.
* Each operation on a device plane's ``XLA Ops`` line belongs to a layer:
  that of the program that ran it (its ``XLA Modules`` event, short name,
  :data:`PROGRAMS`: programs whose jitted body is one ``jax.named_scope``
  of the program; the scope itself is in the op's metadata, which
  ``ProfileData`` does not expose); else that of the innermost program span
  open where the program was launched (:func:`span_layer`); else
  ``unscoped``.  A program execution's ``run_id`` is on the host event that
  enqueued it; where that event is on a runtime thread's line, the flow
  (``_c`` on the enclosing event, ``_p`` on its producer) leads back to
  the Python line of the thread that called it.  A layer's time is the
  union of its operations' intervals inside the window; ``unscoped`` is the
  busy time no layer covers.  Both are averaged over the devices that ran
  an operation in the window.
* Of the program spans that lie inside the window, each one's idle time is
  the part of it in which the first busy device ran no operation: the
  device waiting on the host while the span ran.

:func:`reduce` works on plain tuples alone, so a test can feed it a trace
it builds.
"""

from __future__ import annotations

import bisect
import functools

from bench.trace_reduce import DEVICE_PREFIX, WINDOW, _clip, _device_lines, _short, _union

#: The layer of each program whose jitted body is one device scope
#: (``core/relation.py``, ``kernels/bitmm.py``).
PROGRAMS = {
    "jit__merge_sorted": "tuple.merge",
    "jit__sort_pad": "tuple.merge",
    "jit__dedup_sorted": "tuple.merge",
    "jit_bitmm_call": "pbme",
    "jit_bitmm_fused_delta_call": "pbme",
}
UNSCOPED = "unscoped"

# plane = (name, [(line name, [(event name, start_ns, end_ns, stats)])])


def span_layer(name: str, args: dict) -> str | None:
    """The layer of device work launched inside a program span: the PBME
    increment runs eager operations, which carry no scope, inside the
    ``stratum`` span of mode ``bitmatrix``."""
    if name == "stratum" and args.get("mode") == "bitmatrix":
        return "pbme"
    return None


def read_planes(profile) -> list:
    """Plain tuples of a ``jax.profiler.ProfileData``, with event stats."""
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats)) for e in ln.events])
                      for ln in p.lines])
            for p in profile.planes]


def load(trace_dir: str) -> list:
    """Planes, with stats, of the newest ``.xplane.pb`` under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return read_planes(ProfileData.from_file(max(files, key=os.path.getmtime)))


class _Busy:
    """Busy time of sorted disjoint intervals over any ``[a, b)``."""

    def __init__(self, intervals):
        self.starts = [a for a, _b in intervals]
        self.ends = [b for _a, b in intervals]
        self.before = [0.0]
        for a, b in intervals:
            self.before.append(self.before[-1] + (b - a))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a)


def reduce(planes: list, records: dict | None = None) -> dict:
    """``{"window_s", "busy_s", "layers", "spans"}``.

    ``layers`` maps each layer (and ``unscoped``) to device seconds in the
    window; ``spans`` maps the name of each program span inside the window
    to their ``count``, ``seconds`` and ``idle_s``.
    Raises ``ValueError`` when the trace has no ``bench.window`` span.
    """
    records = records or {}
    windows, spans, launches, consumers, producers = [], [], {}, {}, {}
    for pname, lines in planes:
        if pname.startswith("/device:"):
            continue
        for li, (_ln, evs) in enumerate(lines):
            for name, a, b, st in evs:
                if name == WINDOW:
                    windows.append((a, b))
                elif "span_id" in st:
                    spans.append(((pname, li), name, a, b, st["span_id"]))
                else:
                    rid = st.get("run_id")
                    if rid is not None and a < launches.get(rid, (None, a + 1))[1]:
                        launches[rid] = ((pname, li), a)
                    if "_c" in st:
                        consumers.setdefault((pname, li), []).append((a, b, st["_c"]))
                    if "_p" in st:
                        producers[st["_p"]] = ((pname, li), a)
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    by_thread: dict = {}
    for key, name, a, b, sid in spans:
        by_thread.setdefault(key, []).append((a, b, sid, name))

    @functools.cache
    def launched_in(run_id) -> str | None:
        if run_id not in launches:
            return None
        key, t = launches[run_id]
        if key not in by_thread:        # a runtime thread: follow the flow back
            flow = next((c for a, b, c in consumers.get(key, ()) if a <= t <= b), None)
            if flow not in producers:
                return None
            key, t = producers[flow]
        inner = None
        for a, b, sid, name in by_thread.get(key, ()):
            if a <= t <= b and (inner is None or a >= inner[0]):
                inner = (a, sid, name)
        if inner is None:
            return None
        name, args = records.get(inner[1], (inner[2], {}))
        return span_layer(name, args)

    busy_per_device, layer_time = [], {}
    first_busy = None
    for pname, lines in planes:
        if not pname.startswith(DEVICE_PREFIX):
            continue
        ops, modules = _device_lines(lines)
        inside = [_clip(a, b, w0, w1) for _n, a, b, _st in ops]
        inside = [(a, b) for a, b in inside if b > a]
        if not inside:
            continue
        busy = _union(inside)
        busy_per_device.append(sum(b - a for a, b in busy))
        if first_busy is None:
            first_busy = busy
        mods = sorted(((a, b, n, st) for n, a, b, st in modules), key=lambda m: m[0])
        starts = [m[0] for m in mods]
        per_layer: dict[str, list] = {}
        for a, b in inside:
            layer = None
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and mods[i][1] >= a:
                _ma, _mb, mname, mst = mods[i]
                layer = PROGRAMS.get(_short(mname)) or launched_in(mst.get("run_id"))
            per_layer.setdefault(layer or UNSCOPED, []).append((a, b))
        per_layer.pop(UNSCOPED, None)
        covered = []
        for layer, ivs in per_layer.items():
            u = _union(ivs)
            covered.extend(u)
            layer_time[layer] = layer_time.get(layer, 0.0) + sum(b - a for a, b in u)
        layer_time[UNSCOPED] = layer_time.get(UNSCOPED, 0.0) + (
            busy_per_device[-1] - sum(b - a for a, b in _union(covered)))

    ndev = max(len(busy_per_device), 1)
    ns = 1e-9
    device = _Busy(first_busy or [])
    out_spans: dict[str, dict] = {}
    for _key, name, a, b, _sid in spans:
        if a < w0 or b > w1:
            continue
        idle = (b - a) - device.within(a, b)
        agg = out_spans.setdefault(name, {"count": 0, "seconds": 0.0, "idle_s": 0.0})
        agg["count"] += 1
        agg["seconds"] += (b - a) * ns
        agg["idle_s"] += idle * ns
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": sum(busy_per_device) / ndev * ns,
        "layers": {k: v / ndev * ns for k, v in sorted(layer_time.items())},
        "spans": out_spans,
    }

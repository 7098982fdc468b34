"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the device's
busy and idle time, its costliest operations and its longest idle gaps.

Busy is the union of the intervals in which an operation runs on a device,
per device, averaged over the devices used; the window is the span of the
harness's own annotation (``bench.window``) on the host plane, else the span
of the device events.  Kept with the benchmark so that every PR computes the
same number the same way; checked by ``fixtures/two_ops.xspace.txt``.
"""

import glob
import os
import re

WINDOW_ANNOTATION = "bench.window"
_ARRAY_SHAPE = re.compile(r"[a-z]+\d*\[\d[\d,]*\]")
_HLO_LINE = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
OPS_LINE = "XLA Ops"


def union_length(intervals):
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _device_planes(profile):
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "CUSTOM" not in p.name.upper()]


def short_name(event_name, limit=64):
    """XLA prints a device operation as its whole HLO line; keep the result's
    name, the opcode and the first array shape in the line:
    ``%fusion.30 fusion f32[6000000]``."""
    m = _HLO_LINE.match(event_name)
    if not m:
        return event_name[:limit]
    shape = _ARRAY_SHAPE.search(event_name)
    return " ".join([m.group(1), m.group(2)]
                    + ([shape.group(0)] if shape else []))[:limit]


def _op_events(plane):
    lines = list(plane.lines)
    chosen = [l for l in lines if l.name == OPS_LINE] or [
        l for l in lines if l.name not in ("Steps", "XLA Modules",
                                           "XLA TraceMe", "Framework Ops")]
    return [(short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for l in chosen for e in l.events if e.duration_ns > 0]


def _annotation(profile, name):
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for l in p.lines:
            for e in l.events:
                if e.name == name:
                    return e.start_ns, e.start_ns + e.duration_ns
    return None


def reduce_profile(profile, host_spans=(), anchor_s=None, top=10):
    """``host_spans`` are (name, start_wall_s, end_wall_s) on the host clock;
    ``anchor_s`` is the host clock at the start of the window
    annotation, which ties the two clocks together."""
    planes = [(p, _op_events(p)) for p in _device_planes(profile)]
    planes = [(p, ev) for p, ev in planes if ev]
    if not planes:
        return None
    window = _annotation(profile, WINDOW_ANNOTATION)
    if window is None:
        window = (min(s for _, ev in planes for _, s, _ in ev),
                  max(e for _, ev in planes for _, _, e in ev))
    lo, hi = window
    busy, per_op, idle = [], {}, []
    for _, ev in planes:
        inside = [(max(s, lo), min(e, hi)) for _, s, e in ev
                  if e > lo and s < hi]
        busy.append(union_length(inside))
        for name, s, e in ev:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0.0) + (min(e, hi) - max(s, lo))
        idle.append(gaps(inside, lo, hi))
    n = len(planes)
    spans_ns = []
    if anchor_s is not None:
        for name, s, e in host_spans:
            spans_ns.append((name, lo + (s - anchor_s) * 1e9,
                             lo + (e - anchor_s) * 1e9))

    def covering(s, e):
        """Innermost (shortest) host span that covers most of the gap."""
        best, best_len = "unattributed", None
        for name, a, b in spans_ns:
            overlap = min(e, b) - max(s, a)
            if overlap >= 0.5 * (e - s) and (best_len is None
                                             or b - a < best_len):
                best, best_len = name, b - a
        return best

    by_host = {}
    for s, e in idle[0]:
        k = covering(s, e)
        by_host[k] = by_host.get(k, 0.0) + (e - s)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "device_ops": sorted(([k, v / n / 1e9] for k, v in per_op.items()),
                             key=lambda kv: (-kv[1], kv[0]))[:top],
        "idle_gaps": sorted(([k, v / 1e9] for k, v in by_host.items()),
                            key=lambda kv: (-kv[1], kv[0]))[:top],
    }


def newest_xplane(log_dir):
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def reduce_file(path, **kw):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), **kw)


def reduce_text(text, **kw):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_text_proto(text), **kw)

"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

* device op events: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane (one event per executed HLO operation, custom calls included);
* busy time: the union of those events' intervals inside the window,
  averaged over the device planes; idle share = 1 - busy / window;
* device self time per op kind (:func:`short_name`; an enclosing op
  such as a ``while`` loop is charged only for what its body ops do
  not cover), and the idle gaps between busy intervals, each named by
  the innermost host annotation that covers it;
* device time per program: the ``XLA Modules`` line, one event per
  executed program, named as JAX names it (``jit_decode_fn``);
* the window: the host annotation named ``WINDOW`` that the harness
  opens around the traced part of a run; a trace without it is an
  error.

Run as a script on a trace file to print its reduction as JSON.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

WINDOW = "chipbench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    """A trace file, plain or xz-compressed (``.xz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        import lzma
        with lzma.open(path) as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An op's kind from its HLO text: ``%while.57 = (...) while(...)``
    -> ``while``; a custom call gets its target:
    ``closed_call:tpu_custom_call`` for a Pallas (Mosaic) kernel."""
    head = name.split(" = ", 1)[0].lstrip("%")
    head = re.sub(r"(\.\d+|\.clone)+$", "", head)
    m = _TARGET.search(name)
    return f"{head}:{m.group(1)}" if m else head


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def device_ops(pd, line: str = OPS_LINE) -> dict:
    """plane name -> [(event name, start_ns, end_ns)] of one line of each
    device: its ops (``XLA Ops``) or its programs (``XLA Modules``)."""
    return {p.name: sorted(_events(p, line), key=lambda e: e[1])
            for p in pd.planes if p.name.startswith(DEVICE_PREFIX)}


def module_name(name: str) -> str:
    """A program's name without its fingerprint:
    ``jit_decode_fn(9262806663112889868)`` -> ``jit_decode_fn``."""
    return name.split("(", 1)[0]


def host_spans(pd) -> list:
    """[(name, start_ns, end_ns)] of every host-thread event."""
    out = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                out.extend((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events)
    return out


def self_times(events, lo: float, hi: float) -> dict:
    """Device seconds per op kind inside [lo, hi], each op counted for
    its self time: an op that encloses others (a ``while`` loop around
    its body) is charged only for the time none of them covers."""
    by_kind: dict = {}
    stack: list = []            # [kind, start, end, covered by children]

    def close(item):
        kind, s, e, child = item
        own = max(0.0, min(e, hi) - max(s, lo)) - child
        by_kind[kind] = by_kind.get(kind, 0.0) + max(own, 0.0)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0.0, min(e, hi, stack[-1][2])
                                - max(s, lo))
        stack.append([short_name(name), s, e, 0.0])
    while stack:
        close(stack.pop())
    return {k: v / 1e9 for k, v in by_kind.items()}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of the intervals clipped to [lo, hi]."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


PHASE_PREFIX = "engine."


def _namer(spans):
    """What the host was doing at a time: the innermost engine phase
    the harness annotated (``engine.*``) and the innermost host span of
    all, as ``"<phase> > <span>"``; ``"no host span"`` when none."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]

    def name(t):
        inner = phase = None
        for sp in spans[:bisect.bisect_right(starts, t)]:
            nm, s, e = sp
            if e < t:
                continue
            if inner is None or e - s < inner[2] - inner[1]:
                inner = sp
            if nm.startswith(PHASE_PREFIX) and (
                    phase is None or e - s < phase[2] - phase[1]):
                phase = sp
        if inner is None:
            return "no host span"
        if phase is None or phase is inner:
            return inner[0]
        return f"{phase[0]} > {inner[0]}"
    return name


def reduce(pd, top: int = 10) -> dict:
    """Busy and idle time, device time by op name and the longest idle
    gaps of the traced window."""
    devices = device_ops(pd)
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device op events")
    hosts = host_spans(pd)
    marks = [(s, e) for n, s, e in hosts if n == WINDOW]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = marks[0]
    window_ns = hi - lo
    by_module: dict = {}
    for evs in device_ops(pd, MODULES_LINE).values():
        for nm, s, e in evs:
            k = module_name(nm)
            by_module[k] = (by_module.get(k, 0.0)
                            + max(0.0, min(e, hi) - max(s, lo)) / 1e9)
    busy, by_op, gaps = [], {}, []
    name = _namer([h for h in hosts if h[0] != WINDOW])
    for evs in devices.values():
        merged = union(((s, e) for _, s, e in evs), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for kind, secs in self_times(evs, lo, hi).items():
            by_op[kind] = by_op.get(kind, 0.0) + secs
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
    busy_s = sum(busy) / len(busy) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "devices": len(devices),
        "op_s": {k: v / len(devices) for k, v in ops},
        "module_s": {k: v / len(devices) for k, v in by_module.items()},
        "device_ops": [[k, v / len(devices)] for k, v in ops[:top]],
        "idle_gaps": [[name((a + b) / 2), (b - a) / 1e9] for a, b in
                      sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
    }


def op_seconds(red: dict, pattern) -> float:
    """Device seconds of the ops whose name matches ``pattern`` (a
    compiled regular expression)."""
    return sum(v for k, v in red["op_s"].items() if pattern.search(k))


if __name__ == "__main__":
    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    red = reduce(load(path))
    red["op_s"] = dict(list(red["op_s"].items())[:40])
    print(json.dumps(red, indent=1))

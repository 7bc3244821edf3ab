"""Reduction of a profiler trace (``.xplane.pb``) to the per-layer numbers.

The traced window is the harness's ``window`` span on the host. Within it:

- busy time: the union of the intervals of the device's ops (the
  ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged over chips;
- the ops that took most device time, by opcode and result shape (the
  ops of a ``while`` body count, the ``while`` itself does not);
- Pallas kernel time: the ops that run a Mosaic kernel (``tpu_custom_call``
  in the op's HLO text or stats);
- idle gaps: the intervals of the window in which no op ran, each named
  by the harness span (``dispatch``, ``wait``, ``data``) that the host was
  in for most of it;
- rounds: the window's ``dispatch`` spans count the calls.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict

HOST_SPANS = ("dispatch", "wait", "data")
TOP = 10


def _xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(_xplane(path))


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


_OP = re.compile(r"^%?([\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> tuple[str, str]:
    """(opcode, result shape) of a device op whose event name is its HLO
    instruction (``%fusion.3 = f32[8]{0} fusion(...), ...``); the name
    itself where it is not."""
    m = _OP.match(name)
    if not m:
        return name[:80], ""
    return m.group(3), m.group(2)[:80]


def is_kernel(name: str, stats: dict) -> bool:
    """A Mosaic (Pallas) kernel: a custom call to ``tpu_custom_call``."""
    text = name + " " + " ".join(str(v) for v in stats.values())
    return "tpu_custom_call" in text


def _union(intervals):
    total, end = 0, None
    start_cur = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start_cur
            start_cur, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start_cur
    return total


def _gaps(intervals, lo, hi):
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def reduce(path: str) -> dict:
    pd = _load(path)
    host = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window" or ev.name in HOST_SPANS:
                        host[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops = [line for line in plane.lines if line.name == "XLA Ops"]
            devices.append(ops)
    if not host["window"]:
        raise RuntimeError("the trace holds no window span")
    lo, hi = max(host["window"], key=lambda iv: iv[1] - iv[0])
    spans = {k: [iv for iv in host[k] if lo <= iv[0] < hi]
             for k in HOST_SPANS}
    busy, kernel_ns, kernel_calls = [], 0, 0
    by_name = defaultdict(int)
    first = None
    for lines in devices:
        ivs = []
        for line in lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                ivs.append((s, e))
                opcode, shape = op_kind(ev.name)
                if opcode in CONTAINERS:
                    continue        # its body's ops are events of their own
                kernel = ((opcode == "custom-call" or not shape)
                          and is_kernel(ev.name, _stats(ev)))
                by_name[f"{opcode} {shape}".strip()
                        + (" tpu_custom_call" if kernel else "")] += e - s
                if kernel:
                    kernel_ns += e - s
                    kernel_calls += 1
        busy.append(_union(ivs))
        if first is None:
            first = ivs
    n_dev = max(len(devices), 1)
    gaps = []
    for a, b in _gaps(first or [], lo, hi):
        where = max(HOST_SPANS, key=lambda k: sum(
            max(0, min(b, e) - max(a, s)) for s, e in spans[k]))
        gaps.append((where, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": len(devices),
        "kernel_s": kernel_ns / n_dev * 1e-9,
        "kernel_calls": kernel_calls,
        "rounds": len(spans["dispatch"]),
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in top],
        "idle_gaps": [[k, v] for k, v in gaps[:TOP]],
    }


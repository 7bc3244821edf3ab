"""The program's named layers in a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives a device op its name, start and length,
but not the stats of its metadata, where the op's ``tf_op`` is kept: its
JAX name-stack path, such as
``jit(round)/fl.exchange/vmap(jit(fused_decode))/pallas_call``. The
program's ``jax.named_scope`` layers (``repro.utils.spans``) are parts of
that path. So this module reads the serialized XSpace itself, with a
decoder of the protobuf wire format for the few messages it needs, and
reduces the traced window (the harness's ``window`` span) to:

- ``scopes``: device seconds of each program scope, averaged over chips.
  An op belongs to the innermost scope on its path; a scope's total holds
  the scopes named under it (``fl.exchange`` holds ``fl.exchange.noise``).
  Each op counts its self time: a ``while``, ``conditional`` or ``call``
  container only the time its body's ops leave uncovered (its loop
  control and the gaps between its ops), so scopes and the unscoped rest
  add up to the busy time;
- ``unscoped_s``: the device seconds of the ops under no scope;
- ``idle_gaps``: the window's gaps in the first device's work, each named
  by the harness span the host was in for most of it, then ``/`` and the
  program's host span that overlaps it most, where one does
  (``dispatch/fl.chunk``).

The window, the busy time and the rounds are read as ``bench/trace.py``
reads them. A trace of a program without named layers reads as unscoped.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from types import SimpleNamespace

from bench import trace

try:
    from repro.utils import spans
    SCOPES, PROGRAM_SPANS = spans.SCOPES, spans.HOST_SPANS
except ImportError:       # a program that names no layers reads as unscoped
    SCOPES, PROGRAM_SPANS = (), ()

DEVICE = "/device:TPU:"


# -- the protobuf wire format --------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, span=None):
    """(field number, value) of each field of one message in
    ``buf[span[0]:span[1]]``: an int for a varint or fixed-width field, the
    (start, end) of its bytes for a length-delimited one."""
    i, end = span or (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            w = 8 if kind == 1 else 4
            v, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map(buf, span):
    """(key, value span) of one entry of a protobuf map."""
    key = val = None
    for f, v in _fields(buf, span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5; XLine: name = 2, timestamp_ns = 3, events = 4;
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
# XEventMetadata: name = 2, stats = 5; XStatMetadata: name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7.

def _plane(buf, span):
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf, span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, val = _map(buf, v)
            event_meta[k] = val
        elif f == 5:
            k, val = _map(buf, v)
            stat_names[k] = next((_str(buf, s) for g, s in _fields(buf, val)
                                  if g == 2), "")
    return name, lines, event_meta, stat_names


def _line(buf, span):
    name, t0, events = "", 0, []
    for f, v in _fields(buf, span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            t0 = v
        elif f == 4:
            mid = off = dur = 0
            for g, w in _fields(buf, v):
                if g == 1:
                    mid = w
                elif g == 2:
                    off = w
                elif g == 3:
                    dur = w
            events.append((mid, off, dur))
    return name, t0, events


def _event_meta(buf, span, stat_names):
    """(name, tf_op) of an event's metadata."""
    name, tf_op = "", ""
    for f, v in _fields(buf, span):
        if f == 2:
            name = _str(buf, v)
        elif f == 5:
            mid, val = None, ""
            for g, w in _fields(buf, v):
                if g == 1:
                    mid = w
                elif g == 5:
                    val = _str(buf, w)
                elif g == 7:
                    val = stat_names.get(w, "")
            if stat_names.get(mid) == "tf_op":
                tf_op = val
    return name, tf_op


def read(path: str) -> SimpleNamespace:
    """The trace's host spans and device ops, in picoseconds:
    ``host[name]`` the (start, end) of each host event of that name,
    ``devices`` one list per chip of (start, end, HLO text, tf_op) of
    the ops of its ``XLA Ops`` line."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            buf = f.read()
    else:
        with open(trace._xplane(path), "rb") as f:
            buf = f.read()
    buf = memoryview(buf)
    host, devices = defaultdict(list), []
    for f, span in _fields(buf):
        if f != 1:
            continue
        name, lines, event_meta, stat_names = _plane(buf, span)
        is_host = name.startswith("/host:")
        is_device = (name.startswith(DEVICE)
                     and name[len(DEVICE):].isdigit())
        if not (is_host or is_device):
            continue
        meta, ops = {}, []
        for ls in lines:
            lname, t0, events = _line(buf, ls)
            if is_device and lname != "XLA Ops":
                continue
            for mid, off, dur in events:
                if mid not in meta:
                    meta[mid] = _event_meta(buf, event_meta.get(mid, (0, 0)),
                                            stat_names)
                s = t0 * 1000 + off
                ev_name, tf_op = meta[mid]
                if is_host:
                    host[ev_name].append((s, s + dur))
                else:
                    ops.append((s, s + dur, ev_name, tf_op))
        if is_device:
            devices.append(ops)
    return SimpleNamespace(host=host, devices=devices)


# -- scopes --------------------------------------------------------------------

_WRAP = re.compile(r"^\w+\((.*)\)$")


def _unwrap(part: str) -> str:
    """A path component without its transform wrappers:
    ``transpose(jvp(fl.local_steps))`` is ``fl.local_steps``."""
    while (m := _WRAP.match(part)):
        part = m.group(1)
    return part


def scope_of(tf_op: str):
    """The innermost program scope on an op's name-stack path, or None.
    Where XLA merged ops, ``tf_op`` joins their paths with ``;``: the
    first one is read."""
    inner = None
    for part in tf_op.split(";")[0].split("/"):
        if _unwrap(part) in SCOPES:
            inner = _unwrap(part)
    return inner


def window(tr) -> tuple[int, int]:
    if not tr.host["window"]:
        raise RuntimeError("the trace holds no window span")
    return max(tr.host["window"], key=lambda iv: iv[1] - iv[0])


def _holder(names):
    """The innermost scope that holds each of ``names`` (None: no scope)."""
    if None in names:
        return None
    return max((p for p in SCOPES
                if all(n == p or n.startswith(p + ".") for n in names)),
               key=len, default=None)


def ops_in_window(tr):
    """Per chip, (self seconds, HLO text, tf_op, scope) of each op in the
    window, clipped to it. An op's self time is its length less that of
    the ops nested in it: a leaf op keeps its whole length, a container
    (``while``, ``conditional``, ``call``) only what its body's ops leave
    uncovered, so the self times of a chip add up to its busy time. An op
    belongs to the innermost scope on its ``tf_op``; a container the
    compiler left without one, to the innermost scope that holds all of
    its body's ops."""
    lo, hi = window(tr)
    out = []
    for ops in tr.devices:
        evs = sorted(((max(s, lo), min(e, hi), name, tf_op)
                      for s, e, name, tf_op in ops if s < hi and e > lo),
                     key=lambda ev: (ev[0], -ev[1]))
        own = [e - s for s, e, *_ in evs]
        kids = [[] for _ in evs]
        stack = []          # indices of the open enclosing events
        for i, (s, e, *_) in enumerate(evs):
            while stack and evs[stack[-1]][1] <= s:
                stack.pop()
            if stack and e <= evs[stack[-1]][1]:
                own[stack[-1]] -= e - s
                kids[stack[-1]].append(i)
            stack.append(i)
        scope = [None] * len(evs)
        for i in reversed(range(len(evs))):   # a body's ops come later
            tf_op = evs[i][3]
            scope[i] = (scope_of(tf_op) if tf_op or not kids[i] else
                        _holder({scope[k] for k in kids[i]}))
        out.append([(t * 1e-12, name, tf_op, sc) for t, (_, _, name, tf_op),
                    sc in zip(own, evs, scope)])
    return out


def _overlap(a, b, ivs):
    return sum(max(0, min(b, e) - max(a, s)) for s, e in ivs)


def reduce(path: str) -> dict:
    tr = read(path)
    lo, hi = window(tr)
    n_dev = max(len(tr.devices), 1)
    own, unscoped = defaultdict(float), 0.0
    for ops in ops_in_window(tr):
        for secs, _, _, s in ops:
            if s is None:
                unscoped += secs
            else:
                own[s] += secs
    totals = {s: sum(t for c, t in own.items()
                     if c == s or c.startswith(s + ".")) / n_dev
              for s in SCOPES}
    busy = [trace._union([(max(s, lo), min(e, hi)) for s, e, *_ in ops
                          if lo < e and s < hi]) for ops in tr.devices]
    inside = {k: [iv for iv in tr.host[k] if lo <= iv[0] < hi]
              for k in trace.HOST_SPANS + PROGRAM_SPANS}
    first = [(s, e) for s, e, *_ in (tr.devices[0] if tr.devices else [])
             if lo < e and s < hi]
    gaps = []
    for a, b in trace._gaps(first, lo, hi):
        where = max(trace.HOST_SPANS,
                    key=lambda k: _overlap(a, b, inside[k]))
        cover = {k: _overlap(a, b, inside[k]) for k in PROGRAM_SPANS}
        prog = max(cover, key=cover.get, default=None)
        if prog is not None and cover[prog] > 0:
            where = f"{where}/{prog}"
        gaps.append([where, (b - a) * 1e-12])
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-12,
        "busy_s": sum(busy) / n_dev * 1e-12,
        "devices": len(tr.devices),
        "rounds": len(inside["dispatch"]),
        "scopes": totals,
        "unscoped_s": unscoped / n_dev,
        "idle_gaps": gaps[:trace.TOP],
    }

"""Trace to numbers: device busy time, the traced window, the ops that
took most time and the longest idle gaps, each labelled by the harness's
host span that covers most of it.  Input is ``tracing.extract``'s plain
form, so the arithmetic is tested on hand-made traces.

On the TPU the op line nests: a ``while`` op spans its whole loop, and
the ops of its body run inside it.  Busy time is therefore the union of
the leaf ops, those that enclose no other op, so that the gaps between
a loop body's ops count as idle."""
from __future__ import annotations

import collections

TOP = 10


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def leaves(events) -> list:
    """The events that enclose no other event (sorted by start, longer
    first on ties)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, ev in enumerate(evs):
        end = ev[1] + ev[2]
        if i + 1 < len(evs) and evs[i + 1][1] < end \
                and evs[i + 1][1] + evs[i + 1][2] <= end:
            continue                      # the next event lies inside
        out.append(ev)
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _gaps(busy, lo, hi) -> list:
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _label(gap, spans) -> str:
    """The host span (other than the whole call) that overlaps ``gap``
    most, or "host" where none does."""
    best, name = 0, "host"
    for span, s, e in spans:
        if span == "call":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, span
    return name


def reduce(trace: dict) -> dict:
    """{"window_s", "busy_s" (mean over devices), "ops" (top device ops
    by leaf time, [name, s]), "gaps" (longest idle gaps, [span, s])}."""
    spans = [(n, s, s + d) for n, s, d in trace["host"]]
    calls = [(s, e) for n, s, e in spans if n == "call"]
    if not calls or not trace["device"]:
        raise ValueError("the trace holds no traced call or no device ops")
    lo, hi = calls[0]
    busy_total, ops, gaps = 0.0, collections.Counter(), []
    for i, events in enumerate(trace["device"].values()):
        leaf = [ev for ev in leaves(events)
                if ev[1] < hi and ev[1] + ev[2] > lo]
        busy = union(_clip([(s, s + d) for _, s, d in leaf], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            gaps = [(_label(g, spans), g[1] - g[0])
                    for g in _gaps(busy, lo, hi)]
            for name, s, d in leaf:
                ops[op_name(name)] += min(s + d, hi) - max(s, lo)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / len(trace["device"]) * 1e-9,
        "ops": [[n, d * 1e-9] for n, d in ops.most_common(TOP)],
        "gaps": [[n, d * 1e-9] for n, d in gaps[:TOP]],
    }

"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

The harness marks every call into the program's search as a host event
``search_call`` (with the batch size as its ``nq`` stat). On the device
planes (``/device:TPU:<n>``) the line ``XLA Ops`` holds one event per
executed HLO op; a Pallas kernel shows under its HLO name (``hop.4``,
``pq_lut.1``), which ``base_name`` strips to the kernel's name.

The traced window runs from the start of the first ``search_call`` to the
end of the last. Within it:

* busy time is the union of op intervals, averaged over the device planes
  that ran anything; idle is the rest;
* each call gets the device ops that start inside its span (a call blocks
  until its answer is back, so its device work lies inside it);
* an op's time is its self time: a ``while`` op spans the whole loop, and
  the ops of its body, which lie inside it, count for themselves;
* each idle gap is labelled by what the host was doing: inside a
  ``search_call`` (staging, dispatch, copying answers) or between calls.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

KERNELS = ("hop", "pq_lut")      # the Pallas kernels of the search path
CALL = "search_call"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def base_name(op: str) -> str:
    """``%hop.4 = (f32[...]) custom-call(...)`` -> ``hop``: the HLO
    instruction's name without its ``%`` and numeric suffix."""
    return re.sub(r"\.\d+$", "", op.split(" = ", 1)[0].lstrip("%"))


def _self_times(ops):
    """Each op's duration less that of the ops nested in it (a ``while``
    op spans its whole loop; its body's ops lie inside it)."""
    out, stack = [], []             # stack: indices of open enclosing ops
    for i, (name, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        out.append([name, s, e, e - s])
        if stack and e <= ops[stack[-1]][2]:
            out[stack[-1]][3] -= e - s
        stack.append(i)
    return out


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(host_calls, device_ops, *, kernels=KERNELS):
    """The reduction itself, on plain tuples so a test can feed it.

    host_calls: [(start_ns, end_ns, nq)]; device_ops: {plane: [(name,
    start_ns, end_ns)]}. Returns None where there is nothing to read."""
    calls = sorted(host_calls)
    planes = {p: sorted(ops, key=lambda o: (o[1], -o[2]))
              for p, ops in device_ops.items() if ops}
    if not calls or not planes:
        return None
    w0, w1 = calls[0][0], max(c[1] for c in calls)
    window = w1 - w0
    busy_total = 0.0
    merged_by_plane = {}
    for p, ops in planes.items():
        merged = _merge((max(s, w0), min(e, w1)) for _, s, e in ops
                        if e > w0 and s < w1)
        merged_by_plane[p] = merged
        busy_total += sum(e - s for s, e in merged)
    busy = busy_total / len(planes)
    first = sorted(planes)[0]
    timed = {p: _self_times(ops) for p, ops in planes.items()}
    op_time = defaultdict(float)
    for ops in timed.values():
        for name, s, e, own in ops:
            if w0 <= s < w1:
                op_time[base_name(name)] += own / len(planes)
    per_call = []
    ops0 = timed[first]
    starts = [o[1] for o in ops0]
    for s, e, nq in calls:
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        rec = {"nq": nq, "span_ns": e - s, "hop_launches": 0,
               "kernel_ns": defaultdict(float), "xla_ns": 0.0}
        for name, _, _, own in ops0[i:j]:
            b = base_name(name)
            if b in kernels:
                rec["kernel_ns"][b] += own
                if b == "hop":
                    rec["hop_launches"] += 1
            else:
                rec["xla_ns"] += own
        rec["kernel_ns"] = dict(rec["kernel_ns"])
        per_call.append(rec)
    gaps = []
    merged = merged_by_plane[first]
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    spans = [(s, e) for s, e, _ in calls]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        mid = (g0 + g1) / 2
        inside = any(s <= mid < e for s, e in spans)
        gaps.append(("in search_call" if inside else "between calls",
                     (g1 - g0) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "calls": per_call,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in device_ops],
            "idle_gaps": [[n, t] for n, t in gaps[:10]],
        },
    }


def read_events(pdata, call=CALL):
    """(host_calls, device_ops) out of a ``jax.profiler.ProfileData``."""
    host_calls, device_ops = [], {}
    for plane in pdata.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == call:
                        nq = dict(ev.stats).get("nq")
                        host_calls.append((ev.start_ns, ev.end_ns,
                                           int(nq) if nq is not None else 0))
        elif _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events]
    return host_calls, device_ops


def reduce_trace(trace_dir: str):
    """Reduce the newest trace under ``trace_dir``; None if there is none."""
    import jax
    path = newest_xplane(trace_dir) if trace_dir else None
    if path is None:
        return None
    pdata = jax.profiler.ProfileData.from_file(path)
    return reduce_events(*read_events(pdata))

"""Reduce the program's own spans and scopes in a profiler trace.

The program marks its phases in two ways, and this module reads both:

* host spans (``repro.obs`` spans mirrored into the profiler): per batch
  ``engine.collect``, then ``search.call`` around ``search.stage``,
  ``search.dispatch`` and ``search.fetch``, then ``engine.fanout``;
* device scopes (``jax.named_scope`` in the beam search, found in each
  compiled op's ``op_name`` metadata): ``lut`` and ``init`` before the
  loop, ``frontier``, ``hop``, ``pool``, ``visited`` and ``trim`` in it.

A trace names device ops by their HLO instruction, so the scope of an op
comes from the compiled text of the program that ran it: the served
search fn's ``lower(nq, k)``, compiled again (a cache lookup in the
process that ran it). An op is taken to belong to a program only if its
name, result shape and opcode in the trace are those of the compiled
instruction.

Idle time is device time with no op running, inside the traced window of
``trace_reduce`` (first to last ``search_call``). Each idle instant goes
to the span the host was in: a host span of the call or the fan-out
(``HOST_SPANS``), else ``engine.collect``, else the rest of
``search.call``, else ``none``; so the parts add up to the whole.

Against a program that has no such spans, scopes or ``lower``, every
reading here is None.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from pathlib import Path

from benchmarks.chip import trace_reduce

SCOPES = ("lut", "init", "frontier", "hop", "pool", "visited", "trim")
NONE = "none"
COLLECT = "engine.collect"
CALL = "search.call"
HOST_SPANS = ("search.stage", "search.dispatch", "search.fetch",
              "engine.fanout")
# in priority order: an idle instant goes to the first span it lies in
SPANS = HOST_SPANS + (COLLECT, CALL)
_PALLAS = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_SIG = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_LOOP = re.compile(r"\b(?:condition|body)=(%[\w.\-]+)")


# ---------------------------------------------------------------------------
# compiled text -> the scope of each instruction
# ---------------------------------------------------------------------------


def _scope_of(op_name: str):
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


def _signature(text: str):
    text = text.strip()
    m = _SIG.match(text[5:] if text.startswith("ROOT ") else text)
    return (m.group(1), m.group(2), m.group(3)) if m else None


def _operands(text: str) -> list:
    """The instruction names an instruction's operand list holds."""
    text = text.strip()
    m = _SIG.match(text[5:] if text.startswith("ROOT ") else text)
    if not m:
        return []
    rest = text[m.end() + (5 if text.startswith("ROOT ") else 0):]
    return re.findall(r"%([\w.\-]+)", rest[:rest.find(")")])


def scope_map(hlo_text: str) -> dict:
    """``{instruction: (scope, in_loop, pallas, signature)}`` for every
    instruction of a compiled module's text.

    The scope is the first component of the instruction's ``op_name`` that
    names a scope; else the scope most of a fusion's own instructions
    carry. An instruction with no ``op_name`` at all (one the compiler
    put in: a layout copy, a reshape, a sort of an expanded scatter)
    takes the one scope all its operands have, else the scope its
    neighbours on both sides in its computation share. The rest are
    ``none``. ``in_loop`` says it lies in a while loop's body or
    condition."""
    comps, order, lines = {}, [], {}
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split(" ", 2)
            cur = head[1] if head[0] == "ENTRY" else head[0]
            comps[cur] = []
            order.append(cur)
        elif cur is not None and line.startswith("  "):
            m = _INSTR.match(line)
            if m:
                comps[cur].append(m.group(1))
                lines[m.group(1)] = line
    loops = set()
    for text in lines.values():
        if " while(" in text:
            loops.update(_LOOP.findall(text))

    def own(name):
        m = _OP_NAME.search(lines[name])
        return _scope_of(m.group(1)) if m else None

    def fused(comp, seen):
        counts = defaultdict(int)
        for name in comps.get(comp, ()):
            s = own(name)
            if s is not None:
                counts[s] += 1
            for callee in _CALLS.findall(lines[name]):
                if callee not in seen:
                    seen.add(callee)
                    for s2, n in fused(callee, seen).items():
                        counts[s2] += n
        return counts

    out = {}
    for comp in order:
        names = comps[comp]
        scopes, done, orphans = [], {}, []
        for name in names:
            s = own(name)
            if s is None:
                callees = _CALLS.findall(lines[name])
                counts = defaultdict(int)
                for callee in callees:
                    for s2, n in fused(callee, {callee}).items():
                        counts[s2] += n
                if counts:
                    s = max(sorted(counts), key=counts.get)
            unnamed = _OP_NAME.search(lines[name]) is None
            if s is None and unnamed:
                ins = {done.get(o) for o in _operands(lines[name])}
                if len(ins) == 1:
                    s = ins.pop()
            scopes.append(s)
            done[name] = s
            if s is None and unnamed:
                orphans.append(len(scopes) - 1)
        known = [i for i, s in enumerate(scopes) if s is not None]
        for i in orphans:
            if known:
                j = bisect.bisect_left(known, i)
                if 0 < j < len(known) \
                        and scopes[known[j - 1]] == scopes[known[j]]:
                    scopes[i] = scopes[known[j]]
        for name, s in zip(names, scopes):
            text = lines[name]
            out[name] = (s or NONE, comp in loops, _PALLAS in text,
                         _signature(text))
    return out


# ---------------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------------


def read_spans(pdata) -> list:
    """``[(name, start_ns, end_ns)]`` of the program's spans on the host."""
    out = []
    for plane in pdata.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def idle_split(host_calls, device_ops, spans):
    """Device-idle ns inside the traced window, by the span the host was
    in (``HOST_SPANS`` first, then ``engine.collect``, the rest of
    ``search.call``, then ``none``). None where nothing was traced."""
    calls = sorted(host_calls)
    plane = sorted(p for p, ops in device_ops.items() if ops)
    if not calls or not plane:
        return None
    w0, w1 = calls[0][0], max(c[1] for c in calls)
    busy = trace_reduce._merge(
        (max(s, w0), min(e, w1)) for _, s, e in device_ops[plane[0]]
        if e > w0 and s < w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    rank = {n: i for i, n in enumerate(SPANS)}
    points = []                      # (t, delta, key); key -1 is idle
    for a, b in idle:
        points += [(a, 1, -1), (b, -1, -1)]
    for name, s, e in spans:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            points += [(s, 1, rank[name]), (e, -1, rank[name])]
    points.sort()
    active = defaultdict(int)
    out = defaultdict(float)
    prev = None
    for t, delta, key in points:
        if prev is not None and t > prev and active[-1] > 0:
            inside = [k for k in range(len(SPANS)) if active[k] > 0]
            out[SPANS[inside[0]] if inside else NONE] += t - prev
        active[key] += delta
        prev = t
    split = {n: out.get(n, 0.0) for n in SPANS + (NONE,)}
    return {"idle_ns": split, "idle_total_ns": sum(b - a for a, b in idle),
            "calls": len(calls)}


def scope_times(host_calls, device_ops, programs):
    """Device self-time ns per scope over the calls whose program is
    known: ``programs`` maps a call's nq to its ``scope_map`` (or None).
    Also the loop body's non-Pallas ns in and out of any scope, the
    unscoped ops by name, and the loop trips (hop launches) of those
    calls. None where no call's ops match a known program."""
    plane = sorted(p for p, ops in device_ops.items() if ops)
    if not host_calls or not plane:
        return None
    ops = trace_reduce._self_times(
        sorted(device_ops[plane[0]], key=lambda o: (o[1], -o[2])))
    starts = [o[1] for o in ops]
    per_scope = defaultdict(float)
    unscoped = defaultdict(float)
    loop = {"scoped": 0.0, "unscoped": 0.0}
    trips = mapped = 0
    for s, e, nq in sorted(host_calls):
        smap = programs(nq)
        if not smap:
            continue
        mine = ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]
        found = []
        for name, _, _, own in mine:
            key = name.split(" = ", 1)[0].lstrip("%")
            entry = smap.get(key)
            sig = _signature(name) if " = " in name else None
            if entry is None or (sig is not None and entry[3] is not None
                                 and sig != entry[3]):
                found = None
                break
            found.append((key, entry, own))
        if not found:
            continue
        mapped += 1
        for key, (scope, in_loop, pallas, _), own in found:
            per_scope[scope] += own
            if trace_reduce.base_name(key) == "hop":
                trips += 1
            if in_loop and not pallas:
                loop["unscoped" if scope == NONE else "scoped"] += own
            if scope == NONE:
                unscoped[trace_reduce.base_name(key)] += own
    if not mapped:
        return None
    return {"scope_ns": dict(per_scope), "loop_ns": loop, "trips": trips,
            "mapped_calls": mapped, "unscoped_ns": dict(unscoped)}


# ---------------------------------------------------------------------------
# one run's record -> the reduction, once
# ---------------------------------------------------------------------------

_CACHE: dict = {}


def _bench_root(reader_file: str) -> tuple:
    """(checkout root, benchmark dir) of a reader in ``<bench>/metrics``."""
    bench = Path(reader_file).absolute().parents[1]
    return bench.parents[1], bench


def trace_dir(rec, reader_file: str):
    """The traced run's directory: that of the cell whose configuration
    and traffic are the record's."""
    root, bench = _bench_root(reader_file)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    confs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        conf = confs.get(w["config"])
        traffic = bench / "traffic" / f"{w['traffic']}.json"
        if conf is None or not traffic.is_file() \
                or not (root / conf["file"]).is_file():
            continue
        if json.loads(traffic.read_text()) == rec["traffic"] and \
                json.loads((root / conf["file"]).read_text()) == rec["config"]:
            return root / ".bench_cache" / "trace" / w["name"]
    return None


def served_programs(rec):
    """nq -> ``scope_map`` of the program the served search fn runs for a
    call of nq queries at the traffic's k; None where the program's search
    fn has no ``lower``."""
    import jax
    import jax.numpy as jnp
    from repro.core.device_index import DeviceIndex
    from repro.serving.engine import make_device_search_fn
    cfg, layout = rec["config"], rec["layout"]
    s = cfg["assumed"]["search"]
    sds = jax.ShapeDtypeStruct
    index = DeviceIndex(
        chunk_words=sds((cfg["n_vectors"], layout.device_rows, 128),
                        jnp.int32),
        centroids=sds((cfg["pq_m"], cfg["pq_ks"], cfg["dim"] // cfg["pq_m"]),
                      jnp.float32),
        ep_ids=sds((1,), jnp.int32),
        ep_codes=sds((1, cfg["pq_m"]), jnp.int32))
    fn = make_device_search_fn(
        index, layout, metric=cfg["metric"], L=s["L"], w=s["w"],
        max_hops=s["max_hops"], backend=s["backend"],
        adc_dtype=s["adc_dtype"], rerank=s["rerank"])
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    k = int(rec["traffic"]["k"])
    maps = {}

    def program(nq):
        if nq not in maps:
            maps[nq] = scope_map(lower(nq, k).compile().as_text())
        return maps[nq]
    return program


def reduce_path(path: str, programs) -> dict:
    """The reduction of one ``.xplane.pb``: ``phases`` and what the
    per-layer readers take from it."""
    import jax
    pdata = jax.profiler.ProfileData.from_file(path)
    host_calls, device_ops = trace_reduce.read_events(pdata)
    spans = read_spans(pdata)
    seen = {n for n, _, _ in spans}
    idle = idle_split(host_calls, device_ops, spans) \
        if CALL in seen else None
    scopes = scope_times(host_calls, device_ops, programs) \
        if programs is not None else None
    return {"idle": idle, "scopes": scopes, "spans_seen": sorted(seen),
            "phases": phases(idle, scopes)}


def phases(idle, scopes):
    """Device ms per traced call by scope, and idle ms per call by span."""
    out = {}
    if scopes is not None:
        n = scopes["mapped_calls"]
        loop = scopes["loop_ns"]
        body = loop["scoped"] + loop["unscoped"]
        out["device_ms_per_call"] = {
            k: v / n / 1e6 for k, v in sorted(scopes["scope_ns"].items())}
        out["loop_unscoped_share"] = \
            100.0 * loop["unscoped"] / body if body else None
        out["unscoped_ms_per_call"] = [
            [k, v / n / 1e6] for k, v in
            sorted(scopes["unscoped_ns"].items(), key=lambda kv: -kv[1])[:10]]
        out["mapped_calls"] = n
        out["trips"] = scopes["trips"]
    if idle is not None:
        n = idle["calls"]
        out["idle_ms_per_call"] = {k: v / n / 1e6
                                   for k, v in idle["idle_ns"].items()}
        out["idle_total_ms_per_call"] = idle["idle_total_ns"] / n / 1e6
        out["calls"] = n
    return out


def reduce_run(rec, reader_file: str):
    """The reduction of the run's trace, computed once per trace file;
    None for an untraced run or one with no trace file."""
    if rec["window"].trace is None:
        return None
    d = trace_dir(rec, reader_file)
    path = trace_reduce.newest_xplane(str(d)) if d is not None else None
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = reduce_path(path, served_programs(rec))
    return _CACHE[path]


# ---------------------------------------------------------------------------
# what the per-layer readers read
# ---------------------------------------------------------------------------


def idle_ms_per_call(rec, reader_file: str, names) -> float | None:
    r = reduce_run(rec, reader_file)
    if r is None or r["idle"] is None:
        return None
    if not set(names) & set(r["spans_seen"]):
        return None
    idle = r["idle"]
    return sum(idle["idle_ns"][n] for n in names) / idle["calls"] / 1e6


def scope_ms_per_trip(rec, reader_file: str, scope: str) -> float | None:
    r = reduce_run(rec, reader_file)
    if r is None or r["scopes"] is None or not r["scopes"]["trips"]:
        return None
    s = r["scopes"]
    return s["scope_ns"].get(scope, 0.0) / s["trips"] / 1e6

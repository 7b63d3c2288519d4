"""A profiler trace of the measured window -> per-span device time, busy
and idle time, host stages, and the breakdown of the result line.

Spans are the program's ``obs.trace.phase_span`` names (``jax.named_scope``),
which reach the compiled HLO as ``op_name`` metadata.  The join that turns
a device op into its layer:

  device op event (module, hlo op name, duration)
      -> the compiled program's HLO text, ``%<op> = ... op_name="<path>"``
      -> the OUTERMOST of :data:`SPANS` on <path>
      -> per-span device time (``span_s``), and the share of op time so
         attributed (``coverage``).

Each op counts toward one layer only, so ``span_s`` over :data:`SPANS` never
counts an op twice.  A per-layer reader may also declare spans of its own
(``SPANS`` in its module under ``bench/metrics/``), at any depth: a declared
span is a part of a layer, and ``span_in_s`` gives it the device time of
every op whose path holds it, nested or not, without moving ``span_s``.
A reader's ``HOST_SPANS`` name host stages (``obs.trace.host_span``, a
``jax.profiler.TraceAnnotation``): ``host_span_s`` and ``host_span_n`` give
their time and count inside ``bench/window``, and the idle gaps of the
breakdown are named by them too.  ``counters`` passes through the
driver's means of the program's own per-round metrics over the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: every span the per-layer metrics read
SPANS = ("fl/local_steps", "fl/apply",
         "wire/quantize_pack", "wire/psum", "wire/ring_hops",
         "wire/reduce_scatter", "wire/all_gather", "wire/unpack_dequant",
         "fleet/advance_channel", "fleet/power_assign", "fleet/rates_cost",
         "fleet/select", "fleet/drop_realize", "fleet/energy_ledger")

_METADATA_RE = re.compile(
    r"%?([\w.\-]+) = .*metadata=\{[^}]*op_name=\"([^\"]+)\"")


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------

def parse_hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """``as_text()`` -> {instruction name: op_name metadata path}."""
    return {m.group(1): m.group(2)
            for m in _METADATA_RE.finditer(hlo_text)}


def span_at(path: str, span: str) -> int:
    """Where ``span`` sits on an ``op_name`` path, at any depth (-1: not
    on it)."""
    at = path.find("/" + span + "/")
    if at < 0 and path.startswith(span + "/"):
        at = 0
    return at


def outermost_span(path: Optional[str],
                   spans: Iterable[str] = SPANS) -> Optional[str]:
    """The FIRST known span on an ``op_name`` path (outermost wins)."""
    if not path:
        return None
    best, best_at = None, len(path) + 1
    for span in spans:
        at = span_at(path, span)
        if 0 <= at < best_at:
            best, best_at = span, at
    return best


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

class Op:
    __slots__ = ("name", "module", "start", "end")

    def __init__(self, name, module, start, end):
        self.name, self.module, self.start, self.end = name, module, start, end


def _stats(ev) -> Dict[str, Any]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


_OP_NAME_RE = re.compile(r"^%?([\w.\-]+)(?: = |$)")


def _op_name(event_name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.2 = u32[3] fusion(), ...``); the instruction name leads."""
    m = _OP_NAME_RE.match(event_name)
    return m.group(1) if m else event_name


def _module_base(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return name.split("(")[0].strip()


def load(trace_dir: str):
    from jax.profiler import ProfileData
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return ProfileData.from_file(hits[-1])


def device_ops(pd) -> Dict[int, List[Op]]:
    """Device id -> op events.  TPU planes keep ops on their ``XLA Ops``
    line and programs on ``XLA Modules``; the CPU backend records ops on
    host threads, tagged with ``hlo_op`` and ``device_ordinal``."""
    out: Dict[int, List[Op]] = defaultdict(list)
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                               _module_base(e.name))
                              for e in lines.get("XLA Modules", [])))
            starts = [m[0] for m in modules]
            for e in lines.get("XLA Ops", []):
                st = _stats(e)
                module = st.get("hlo_module") or _enclosing(
                    modules, starts, e.start_ns)
                out[dev].append(Op(_op_name(e.name), module or "",
                                   e.start_ns, e.start_ns + e.duration_ns))
    if out:
        return dict(out)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" not in st:
                    continue
                out[int(st.get("device_ordinal", 0))].append(Op(
                    st["hlo_op"], st.get("hlo_module", ""), e.start_ns,
                    e.start_ns + e.duration_ns))
    return dict(out)


def _enclosing(modules: List[Tuple[float, float, str]], starts: List[float],
               t: float) -> Optional[str]:
    """The program running at time ``t`` (modules sorted by start)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return None


def host_spans(pd, names: Iterable[str] = (), prefix: str = "bench/"
               ) -> List[Tuple[float, float, str]]:
    """The benchmark's own host spans and the declared ``names``,
    (start, end, name)."""
    names = frozenset(names)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix) or e.name in names:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return sorted(out)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def leaf_ops(ops: List[Op]) -> List[Op]:
    """Drop ops that enclose other ops of the same device (control-flow
    and call wrappers), whose durations would count their children twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    keep = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end \
                and (nxt.end - nxt.start) < (o.end - o.start):
            continue
        keep.append(o)
    return keep


def union_length(intervals: List[Tuple[float, float]]) -> float:
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


def busy_intervals(ops: List[Op], w0: float, w1: float):
    out = []
    for o in ops:
        s, e = max(o.start, w0), min(o.end, w1)
        if e > s:
            out.append((s, e))
    return sorted(out)


def gaps(intervals: List[Tuple[float, float]], w0: float, w1: float):
    """Idle stretches of one device inside [w0, w1]."""
    out, t = [], w0
    merged = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def name_gap(gap: Tuple[float, float], spans) -> str:
    """What the host was doing in a gap: the innermost host span that
    covers most of it (more than half), else the one that covers the
    largest part, the shorter on a tie."""
    covering = []
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > 0 and name != "bench/window":
            covering.append((cover, e - s, name))
    if not covering:
        return "host: outside any span"
    most = [c for c in covering if 2 * c[0] > gap[1] - gap[0]]
    if most:
        return min(most, key=lambda c: c[1])[2]
    return max(covering, key=lambda c: (c[0], -c[1]))[2]


def host_span_totals(spans, names: Iterable[str], w0: float, w1: float):
    """({name: seconds}, {name: count}) of the ``names`` host spans that
    overlap [w0, w1], their time cut to it."""
    names = frozenset(names)
    secs: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for s, e, name in spans:
        cover = min(e, w1) - max(s, w0)
        if name in names and cover > 0:
            secs[name] += cover * 1e-9
            count[name] += 1
    return dict(secs), dict(count)


def reduce(trace_dir: str, rec: Dict[str, Any], devices,
           spans: Iterable[str] = (), host: Iterable[str] = ()
           ) -> Dict[str, Any]:
    """Everything the per-layer readers need from one traced window;
    ``rec`` is the driver's record (rounds, work counts, HLO texts,
    counters); ``spans`` and ``host`` are the device and host spans the
    readers declare."""
    spans, host = tuple(spans), tuple(host)
    pd = load(trace_dir)
    spans_host = host_spans(pd, host)
    win = [(s, e) for s, e, n in spans_host if n == "bench/window"]
    if not win:
        raise RuntimeError("the trace holds no bench/window span")
    w0, w1 = win[-1]
    op_maps = {_module_base(m): parse_hlo_op_names(t)
               for m, t in rec.get("hlo", {}).items()}
    per_dev = device_ops(pd)
    if not per_dev:
        raise RuntimeError("the trace holds no device op")
    n_dev = len(per_dev)
    span_s: Dict[str, float] = defaultdict(float)
    span_in: Dict[str, float] = defaultdict(float)
    busy_total, attributed, provenanced, op_total = 0.0, 0.0, 0.0, 0.0
    op_time: Dict[str, float] = defaultdict(float)
    first_dev = min(per_dev)
    dev_gaps = []
    for dev, ops in per_dev.items():
        ops = leaf_ops(ops)
        intervals = busy_intervals(ops, w0, w1)
        busy_total += union_length(intervals)
        for o in ops:
            s, e = max(o.start, w0), min(o.end, w1)
            if e <= s:
                continue
            dur = (e - s) * 1e-9
            path = op_maps.get(_module_base(o.module), {}).get(o.name)
            span = outermost_span(path)
            op_total += dur
            if path:
                provenanced += dur
            if span is not None:
                attributed += dur
                span_s[span] += dur
            for declared in spans:
                if path and span_at(path, declared) >= 0:
                    span_in[declared] += dur
            op_time[f"{o.name} [{span or 'no span'}]"] += dur / n_dev
        if dev == first_dev:
            dev_gaps = gaps(intervals, w0, w1)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(dev_gaps, key=lambda g: g[0] - g[1])[:10]
    host_s, host_n = host_span_totals(spans_host, host, w0, w1)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev * 1e-9,
        "span_s": {k: v / n_dev for k, v in span_s.items()},
        "span_in_s": {k: v / n_dev for k, v in span_in.items()},
        "host_span_s": host_s,
        "host_span_n": host_n,
        "op_s": op_total / n_dev,
        "coverage": attributed / op_total if op_total else 0.0,
        "provenanced": provenanced / op_total if op_total else 0.0,
        "rounds": rec["rounds"],
        "chips": n_dev,
        "breakdown": {
            "device_ops": [[n, v] for n, v in top_ops],
            "idle_gaps": [[name_gap(g, spans_host), (g[1] - g[0]) * 1e-9]
                          for g in top_gaps]},
        "work": rec.get("work", {}),
        "counters": rec.get("counters", {}),
        "device_kind": devices[0].device_kind,
    }

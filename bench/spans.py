"""The serving program's own spans and stage scopes in a profiler trace.

The program (``repro.core.obs``) writes host spans named ``serve.*`` with
``jax.profiler.TraceAnnotation``, on the clock of the device's op lines,
and runs each stage of its serving executable under a ``jax.named_scope``
(``STAGES``; ``BEAM_STAGES`` inside the beam loop). This module reads
both back:

- ``load_events`` keeps what ``trace.load_events`` keeps, plus the host
  ``serve.*`` spans, and gives each device op its stage ``scope`` from
  the serving executable's HLO (``hlo_scopes``), keyed by instruction
  name: a TPU trace's op events carry no name stack (their stats are
  offsets and durations). An op with no scope of its own that runs
  inside a scoped op (the body of the beam search's ``while``) takes
  that op's.
- the reductions: a stage's share of device busy time
  (``stage_share``), the mean length of a span (``span_ms``), and idle
  gaps and top ops labelled with the host span and the stage scope
  (``idle_gaps``, ``top_ops``).

``Event`` is ``trace.Event`` with a trailing ``scope``; the reducers of
``trace`` take either.
"""

from __future__ import annotations

import collections
import gzip
import json
import re
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench import trace

__all__ = ["Event", "SPAN_PREFIX", "STAGES", "BEAM_STAGES", "scope_of",
           "hlo_scopes", "search_step_hlo", "load_events", "read_events",
           "host_spans",
           "stage_share", "span_ms", "idle_gaps", "top_ops"]

SPAN_PREFIX = "serve."
# the program's stage scopes, as the trace spells them
STAGES = ("cluster_filter", "route_lanes", "prepare_lanes", "beam_search",
          "rerank", "merge_topk")
BEAM_STAGES = ("visited", "expand", "rank", "select")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    scope: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def scope_of(name_stack: str) -> str:
    """The stage of an op from its name stack
    (``jit(search_step)/vmap(beam_search)/.../while/body/visited/or`` ->
    ``beam_search/visited``); "" where no stage scope is on it."""
    parts = [re.sub(r"^(?:\w+\()+|\)+$", "", p)
             for p in name_stack.split("/")]
    for i, p in enumerate(parts):
        if p in STAGES:
            sub = [q for q in parts[i + 1:] if q in BEAM_STAGES] \
                if p == "beam_search" else []
            return p + ("/" + sub[-1] if sub else "")
    return ""


_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_CALLED = re.compile(r"(calls|body|condition|to_apply)=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> stage scope, from a compiled module's text
    (``Compiled.as_text()``). An instruction takes the scope of its own
    ``op_name``; a fusion or call without one, the commonest scope of the
    instructions it calls; one with neither (a copy, a sort or a
    broadcast the compiler put in), the commonest scope of its operands,
    else of its users, else that of the instruction that runs its
    computation (a ``while`` its body)."""
    own: dict[str, str] = {}
    comp_of: dict[str, str] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    calls: dict[str, list[tuple[str, str]]] = {}
    operands: dict[str, list[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else ""
        comp_of[name] = comp
        members[comp].append(name)
        calls[name] = _CALLED.findall(line)
        called = {c for _, c in calls[name]}
        operands[name] = [r for r in _REF.findall(line.split(" = ", 1)[1])
                          if r not in called]

    def fused(name: str, seen: set) -> str:
        votes = collections.Counter()
        for kind, c in calls[name]:
            if kind != "calls" or c in seen:
                continue
            seen.add(c)
            for inner in members.get(c, ()):
                s = own[inner] or fused(inner, seen)
                if s:
                    votes[s] += 1
        return votes.most_common(1)[0][0] if votes else ""

    scope = {n: own[n] or fused(n, set()) for n in own}
    users: dict[str, list[str]] = collections.defaultdict(list)
    for n, ops in operands.items():
        for o in ops:
            users[o].append(n)
    for links in (operands, users):
        changed = True
        while changed:
            changed = False
            for n in scope:
                if scope[n]:
                    continue
                votes = collections.Counter(
                    scope[o] for o in links.get(n, ())
                    if scope.get(o) and comp_of.get(o) == comp_of[n])
                if votes:
                    scope[n] = votes.most_common(1)[0][0]
                    changed = True
    caller = {c: n for n, cs in calls.items() for kind, c in cs
              if kind in ("body", "condition")}

    def inherited(name: str) -> str:
        seen = set()
        while not scope[name] and comp_of[name] in caller \
                and name not in seen:
            seen.add(name)
            name = caller[comp_of[name]]
        return scope[name]

    return {n: inherited(n) for n in scope}


def search_step_hlo(eng, bucket: int) -> str:
    """Compiled text of an engine's serving executable for ``bucket``
    (the persistent compile cache returns the executable that ran; it
    keys entries without their metadata unless
    ``jax_compilation_cache_include_metadata_in_key`` is set, so an entry
    compiled before the stage scopes existed comes back without them)."""
    import jax.numpy as jnp
    fn = eng._search_cache[bucket]
    q = np.zeros((bucket, eng.icfg.dim), np.float32)
    return fn.lower(eng.placed, eng.index.centroids, eng.index.rotation,
                    eng.host.vectors, q, jnp.int32(bucket)
                    ).compile().as_text()


def _xplane(trace_dir) -> Path | None:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return paths[-1] if paths else None


def load_events(trace_dir, scopes: dict[str, str] | None = None
                ) -> list[Event]:
    """``trace.load_events`` plus the host ``serve.*`` spans, with each
    device op's stage scope from ``scopes`` (an ``hlo_scopes`` map of the
    executable that ran)."""
    from jax.profiler import ProfileData
    path = _xplane(trace_dir)
    if path is None:
        return []
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        dev = trace._is_device(plane.name)
        for line in plane.lines:
            ops = dev and line.name == trace.OPS_LINE
            keep = dev and line.name in (trace.OPS_LINE, trace.MODULES_LINE)
            for e in line.events:
                if not (keep or e.name == trace.WINDOW
                        or e.name.startswith(SPAN_PREFIX)):
                    continue
                name = trace.short_name(e.name)
                scope = (scopes or {}).get(name.split(" ")[0], "") \
                    if ops else ""
                out.append(Event(plane.name, line.name, name,
                                 float(e.start_ns), float(e.duration_ns),
                                 scope))
    return _inherit_scopes(out)


def _inherit_scopes(events: list[Event]) -> list[Event]:
    """An op without a scope that runs wholly inside a scoped op of the
    same line takes that op's scope."""
    out, open_ = [], {}
    for e in sorted(events, key=lambda e: (e.plane, e.line, e.start_ns,
                                           -e.dur_ns)):
        key = (e.plane, e.line)
        if e.line == trace.OPS_LINE:
            stack = open_.setdefault(key, [])
            while stack and stack[-1].end_ns < e.end_ns:
                stack.pop()
            if not e.scope and stack:
                e = e._replace(scope=stack[-1].scope)
            if e.scope:
                stack.append(e)
        out.append(e)
    return out


def read_events(path) -> list[Event]:
    """Events saved with ``trace.save_events``, scopes included (a
    recorded fixture)."""
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def host_spans(events, name: str | None = None, lo: float = -np.inf,
               hi: float = np.inf) -> list:
    """The program's host spans (``name`` alone where given) that lie
    wholly inside [lo, hi], in start order."""
    return sorted((e for e in events
                   if e.name.startswith(SPAN_PREFIX)
                   and (name is None or e.name == name)
                   and e.start_ns >= lo and e.end_ns <= hi),
                  key=lambda e: e.start_ns)


def stage_share(events, stage: str) -> float | None:
    """Device time under a stage scope, as a percentage of device busy
    time, inside the traced span (the union of op intervals each way;
    mean over the devices). None where the ops carry no scope."""
    span = trace.window_ns(events)
    ops = trace.device_ops(events)
    if span is None or not ops or not any(
            getattr(e, "scope", "") for v in ops.values() for e in v):
        return None
    lo, hi = span
    shares = []
    for v in ops.values():
        busy = trace.busy_ns(v, lo, hi)
        mine = [e for e in v if e.scope == stage
                or e.scope.startswith(stage + "/")]
        shares.append(100.0 * trace.busy_ns(mine, lo, hi) / busy
                      if busy > 0 else 0.0)
    return float(np.mean(shares))


def span_ms(events, name: str) -> float | None:
    """Mean length of the host spans ``name`` wholly inside the traced
    span, in ms; None where there are none."""
    span = trace.window_ns(events)
    if span is None:
        return None
    got = host_spans(events, name, *span)
    return 1e-6 * sum(e.dur_ns for e in got) / len(got) if got else None


def _host_label(spans: list, a: float, b: float) -> str:
    """The innermost ``serve.*`` span that covers most of [a, b]."""
    if not spans:
        return "no program span"
    starts = np.array([e.start_ns for e in spans])
    ends = np.array([e.end_ns for e in spans])
    cover = np.minimum(ends, b) - np.maximum(starts, a)
    if cover.max() <= 0:
        return "no program span"
    best = np.flatnonzero(cover == cover.max())
    inner = best[np.argmin(ends[best] - starts[best])]
    return f"host in {spans[inner].name}"


def idle_gaps(ops: list, spans: list, lo: float, hi: float,
              n: int | None = 10) -> list[list]:
    """[[label, seconds], ...] of the longest idle gaps of a device inside
    [lo, hi] (``n`` None: every gap), as ``trace.idle_gaps`` finds them,
    labelled by the op that ends where the gap starts and by the host
    span that covers most of the gap."""
    spans = host_spans(spans)
    names = {}
    for e in ops:
        names.setdefault(e.end_ns, e.name)
    gaps, prev_end, prev_name = [], lo, "window start"
    for a, b in trace._merged((max(e.start_ns, lo), min(e.end_ns, hi))
                              for e in ops if e.end_ns > lo
                              and e.start_ns < hi):
        if a > prev_end:
            gaps.append((a - prev_end, prev_name, prev_end, a))
        prev_end, prev_name = b, names.get(b, "op")
    if hi > prev_end:
        gaps.append((hi - prev_end, prev_name, prev_end, hi))
    gaps.sort(key=lambda g: -g[0])
    return [[f"after {name}; {_host_label(spans, a, b)}", g * 1e-9]
            for g, name, a, b in gaps[:n]]


def top_ops(ops: list, n: int = 10) -> list[list]:
    """``trace.top_ops``, each op labelled with its stage scope."""
    scope = {e.name: getattr(e, "scope", "") for e in ops}
    return [[f"{name} [{scope[name] or 'no scope'}]", s]
            for name, s in trace.top_ops(ops, n)]

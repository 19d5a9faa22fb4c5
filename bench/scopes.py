"""The program's own spans and name scopes in a profile of the window.

bench/trace.py reduces the device to busy, idle, kernels and programs,
and names each idle gap after the benchmark's span (``bench.*``) open
at its middle.  This module reads what the program itself puts in the
same ``.xplane.pb``, on the same clock:

* the engine's host spans (``engine.*``, serving/engine.py), nested in
  the benchmark's.  ``idle_by_span`` sums every idle interval of the
  device by the innermost span open over each part of it (``none``
  where no span is open), so its values add up to the window's whole
  idle time.  ``step_host_s`` is each ``engine.step`` less its
  ``*.sync`` children: the host's own work in a step, without the wait
  for the device.
* the model's name scopes in each operation's op_name (``sc_linear``,
  ``kv_write``, ``paged_attn``, ``sampler``).  ``device_by_scope`` sums
  device time by the innermost of them.  An operation under none of
  them counts under ``<program>`` and the named loop it runs in
  (``layers``, ``chunks``: ``jit__decode_fn/layers``); an operation XLA
  added (an async copy) has no op_name, and takes the loop of the
  innermost ``while`` that runs around it.  A TPU profile names each
  operation by its HLO instruction without metadata, so the op_names
  come from the compiled text of the programs that ran
  (``hlo_op_names``).
"""

from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict

from bench.trace import CONTAINERS, find_xplane, op_name

__all__ = ["SCOPES", "LOOPS", "innermost", "idle_by_span", "step_host_s",
           "scope_of", "device_by_scope", "reduce_program", "hlo_op_names",
           "summarize"]

SCOPES = ("sc_linear", "kv_write", "paged_attn", "sampler")
LOOPS = ("layers", "chunks")
SPAN_PREFIXES = ("bench.", "engine.")
SYNC = ".sync"
OP_NAME = re.compile(r'op_name="([^"]*)"')
HLO_HEAD = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) [a-z][\w\-]*\(")
INDEX_NOTE = re.compile(r"/\*index=\d+\*/|\s")


def innermost(spans, lo: int, hi: int):
    """Consecutive ``(start, end, label)`` segments covering ``[lo, hi]``,
    each labelled by the innermost of the nested ``(label, start, end)``
    spans open over it, or ``None``."""
    out, stack, t = [], [], lo

    def upto(x):
        nonlocal t
        x = min(max(x, lo), hi)
        if x > t:
            out.append((t, x, stack[-1][1] if stack else None))
            t = x
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][0] <= s:     # spans that ended before
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append((e, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return out


def _complement(intervals, lo, hi):
    """Gaps of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def idle_by_span(device_busy: dict, spans, lo: int, hi: int) -> dict:
    """Seconds of device idle in ``[lo, hi]`` by the innermost span open
    over them (``"none"`` where no span is), averaged over devices.
    ``device_busy``: device -> ``(start_ns, end_ns)`` operation
    intervals; ``spans``: ``(name, start_ns, end_ns)``."""
    seg = innermost(spans, lo, hi)
    starts = [s for s, _, _ in seg]
    out = defaultdict(float)
    for busy in device_busy.values():
        for a, b in _complement(busy, lo, hi):
            i = bisect.bisect_right(starts, a) - 1
            while a < b:
                s, e, name = seg[i]
                cut = min(b, e)
                out[name or "none"] += cut - a
                a, i = cut, i + 1
    n = max(len(device_busy), 1)
    return {k: v * 1e-9 / n for k, v in sorted(out.items(),
                                              key=lambda kv: -kv[1])}


def _children(spans):
    """Each span's direct children: spans nest, so sort by start (a
    parent before a child that starts with it) and keep a stack."""
    kids = defaultdict(list)
    stack = []
    for sp in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= sp[1]:
            stack.pop()
        if stack:
            kids[stack[-1]].append(sp)
        stack.append(sp)
    return kids


def step_host_s(spans) -> list:
    """``(kind, seconds)`` per ``engine.step``: its duration less its
    ``*.sync`` descendants.  ``kind`` is ``decode`` for a step that
    dispatched a decode and admitted nothing, ``prefill`` for one that
    prefilled, else ``other``."""
    kids = _children(spans)
    out = []

    def below(sp):
        for k in kids.get(sp, ()):
            yield k
            yield from below(k)
    for sp in spans:
        if sp[0] != "engine.step":
            continue
        names = [k[0] for k in below(sp)]
        sync = sum(k[2] - k[1] for k in below(sp) if k[0].endswith(SYNC))
        kind = "prefill" if "engine.prefill" in names else \
            "decode" if "engine.decode.dispatch" in names else "other"
        out.append((kind, (sp[2] - sp[1] - sync) * 1e-9))
    return out


def scope_of(path: str, program: str, loop_path: str = "") -> str:
    """The innermost of ``SCOPES`` in an op_name ``path``; failing
    that, ``program`` and the innermost of ``LOOPS`` in ``path`` or, for
    an operation without an op_name, in ``loop_path`` (the op_name of
    the loop that runs around it)."""
    parts = path.split("/")
    for p in reversed(parts):
        if p in SCOPES:
            return p
    for p in reversed(parts if path else loop_path.split("/")):
        if p in LOOPS:
            return f"{program}/{p}"
    return program


def _result_type(event_name: str) -> str:
    """``s8`` of ``%copy.3 = s8[257,32]{1,0} copy(...)``, ``tuple`` for
    a tuple result, ``""`` for an event name without HLO text."""
    _, _, rest = event_name.partition(" = ")
    if not rest:
        return ""
    return "tuple" if rest.startswith("(") else rest.split("[", 1)[0]


def device_by_scope(device_ops: dict, lo: int, hi: int):
    """Device seconds by scope (``scope_of``), and the 20 largest
    (scope, operation, seconds) triples, the operation named with its
    result type (``copy:s8``: a copy of KV codes, ``copy:bf16`` of
    weights).  ``device_ops``: device -> ``(event name, start_ns,
    end_ns, op_name, program)``.  Loops and calls count no time of
    their own (their body's operations do)."""
    by, pairs = defaultdict(float), defaultdict(float)
    for ops in device_ops.values():
        loops = [(path, s, e) for name, s, e, path, _ in ops
                 if op_name(name) == "while"]
        seg = innermost(loops, lo, hi) if loops else [(lo, hi, None)]
        starts = [s for s, _, _ in seg]
        for name, s, e, path, program in ops:
            s, e = max(s, lo), min(e, hi)
            base = op_name(name)
            if e <= s or base in CONTAINERS:
                continue
            loop = seg[max(bisect.bisect_right(starts, s) - 1, 0)][2]
            key = scope_of(path, program, loop or "")
            by[key] += e - s
            dt = _result_type(name)
            pairs[(key, f"{base}:{dt}" if dt else base)] += e - s
    n = max(len(device_ops), 1)
    top = sorted(pairs.items(), key=lambda kv: -kv[1])[:20]
    return ({k: v * 1e-9 / n for k, v in sorted(by.items(),
                                                key=lambda kv: -kv[1])},
            [[k, op, v * 1e-9 / n] for (k, op), v in top])


def reduce_program(device_ops: dict, spans) -> dict:
    """The reduction on plain data: ``device_ops`` as
    :func:`device_by_scope` takes them, ``spans`` the ``(name, start_ns,
    end_ns)`` of one host thread's ``bench.*`` and ``engine.*`` spans.
    The window runs from the first ``bench.*`` span's start to the last
    one's end, as in bench/trace.py."""
    bench = [sp for sp in spans if sp[0].startswith("bench.")]
    if not bench:
        raise ValueError("the trace holds none of the benchmark's spans")
    lo = min(s for _, s, _ in bench)
    hi = max(e for _, _, e in bench)
    busy = {dev: [(max(s, lo), min(e, hi)) for _, s, e, _, _ in ops
                  if min(e, hi) > max(s, lo)]
            for dev, ops in device_ops.items()}
    idle = idle_by_span(busy, spans, lo, hi)
    scopes, pairs = device_by_scope(device_ops, lo, hi)
    steps = step_host_s(spans)
    decode = [t for kind, t in steps if kind == "decode"]
    n_engine = sum(sp[0].startswith("engine.") for sp in spans)
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(idle.values()),
        "idle_by_span": idle,
        "device_by_scope": scopes,
        "ops_by_scope": pairs,
        "steps": len(steps),
        "step_host_ms": statistics.median(decode) * 1e3 if decode else None,
        "spans_per_step": n_engine / len(steps) if steps else None,
    }


def _head(text: str):
    """(instruction name, result type) of an HLO instruction's text, the
    type without spaces or ``/*index=N*/`` notes; None if it is none."""
    m = HLO_HEAD.match(text)
    return (m.group(1), INDEX_NOTE.sub("", m.group(2))) if m else None


def hlo_op_names(texts) -> dict:
    """``(program, instruction) -> {result type: op_name}`` from compiled
    HLO texts (``Compiled.as_text()``, program = the ``HloModule`` name).
    A TPU profile names each operation by its instruction's text without
    its metadata; this is where its op_name comes from."""
    out = {}
    for text in texts:
        prog = text.split(None, 2)[1].rstrip(",")
        for line in text.splitlines():
            m = OP_NAME.search(line)
            head = _head(line) if m else None
            if head:
                out.setdefault((prog, head[0]), {})[head[1]] = m.group(1)
    return out


def _op_path(name: str, program: str, hlo: dict) -> str:
    """An operation's op_name: the ``op_name="..."`` of its event name
    where the profile keeps the metadata, else the compiled program's
    (by instruction name, and by result type where programs of one name
    disagree), else ``""``."""
    m = OP_NAME.search(name)
    if m:
        return m.group(1)
    head = _head(name)
    found = hlo.get((program, head[0]), {}) if head else {}
    if len(set(found.values())) == 1:
        return next(iter(found.values()))
    return found.get(head[1], "") if found else ""


def _in_programs(ops, modules):
    """Each operation's program: the ``XLA Modules`` event running around
    its start (its name up to the parenthesis), else ``?``."""
    if not modules:
        return ["?"] * len(ops)
    lo = min(s for _, s, _ in modules)
    hi = max(e for _, _, e in modules)
    seg = innermost(modules, lo, hi)
    starts = [s for s, _, _ in seg]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = seg[i][2] if 0 <= i and s < seg[i][1] else None
        out.append((prog or "?").split("(")[0])
    return out


def summarize(trace_dir: str, hlo_texts=()) -> dict:
    """:func:`reduce_program` of the ``.xplane.pb`` under ``trace_dir``:
    each TPU device's ``XLA Ops`` with their program and op_name (from
    ``hlo_texts``, the compiled programs that ran, see
    :func:`hlo_op_names`), and the spans of the host thread that holds
    the benchmark's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    hlo = hlo_op_names(hlo_texts)
    ops, threads = {}, []
    for plane in pd.planes:
        dev = plane.name[len("/device:TPU:"):]
        if plane.name.startswith("/device:TPU:") and dev.isdigit():
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = [(ev.name, ev.start_ns, ev.end_ns)
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ())]
            evs = [(ev.name, ev.start_ns, ev.end_ns)
                   for ev in lines["XLA Ops"].events]
            ops[plane.name] = [
                (n, s, e, _op_path(n, prog, hlo), prog)
                for (n, s, e), prog in zip(evs, _in_programs(evs, mods))]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                sp = [(ev.name, ev.start_ns, ev.end_ns)
                      for ev in line.events
                      if ev.name.startswith(SPAN_PREFIXES)]
                if any(n.startswith("bench.") for n, _, _ in sp):
                    threads.append(sp)
    if len(threads) != 1:
        raise ValueError(f"the benchmark's spans sit on {len(threads)} "
                         "host threads, not one")
    return reduce_program(ops, threads[0])

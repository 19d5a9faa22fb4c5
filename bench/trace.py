"""Reduce a profiler trace of the window to device busy, idle and kernel
time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX.  Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds one event per executed operation and ``XLA Modules`` one per
program run.  The benchmark's host spans (``bench.step``,
``bench.submit``, ``bench.wait``) are on the host plane, on the same
clock.

* busy: the union of the operation intervals inside the window, averaged
  over the devices; idle share ``1 - busy / window``;
* kernel time: summed durations of the operations whose own name (not
  their operands') holds the Pallas kernel's name (``paged_attn_decode``,
  ``paged_attn_prefill``);
* module time: summed durations of the programs whose name holds a key
  (``prefill``, ``decode``);
* breakdown: the ten operations (by instruction name, loops and calls
  left out since their time is their body's) that took most time, and
  the ten longest idle gaps, each named after the host span that was
  open at its middle.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

__all__ = ["find_xplane", "summarize", "reduce_events", "op_name"]

HOST_SPANS = ("bench.step", "bench.submit", "bench.wait")
# control flow whose duration is its body's operations
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text, ``%fusion.12 =
    bf16[...] fusion(...)``: keep the instruction's own name, without
    its number (``fusion``, ``paged_attn_decode_pallas``)."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    base = name.rstrip("0123456789")
    return base[:-1] if base.endswith(".") else name


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_events(device_ops: dict, device_modules: dict, host_spans: list,
                  kernels=(), modules=()) -> dict:
    """The reduction, on plain data.

    ``device_ops`` / ``device_modules``: device name -> list of
    ``(name, start_ns, end_ns)``; ``host_spans``: ``(name, start_ns,
    end_ns)`` of the benchmark's spans.  The window runs from the first
    host span's start to the last one's end."""
    if not host_spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    lo = min(s for _, s, _ in host_spans)
    hi = max(e for _, _, e in host_spans)
    window = (hi - lo) * 1e-9
    busy, kernel_ns, module_ns = [], defaultdict(float), defaultdict(float)
    op_ns = defaultdict(float)
    gaps = []
    spans = sorted((s, e, n) for n, s, e in host_spans)
    starts = [s for s, _, _ in spans]
    for dev, ops in device_ops.items():
        inside = []
        for name, s, e in ops:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            inside.append((s, e))
            base = op_name(name)
            if base not in CONTAINERS:
                op_ns[base] += e - s
            for k in kernels:
                if k in base:
                    kernel_ns[k] += e - s
        u = _union(inside)
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                j = bisect.bisect_right(starts, mid) - 1
                label = spans[j][2] if j >= 0 and spans[j][1] >= mid \
                    else "none"
                gaps.append((label, (b - a) * 1e-9))
    for dev, mods in device_modules.items():
        for name, s, e in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                for k in modules:
                    if k in name:
                        module_ns[k] += e - s
    n = max(len(device_ops), 1)
    busy_s = sum(busy) * 1e-9 / n
    gaps.sort(key=lambda g: -g[1])
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "kernel_s": {k: v * 1e-9 / n for k, v in kernel_ns.items()},
        "module_s": {k: v * 1e-9 / n for k, v in module_ns.items()},
        "device_ops": [[k, v * 1e-9 / n] for k, v in top],
        "idle_gaps": [[k, v] for k, v in gaps[:10]],
    }


def summarize(trace_dir: str, kernels=(), modules=()) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops, mods, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") \
                and plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.end_ns)
                       for ev in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    mods[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.end_ns)
                         for ev in line.events if ev.name in HOST_SPANS]
    return reduce_events(ops, mods, host, kernels, modules)

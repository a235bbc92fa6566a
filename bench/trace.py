"""Reduction of a ``jax.profiler`` trace to device busy time, program
time, the top device operations and the host's share of idle gaps.

``load`` reads the ``.xplane.pb`` a traced run wrote into a compact
dict of events (``compact`` form: what the committed test trace holds):
the device's operations, its program executions ("modules"), and the
benchmark's own host spans (``bench.*``, from
``jax.profiler.TraceAnnotation``). ``reduce`` works on that dict alone.
All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

# Where each platform's trace keeps its device work. On a TPU: the
# device plane's "XLA Ops" and "XLA Modules" lines. On the CPU (the
# rehearsal only; its numbers are never device numbers): the XLA
# client's thread and the executable's run spans on the host plane.
SELECT = {
    "tpu": {"plane": "/device:TPU:0", "ops": ("XLA Ops",),
            "modules": ("XLA Modules",)},
    "cpu": {"plane": "/host:CPU", "ops": ("tf_XLAPjRtCpuClient",),
            "modules": ("PjRtCpuExecutable::ExecuteHelper",)},
}
HOST_PREFIX = "bench."
NO_SPAN = "no_bench_span"
PROGRAM_PREFIX = {"tpu": "jit_run", "cpu": ""}


def load(log_dir: str, platform: str) -> Dict:
    """Events of the one trace under ``log_dir``, in compact form."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, found {paths}")
    sel = SELECT[platform]
    data = ProfileData.from_file(paths[0])
    ops: List[Tuple[str, int, int]] = []
    modules: List[Tuple[str, int, int]] = []
    host: List[Tuple[str, int, int]] = []
    lines: Dict[str, int] = {}
    for plane in data.planes:
        for line in plane.lines:
            is_dev = plane.name == sel["plane"]
            key = f"{plane.name}|{line.name}"
            lines.setdefault(key, 0)
            for ev in line.events:
                lines[key] += 1
                rec = (ev.name, int(ev.start_ns), int(ev.duration_ns))
                if plane.name == "/host:CPU" and ev.name.startswith(HOST_PREFIX):
                    host.append(rec)
                if not is_dev:
                    continue
                if platform == "cpu":
                    if line.name.startswith(sel["ops"]) and "::" not in ev.name \
                            and not ev.name.startswith("end:"):
                        ops.append(rec)
                    if ev.name in sel["modules"]:
                        modules.append(rec)
                elif line.name in sel["ops"]:
                    ops.append(rec)
                elif line.name in sel["modules"]:
                    modules.append(rec)
    return {"platform": platform, "ops": ops, "modules": modules, "host": host,
            "lines": lines}


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def op_name(text: str) -> str:
    """An operation's HLO name and result type from the trace's full
    instruction text: ``copy.100 = bf16[32,8,2048,8,128]``."""
    head, _, rest = text.partition(" = ")
    head = head.lstrip("%")
    if not rest:
        return head
    typ = "tuple" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    return f"{head} = {typ}"


def window_of(trace: Dict, name: str = "bench.window") -> Tuple[int, int]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == name]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {name} span in the trace, found {len(spans)}")
    return spans[0]


def reduce(trace: Dict, window: Tuple[int, int], top: int = 10) -> Dict:
    """Busy and idle time of the device inside ``window``.

    - ``busy_ns``: union of the device's operation intervals;
    - ``programs``: per program name, executions that start in the
      window and their summed duration (whole executions);
    - ``device_ops``: the ``top`` operation names by time in the window;
    - ``idle_gaps``: the ``top`` longest gaps between busy intervals,
      each named by the benchmark host span that covers most of it
      (``no_bench_span`` where none covers half of it: the loop waiting
      for frames or window joints, or scheduling);
    - ``idle_by_host``: all idle time, split by the host spans that
      overlap it, the rest under ``no_bench_span``.
    """
    lo, hi = window
    busy = _merge([c for n, s, d in trace["ops"]
                   if (c := _clip(s, s + d, lo, hi)) is not None])
    busy_ns = sum(e - s for s, e in busy)
    by_op: Dict[str, int] = {}
    for n, s, d in trace["ops"]:
        c = _clip(s, s + d, lo, hi)
        if c is not None:
            n = op_name(n)
            by_op[n] = by_op.get(n, 0) + c[1] - c[0]
    programs: Dict[str, List[int]] = {}
    for n, s, d in trace["modules"]:
        if lo <= s < hi:
            agg = programs.setdefault(n, [0, 0])
            agg[0] += 1
            agg[1] += d
    gaps = []
    idle_by_host: Dict[str, int] = {}
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted((s, s + d, n) for n, s, d in trace["host"]
                   if n != "bench.window")
    first = 0  # spans before this one end before the current gap
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        while first < len(spans) and spans[first][1] <= gs:
            first += 1
        cover: Dict[str, int] = {}
        k = first
        while k < len(spans) and spans[k][0] < ge:
            c = _clip(spans[k][0], spans[k][1], gs, ge)
            if c is not None:
                cover[spans[k][2]] = cover.get(spans[k][2], 0) + c[1] - c[0]
            k += 1
        # Each span is charged the idle time it overlaps; the rest of
        # the gap is the loop's own (waiting for frames or a window
        # joint, or scheduling). A gap is named by what covers most of it.
        for n, ns in cover.items():
            idle_by_host[n] = idle_by_host.get(n, 0) + ns
        rest = (ge - gs) - sum(cover.values())
        idle_by_host[NO_SPAN] = idle_by_host.get(NO_SPAN, 0) + max(rest, 0)
        label = max(cover, key=cover.get) if cover else NO_SPAN
        if cover.get(label, 0) < rest:
            label = NO_SPAN
        gaps.append((ge - gs, label))
    gaps.sort(reverse=True)
    return {
        "window_ns": hi - lo,
        "busy_ns": busy_ns,
        "programs": programs,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [(label, ns) for ns, label in gaps[:top]],
        "idle_by_host": idle_by_host,
    }


def program_time(red: Dict, platform: str) -> Tuple[int, int]:
    """(executions, summed ns) of the served programs in the window."""
    prefix = PROGRAM_PREFIX[platform]
    n = t = 0
    for name, (count, ns) in red["programs"].items():
        if name.startswith(prefix):
            n += count
            t += ns
    return n, t

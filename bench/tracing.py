"""Profiler trace of the measured window, and its reduction to numbers.

``record(dir)`` wraps the window in ``jax.profiler`` with the Python tracer
off; the harness marks the window itself with a ``bench.window``
annotation and its calls into each layer with ``bench.<layer>``
annotations.  ``load(dir)`` reads the ``.xplane.pb`` the profiler wrote
into plain tuples, and ``reduce`` turns those into:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices;
- ``window_s``: the length of the ``bench.window`` annotation;
- ``op_s``: device self seconds per operation (its HLO text): its time
  less that of the operations nested in it, as a loop holds its body,
  summed over devices; ``top_ops`` the largest under a short name;
- ``gaps``: idle device seconds on the first device, summed by the
  innermost ``bench.*`` annotation that covers each gap's midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    # per device: [(name, start_ns, end_ns)]
    device_ops: list = field(default_factory=list)
    # host annotations: [(name, start_ns, end_ns)]
    spans: list = field(default_factory=list)


@contextlib.contextmanager
def record(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = [l for l in lines if l.name == OPS_LINE]
            tr.device_ops.append([
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for l in ops for e in l.events])
        elif plane.name.startswith("/host:"):
            tr.spans += [
                (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                for l in lines for e in l.events
                if e.name.startswith("bench.")]
    return tr


def short_name(hlo: str) -> str:
    """``%fusion.9 = f32[8,4]{1,0} fusion(...)`` -> ``fusion.9 fusion
    f32[8,4]``; a Pallas kernel's kind reads ``tpu_custom_call``."""
    name, _, rest = hlo.partition(" = ")
    kind = re.search(r" ([a-z][a-z-]*)\(", " " + rest)
    kind = kind.group(1) if kind else ""
    if 'custom_call_target="tpu_custom_call"' in rest:
        kind = "tpu_custom_call"
    shape = rest.split("{")[0].split(" ")[0] if rest else ""
    return " ".join(x for x in (name.lstrip("%"), kind, shape) if x)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops):
    """[(name, self_ns)] for intervals on one line: each interval's length
    less the lengths of the intervals directly nested in it."""
    out, stack = [], []          # stack: [index into out, end]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            out[stack[-1][0]][1] -= e - s
        out.append([name, e - s])
        stack.append((len(out) - 1, e))
    return out


def reduce(tr: Trace, top: int = 10) -> dict | None:
    """Busy, window, per-op and per-gap seconds, or None where the trace
    holds no window or no device operation."""
    wins = [(s, e) for n, s, e in tr.spans if n == WINDOW]
    if not wins or not any(tr.device_ops):
        return None
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    busy, op_s = [], {}
    first_busy = None
    for ops in tr.device_ops:
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops
                   if e > w0 and s < w1]
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        inside = [(n, s, e) for n, s, e in ops if s >= w0 and e <= w1]
        for name, t in self_times(inside):
            op_s[name] = op_s.get(name, 0) + t
    gaps: dict[str, int] = {}
    inner = [(n, s, e) for n, s, e in tr.spans if n != WINDOW]
    edges = [w0] + [t for iv in first_busy for t in iv] + [w1]
    for lo, hi in zip(edges[0::2], edges[1::2]):
        if hi <= lo:
            continue
        mid = (lo + hi) // 2
        cover = [(s, n) for n, s, e in inner if s <= mid < e]
        label = max(cover)[1] if cover else "host.other"
        gaps[label] = gaps.get(label, 0) + (hi - lo)
    ns = 1e-9
    return dict(
        busy_s=sum(busy) / len(busy) * ns,
        window_s=(w1 - w0) * ns,
        op_s={k: v * ns for k, v in op_s.items()},
        top_ops=[[short_name(k), v * ns] for k, v in _largest(op_s, top)],
        gaps=[[k, v * ns] for k, v in _largest(gaps, top)])


def _largest(d: dict, top: int) -> list:
    return sorted(d.items(), key=lambda kv: -kv[1])[:top]

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<traffic>.json``, its limits in
``bench/workloads/<cell>.json``, the runner of its mode in
``bench/modes/<mode>.py``, each per-layer metric's reader in
``bench/metrics/<metric>.py`` and the chip's peaks in ``bench/peaks.json``.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.  ``--rehearse`` is for tests and CPU
rehearsals only: it skips that look, shrinks the graph as the traffic file
says, and marks the result ``"rehearsal": true``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


class SetupError(RuntimeError):
    """The cell cannot be run here: no result is printed."""


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SetupError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _module(path: Path):
    if not path.is_file():
        raise SetupError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its BENCHMARK.json entry, configuration,
    traffic, limits and metric lists."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}.get(name)
    if cell is None:
        raise SetupError(f"no cell {name!r} in BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[cell["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = _json(root / "bench" / "workloads" / f"{name}.json")["limits"]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return dict(name=name, chips=cell["chips"], config=config,
                traffic=traffic, limits=limits,
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


class Context:
    """What a mode's runner gets: the cell, the run's arguments, and the
    window's instruments."""

    def __init__(self, cell: dict, args, peaks: dict | None):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = dict(cell["traffic"])
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.peaks = peaks
        self.t_start = T_START
        self.reduced_trace = None
        if self.rehearse:
            self.traffic.update(self.traffic.get("rehearse", {}))
            g = dict(self.traffic["graph"])
            g["scale"] = self.traffic.get("scale", g["scale"])
            self.traffic["graph"] = g
        if self.trace:
            self.seconds = min(self.seconds,
                               self.traffic.get("trace_seconds", self.seconds))

    def annotate(self, name: str):
        """A host span on the profiler's clock (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced whole when ``--trace 1``."""
        if not self.trace:
            yield
            return
        import jax
        from bench import tracing
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            with tracing.record(log_dir):
                with jax.profiler.TraceAnnotation(tracing.WINDOW):
                    yield
            self.reduced_trace = tracing.reduce(tracing.load(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        cell = resolve(args.workload)
        mode = _module(BENCH / "modes" / f"{cell['traffic']['mode']}.py")
        readers = {m["name"]: _module(BENCH / "metrics" / f"{m['name']}.py")
                   for m in cell["per_layer"]} if args.trace else {}
        import jax
        devices = jax.devices()
        dev = devices[0]
        if not args.rehearse:
            if dev.platform != "tpu":
                raise SetupError(f"no TPU here (JAX found {dev.platform})")
            if len(devices) < cell["chips"]:
                raise SetupError(f"the cell needs {cell['chips']} chips, "
                                 f"JAX found {len(devices)}")
        peaks = (load_peaks(dev.device_kind) if dev.platform == "tpu"
                 else None)
        from repro import compile_cache
        cache = "off" if args.rehearse else compile_cache.enable()
        print(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
              f"compile cache {cache}", flush=True)
    except (SetupError, KeyError) as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2

    ctx = Context(cell, args, peaks)
    out = mode.run(ctx)
    from bench import compare
    correct, checks = compare.verdict(out["checks"], cell["limits"])
    print("readings " + json.dumps(out["checks"]), flush=True)
    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = readers[m["name"]].read(ctx, out)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    else:
        metrics = {m["name"]: dict(value=float(out["e2e"][m["name"]]),
                                   unit=m["unit"])
                   for m in cell["end_to_end"]}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices),
                  memory_peak_bytes=out["memory_peak_bytes"])
    line = dict(correct=bool(correct),
                attempted=int(out["attempted"]), failed=int(out["failed"]),
                metrics=metrics, device=device)
    rt = ctx.reduced_trace
    if args.trace and rt is not None:
        from bench import tracing
        top = sorted(rt["op_s"].items(), key=lambda kv: -kv[1])[:30]
        print("trace ops " + json.dumps(
            [[tracing.short_name(k), v] for k, v in top]), flush=True)
        device.update(busy_s=rt["busy_s"], window_s=rt["window_s"])
        line["breakdown"] = dict(device_ops=rt["top_ops"],
                                 idle_gaps=rt["gaps"])
    if args.rehearse:
        line["rehearsal"] = True
    line["checks"] = [dict(name=n, value=v, limit=lim)
                      for n, v, lim in checks]
    for n, v, lim in checks:
        print(f"check {n} {v!r} limit {lim!r}", file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once, on the chip:

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compilation, warm-up of this cell's own
shapes), then a window of ``--seconds``, then the comparison with the
plain reference, then one JSON line: the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the run's spans and a profiler trace of the
window. Each number compared for ``correct`` is printed beside its limit
as the last lines of standard error and under ``checks``, the line's last
key.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. The cell, its configuration, its traffic mix, its
limits and its per-layer metrics are all found by name under ``bench/``
(see ``bench/common.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime would otherwise log under the system temp directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = Path(__file__).resolve().parents[1]
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.common import (ROOT, Spans, eprint, load_cell,  # noqa: E402
                          load_json, load_reader, log, metrics_of)



class NoChip(Exception):
    pass


class Ctx:
    """What a driver (``bench/drive_<path>.py``) gets for one run.

    The driver calls ``mark`` after each phase of set-up, ``start_window``
    when set-up is done, ``tick`` before each engine tick or training
    step, and ``end_window`` after the last.
    With ``--trace 1`` the profiler records ``TRACE_LEN`` seconds (at most
    half the window) from a tick boundary that many seconds before the
    window closes, to the first tick boundary after them or the last tick,
    inside one ``bench.window`` annotation: a whole window of a serving
    cell would be millions of device events. Stopping the profiler writes
    the trace out, which takes seconds; a serving cell's drain (requests
    due before the window closed, still decoding) waits for it, and the
    traced run reports no end-to-end metric.
    """

    TRACE_LEN = 8.0

    def __init__(self, cfg, mix, seed, seconds, trace, hooks, trace_dir):
        self.cfg, self.mix, self.seed, self.seconds = cfg, mix, seed, seconds
        self.trace, self.hooks, self.trace_dir = trace, hooks or {}, trace_dir
        self.spans = Spans(annotate=trace)
        self.setup_s = None
        self.t0 = None
        self._tracing = None  # the open bench.window annotation
        self._traced = None   # when the profiler started
        self.marks = []       # (phase of set-up, seconds since start)

    def mark(self, phase: str) -> None:
        self.marks.append((phase, time.perf_counter() - T_START))

    def start_window(self) -> None:
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - T_START
        log("set-up: " + ", ".join(f"{p} at {t:.3f} s" for p, t in
                                   self.marks + [("window", self.setup_s)]))

    def tick(self) -> None:
        if not self.trace or self.t0 is None:
            return
        import jax

        now = time.perf_counter()
        span = min(self.TRACE_LEN, self.seconds / 2)
        if self._traced is None and now - self.t0 >= self.seconds - span:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir))
            self._tracing = jax.profiler.TraceAnnotation("bench.window")
            self._tracing.__enter__()
            self._traced = time.perf_counter()
            log(f"profiler started {now - self.t0:.3f} s into the window, "
                f"in {self._traced - now:.3f} s")
        elif self._tracing is not None and now - self._traced >= span:
            self.end_window()

    def end_window(self) -> None:
        if self._tracing is not None:
            import jax

            t = time.perf_counter()
            self._tracing.__exit__(None, None, None)
            self._tracing = None
            jax.profiler.stop_trace()
            log(f"profiler stopped in {time.perf_counter() - t:.3f} s")


def check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, hooks=None):
    """One run of one cell; returns the result line's dict and the
    ``(name, value, limit)`` checks. ``hooks`` plant a fault or the
    control under the timed path (``bench.faults``); the benchmark's own
    runs pass none."""
    bench, cell, cfg, mix = load_cell(name, root)
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    peaks_all = load_json(root / "bench" / "peaks.json")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    check_device(cell["chips"])
    dev = jax.devices()[0]
    if dev.device_kind not in peaks_all["devices"]:
        raise SystemExit(f"bench: no peaks for device {dev.device_kind!r} "
                         f"in bench/peaks.json")
    peaks = peaks_all["devices"][dev.device_kind]
    trace_dir = root / ".bench_out" / "trace" / name
    ctx = Ctx(cfg, mix, seed, seconds, trace, hooks, trace_dir)
    ctx.mark("chip found")
    driver = importlib.import_module(f"bench.drive_{mix['path']}")
    res = driver.run(ctx)

    checks = [(k, float(v), float(limits[k])) for k, v in res["checks"]]
    correct = bool(res["ok"]) and res["failed"] == 0 and all(
        math.isfinite(v) and v <= lim for _, v, lim in checks)
    e2e, layer = metrics_of(bench, name)
    mem = res["memory"]
    log(f"memory: peak_bytes_in_use {mem['peak_bytes_in_use']}, largest "
        f"program by memory_analysis {mem['program_bytes']}")
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": {}, "device": {
                "platform": dev.platform, "kind": dev.device_kind,
                "count": cell["chips"],
                "memory_peak_bytes": max(mem.values())}}
    if not trace:
        vals = dict(res["e2e"], setup_s=ctx.setup_s)
        for m in e2e:
            line["metrics"][m["name"]] = {"value": vals[m["name"]],
                                          "unit": m["unit"]}
    else:
        from bench import trace_reduce

        red = trace_reduce.reduce_dir(trace_dir)
        rec = {"record": res["record"], "trace": red, "cfg": cfg,
               "peaks": peaks}
        for m in layer:
            v = load_reader(m["name"], root)(rec)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        line["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    return line, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        eprint("bench: the program (src/repro) is not in this checkout")
        return 2
    try:
        line, checks = run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    except NoChip as e:
        eprint(f"bench: {e}; nothing was run")
        return 2
    for k, v, lim in checks:
        eprint(f"check {k} {v!r} limit {lim!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

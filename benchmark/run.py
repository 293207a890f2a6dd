#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that this finds by the
names in ``BENCHMARK.json``: the configuration
(``benchmark/configs/<config>.json``), the traffic mix or job
(``benchmark/traffic/<traffic>.json``, whose ``driver`` names the file
under ``benchmark/drivers/`` that knows the entry point), and for each
per-layer metric its reader ``benchmark/layer_metrics/<metric>.py``.
No table of names lives in code: a later PR adds a cell, a mix, a
configuration or a metric by adding files and one entry.

The process that runs this is the one that holds the chip (thread-mode
head). Without a TPU, or with fewer chips than the cell asks for, it
exits 2 before any work and prints no result. The last line of stdout
is the result object; the numbers compared for ``correct`` are the
last lines of stderr and the last key of the result.

``--rehearsal <file>`` is for the tests under ``benchmark/tests/``
only: it merges tiny sizes from that file over the cell's
configuration and traffic and lets the run go on without a TPU. Every
metric of such a run is named ``cpu_rehearsal.<name>``, so that no
number from a CPU can pass for a device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark.common import deep_merge, load_json, passes, say  # noqa: E402

EXIT_NO_CHIP = 2


class Cell:
    """One run of one cell: what the drivers and the readers are
    given."""

    def __init__(self, bench: Dict[str, Any], args) -> None:
        by_name = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in by_name:
            raise SystemExit(f"run.py: no workload {args.workload!r} in "
                             f"BENCHMARK.json (has: {sorted(by_name)})")
        self.bench = bench
        self.workload = by_name[args.workload]
        self.name: str = self.workload["name"]
        self.chips: int = int(self.workload["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.workload["config"])
        self.config: Dict[str, Any] = load_json(cfg_entry["file"])
        self.traffic: Dict[str, Any] = load_json(
            "benchmark", "traffic", self.workload["traffic"] + ".json")
        self.rehearsal = bool(args.rehearsal)
        if args.rehearsal:
            over = load_json(args.rehearsal)
            self.config = deep_merge(self.config, over.get("config", {}))
            self.traffic = deep_merge(self.traffic,
                                      over.get("traffic", {}))
            self.chips = int(over.get("chips", self.chips))
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.peaks: Optional[Dict[str, float]] = None
        self.devices: List[Any] = []

    def scratch(self, *parts: str) -> str:
        """A fixed directory inside the checkout for what a run writes
        (trace, Trainer storage, logs)."""
        from ray_tpu._private.cache_dir import checkout_cache_dir

        return checkout_cache_dir("bench", self.name, *parts)

    def metric_applies(self, metric: Dict[str, Any]) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return True


def end_to_end_names(cell: Cell) -> List[str]:
    return [m["name"] for m in cell.bench["end_to_end"]
            if cell.metric_applies(m)]


def read_layer_metrics(cell: Cell, ctx: Dict[str, Any],
                       reported: List[str]) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of this cell through its own reader file.
    A reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in cell.bench["per_layer"]:
        # a metric without a list of cells follows the end-to-end
        # metric it moves
        if not cell.metric_applies(m) or (
                "workloads" not in m and m["moves"] not in reported):
            continue
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark.layer_metrics." + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is None:
            say("layer_metric", name=m["name"], value="nothing to read")
            continue
        if m["unit"] == "%" and not 0.0 <= value <= 100.0 + 1e-6:
            raise ValueError(f"{m['name']} reads {value} %: a share has "
                             "to lie within 0..100")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_block(cell: Cell, peak_bytes: int) -> Dict[str, Any]:
    dev = cell.devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(cell.devices),
            "memory_peak_bytes": int(peak_bytes)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", default="",
                    help="tests only: a file of tiny sizes; allows a run "
                         "without a TPU and renames every metric")
    args = ap.parse_args(argv)
    cell = Cell(load_json("BENCHMARK.json"), args)

    import jax

    from ray_tpu._private.cache_dir import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if not cell.rehearsal and devices[0].platform != "tpu":
        print(f"run.py: no TPU: jax reports platform "
              f"{devices[0].platform!r} ({devices[0].device_kind}); the "
              f"benchmark only measures on the chip", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chip(s), jax "
              f"reports {len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP
    cell.devices = list(devices[:cell.chips])
    peaks = load_json("benchmark", "peaks.json")
    kind = devices[0].device_kind
    if kind in peaks:
        cell.peaks = peaks[kind]
    elif not cell.rehearsal:
        raise KeyError(f"device kind {kind!r} is not in "
                       f"benchmark/peaks.json: add its peaks with their "
                       f"source, there is no default")

    # what the program logs (an engine step that failed, a resumed
    # stream) otherwise only reaches the session's gcs.out
    to_stderr = logging.StreamHandler(sys.stderr)
    to_stderr.setLevel(logging.WARNING)
    logging.getLogger("ray_tpu").addHandler(to_stderr)

    say("start", workload=cell.name, seed=cell.seed, seconds=cell.seconds,
        trace=int(cell.trace), platform=devices[0].platform, kind=kind,
        chips=cell.chips, compile_cache=cache_dir,
        rehearsal=cell.rehearsal)
    driver = importlib.import_module(
        "benchmark.drivers." + cell.traffic["driver"])
    result = driver.run(cell, T_PROCESS_START)

    # result: end_to_end {name: value}, attempted, failed, checks
    # [(name, value, limit)], memory_peak_bytes, ctx for the readers
    e2e = {"setup_s": result["setup_s"], **result["end_to_end"]}
    wanted = end_to_end_names(cell)
    missing = [n for n in wanted if n not in e2e]
    if missing:
        raise KeyError(f"driver {cell.traffic['driver']} did not report "
                       f"{missing}")
    units = {m["name"]: m["unit"] for m in
             cell.bench["end_to_end"] + cell.bench["per_layer"]}
    device = device_block(cell, result["memory_peak_bytes"])
    line: Dict[str, Any] = {}
    if cell.trace:
        ctx = result["ctx"]
        summary = ctx.get("trace_summary")
        metrics = read_layer_metrics(cell, ctx, wanted)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {n: {"value": float(e2e[n]), "unit": units[n]}
                   for n in wanted}
    for n in wanted:
        say("end_to_end", name=n, value=float(e2e[n]), unit=units[n])
    if cell.rehearsal:
        metrics = {"cpu_rehearsal." + k: v for k, v in metrics.items()}
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in result["checks"]}
    line = {"correct": passes(result["checks"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": device, **line, "checks": checks}
    sys.stdout.flush()
    for name, value, limit in result["checks"]:
        print(f"check {name}: value {value!r} limit {limit!r} "
              f"{'ok' if passes([(name, value, limit)]) else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

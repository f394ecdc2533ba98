#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for. Without a TPU, or with fewer chips than the cell needs,
it prints no result and exits non-zero; it never falls back to the CPU.

What it does: set-up (imports, weights, warm-up of every shape the cell
uses, programs from the persistent compilation cache in ``.jax_cache/``),
then the measured window of ``--seconds``, then the comparison with the
plain reference that decides ``correct``. Anything compiled inside the
window fails the run. With ``--trace 1`` the window runs under the
profiler and the result carries the cell's per-layer metrics and a
breakdown of device time and idle gaps; with ``--trace 0`` it carries the
end-to-end metrics. The last lines of standard error, and the last key
of the result line, give each compared number beside its limit.

Everything is found by name from ``BENCHMARK.json``; a later change adds
a cell, a configuration or a metric by adding files and entries, and
edits none:

* a configuration ``<config>`` is ``bench/configs/<config>.json`` (the
  published sizes, and ``driver``: the module under ``bench/drivers/``
  that serves it) with its plain reference ``bench/configs/<config>.ref.py``
  beside it, and an entry under ``configs``;
* a traffic mix ``<traffic>`` is the data file ``bench/traffic/<traffic>.json``
  that the driver's one generator reads (lengths, batch, rate, burst);
* a cell is an entry under ``workloads`` naming a configuration and a
  traffic mix, with its limits in ``bench/limits/<cell>.json``, set from
  the readings of sound runs and of the control (``bench/calibrate.py``);
* a per-layer metric ``<metric>`` is ``bench/metrics/<metric>.py``, whose
  ``read(ctx)`` returns the number or ``None`` where the run holds nothing
  to read, and an entry under ``per_layer``;
* a device is a row of ``bench/peaks.json``; a device missing there is an
  error;
* a new kind of cell (one that is not an LM served by ``ServeEngine``)
  brings its own driver module with a ``run_cell`` of the same signature.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    config_name: str
    config_file: Path
    config: dict
    traffic: dict
    chips: int
    limits: dict


class BenchError(RuntimeError):
    pass


def make_cell(name: str, config_name: str, config_file: Path,
              traffic: str, chips: int) -> Cell:
    """A cell from its files; its limits are ``{}`` until they are set."""
    limits_file = BENCH / "limits" / f"{name}.json"
    return Cell(
        name=name, config_name=config_name, config_file=config_file,
        config=json.loads(config_file.read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{traffic}.json").read_text()),
        chips=chips,
        limits=(json.loads(limits_file.read_text())["limits"]
                if limits_file.exists() else {}),
    )


def load_cell(name: str, root: Path = ROOT) -> tuple:
    """The cell ``name`` and the whole of ``BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {', '.join(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cell = make_cell(name, w["config"], root / conf["file"], w["traffic"],
                     w["chips"])
    if not cell.limits:
        raise BenchError(f"no limits in bench/limits/{name}.json")
    return cell, spec


def _load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e: dict) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e.get(metric.get("moves"), True)


def select_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports."""
    e2e = {m["name"]: _reports(m, cell, {}) for m in spec["end_to_end"]}
    if not trace:
        return [m for m in spec["end_to_end"] if e2e[m["name"]]]
    return [m for m in spec["per_layer"] if _reports(m, cell, e2e)]


def judge(checks: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number at or under its
    limit."""
    rows = [(k, v, limits[k]) for k, v in checks.items()]
    return all(v <= lim for _, v, lim in rows), rows


def run(cell: Cell, spec: dict, seed: int, seconds: float, trace: bool,
        device: dict, peaks: dict) -> tuple:
    """Drive the cell; returns its result line (as a dict) and the notes
    for standard error."""
    from bench.compiles import CompileCounter

    counter = CompileCounter()
    driver = importlib.import_module(f"bench.drivers.{cell.config['driver']}")
    out = driver.run_cell(cell, seed, seconds, trace, T_START, counter,
                          trace_dir=ROOT / ".bench_out" / "trace")
    if out["compiles_in_window"]:
        raise BenchError(f"{out['compiles_in_window']} XLA compiles inside "
                         f"the measured window")
    metrics = {}
    if trace:
        ts = out["trace"]
        ctx = SimpleNamespace(cfg=cell.config, traffic=cell.traffic,
                              peaks=peaks, chips=cell.chips,
                              window_s=out["window_s"], work=out["work"],
                              trace=ts)
        for m in select_metrics(spec, cell.name, trace):
            reader = _load_file(BENCH / "metrics" / f"{m['name']}.py",
                                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=ts.busy_s, window_s=ts.window_s)
    else:
        for m in select_metrics(spec, cell.name, trace):
            metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                  "unit": m["unit"]}
    correct, rows = judge(out["checks"], cell.limits)
    correct = correct and out["failed"] == 0
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": dict(device,
                             memory_peak_bytes=out["memory_peak_bytes"])}
    if trace:
        result["breakdown"] = out["trace"].breakdown()
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return result, out["notes"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program, and this package by its name; never this directory
    # itself, whose trace.py would shadow the standard library's
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    # one persistent compilation cache at a fixed path inside the
    # checkout, whatever the environment names
    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    try:
        cell, spec = load_cell(args.workload)
        peaks_all = json.loads((BENCH / "peaks.json").read_text())["devices"]
        import jax

        jax.config.update("jax_compilation_cache_dir", str(cache))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devs = jax.devices()
        d0 = devs[0]
        if d0.platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is {d0.platform}; "
                             f"this benchmark runs on the chip only")
        if len(devs) < cell.chips:
            raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX "
                             f"finds {len(devs)}")
        if d0.device_kind not in peaks_all:
            raise BenchError(f"device {d0.device_kind!r} is not in "
                             f"bench/peaks.json")
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs)}
        result, notes = run(cell, spec, args.seed, args.seconds,
                             bool(args.trace), device,
                             peaks_all[d0.device_kind])
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for note in notes:
        print(note, file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

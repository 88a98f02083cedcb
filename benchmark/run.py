#!/usr/bin/env python3
"""One run of one benchmark cell, on the machine it is started on.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process, and it
belongs to the worker that ``ray_tpu.init()`` + ``JaxTrainer.fit()``
grant it to — the path a user's job takes.  Everything that belongs to
one cell is data found by the names in ``BENCHMARK.json``:

- the cell's configuration      ``benchmark/configs/<config>.json``
- its job (the traffic mix)     ``benchmark/jobs/<traffic>.json``
- the loop the job names        ``benchmark/loops/<loop>.py``  (``loop(config)``)
- each per-layer metric         ``benchmark/layer_metrics/<metric>.py``  (``read(run)``)

so a later PR adds a cell, a configuration, a job or a metric by adding
files and entries.  This file holds no name of any of them.

The last line of standard output is one JSON object (``correct``,
``compared`` — every number ``correct`` compares, beside its limit —
``attempted``, ``failed``, ``metrics``, ``device``, and ``breakdown`` in a
traced run).  No chip, too few chips, an unknown device kind, a failed
``fit()`` or a missing program: a non-zero exit and no result line.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start: set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEADLINE_S = 340  # the driver allows a run 360 s (1200 s when it compiles)
COLD_DEADLINE_S = 1150


class RunFailure(Exception):
    """The run cannot give a result; the message is the reason."""


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def _module(path: str):
    """A file of the benchmark, loaded by its path (metric names carry
    dots, so they are no module names)."""
    spec = importlib.util.spec_from_file_location(
        "_benchmark_" + os.path.basename(path).replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise RunFailure(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(bench: dict, group: str, workload: str) -> list:
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def _worker_loop(config):
    """Runs in the trainer's worker; the loop itself is a file named by
    the job (workers inherit the driver's ``sys.path``, which has ROOT)."""
    import importlib

    importlib.import_module(
        "benchmark.loops." + config["job"]["loop"]).loop(config)


def run_cell(cell: dict, args) -> dict:
    """``ray_tpu.init()`` then ``JaxTrainer.fit()`` with one worker that
    owns the cell's chips; returns what the loop reported last."""
    import ray_tpu
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    conf = _json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    job = _json(os.path.join(HERE, "jobs", cell["traffic"] + ".json"))
    peaks = _json(os.path.join(HERE, "peaks.json"))
    if not os.path.isfile(os.path.join(HERE, "loops", job["loop"] + ".py")):
        raise RunFailure(f"no loop benchmark/loops/{job['loop']}.py")
    ray_tpu.init()  # chips are detected from the device nodes, no JAX
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if have < cell["chips"]:
            raise RunFailure(
                f"no accelerator: ray_tpu.init() found {have} TPU chip(s) "
                f"on this machine, the cell needs {cell['chips']}")
        fit_called = time.time()
        result = JaxTrainer(
            _worker_loop,
            train_loop_config={
                "conf": conf, "job": job, "chips": cell["chips"],
                "peaks": peaks,
                "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "trace_dir": args.trace_dir},
            scaling_config=ScalingConfig(
                num_workers=1, tpu_chips_per_worker=cell["chips"]),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RunFailure(f"fit() failed: {result.error}")
    return {"worker": result.metrics, "conf": conf, "job": job,
            "chips": cell["chips"], "process_start": T_START,
            "fit_called": fit_called,
            "peak": peaks[result.metrics["device"]["kind"]]}


def result_line(bench: dict, cell: dict, run: dict, trace: bool) -> dict:
    w = run["worker"]
    loop = _module(os.path.join(HERE, "loops", run["job"]["loop"] + ".py"))
    e2e = run["end_to_end"] = loop.end_to_end(run)
    metrics = {}
    if trace:
        for m in cell_metrics(bench, "per_layer", cell["name"]):
            value = _module(os.path.join(
                HERE, "layer_metrics", m["name"] + ".py")).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = dict(w["device"], memory_peak_bytes=max(w["peak_bytes_in_use"]))
    line = {"correct": loop.correct(run), "compared": loop.compared(run),
            "attempted": w["window"]["attempted"],
            "failed": w["window"]["failed"], "metrics": metrics,
            "device": device}
    if trace and w["trace"]:
        devs = w["trace"]["devices"]
        device["busy_s"] = sum(d["busy_s"] for d in devs) / len(devs)
        device["window_s"] = sum(d["window_s"] for d in devs) / len(devs)
        idlest = max(devs, key=lambda d: d["idle_s"])
        line["breakdown"] = {"device_ops": idlest["device_ops"],
                             "idle_gaps": idlest["idle_gaps"]}
    return line


def _stop_children(grace_s: float = 10.0) -> None:
    """Nothing this run started may outlive it."""
    me = os.getpid()

    def children():
        out = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/stat") as f:
                    stat = f.read()
            except OSError:
                continue
            if int(stat.rsplit(")", 1)[1].split()[1]) == me:
                out.append(int(pid))
        return out

    deadline = time.monotonic() + grace_s
    while True:
        for pid in children():
            try:
                if os.waitpid(pid, os.WNOHANG)[0] == 0 \
                        and time.monotonic() > deadline:
                    os.kill(pid, signal.SIGKILL)
            except (ChildProcessError, ProcessLookupError):
                pass
        if not children():
            return
        time.sleep(0.1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here (default: a "
                         "temporary directory, removed after reduction)")
    ap.add_argument("--details", default=None,
                    help="also write everything the worker reported, as "
                         "JSON, to this file")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    def expired():
        print("benchmark: FAILED: no end in time", file=sys.stderr,
              flush=True)
        _stop_children(grace_s=0.0)
        os._exit(3)

    line = watchdog = None
    try:
        try:
            from ray_tpu._private.device_env import compile_cache_dir
        except ImportError as e:
            raise RunFailure(f"the program is not in this checkout: {e}")
        # A first run in a checkout compiles and may take 1200 s; any
        # later one finds its programs in the cache and has 360 s.
        cold = not os.path.isdir(compile_cache_dir())
        watchdog = threading.Timer(COLD_DEADLINE_S if cold else DEADLINE_S,
                                   expired)
        watchdog.daemon = True
        watchdog.start()
        bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = find_cell(bench, args.workload)
        run = run_cell(cell, args)
        line = result_line(bench, cell, run, bool(args.trace))
        if args.details:
            with open(args.details, "w") as f:
                json.dump(run, f, indent=1)
    except RunFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
    finally:
        _stop_children()
        if watchdog is not None:
            watchdog.cancel()
    if line is None:
        return 1
    if "jax" in sys.modules:
        print("benchmark: FAILED: the driver process imported JAX",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The ``train_moe`` loop: what a cell of kind ``"loop": "train_moe"`` runs.

``loops/train.py`` for a mixture-of-experts configuration — the same
set-up, window and traced steps through the same entry points
(``JaxTrainer.fit()`` hands ``loop(config)`` to the worker that owns the
chips), with what the dense loop cannot express:

- the plain reference is ``reference/olmoe.py`` (``train.py`` imports
  ``reference/decoder.py`` by name), and the loss compared is the TRAINING
  loss: cross-entropy plus the load-balancing and router z-losses;
- every step's ``moe_dropped`` and ``moe_load_max_over_mean`` are fetched
  with its loss (one step late, one transfer), and ``correct`` also
  requires that no step of the window dropped an assignment;
- the traced run reduces the trace by step scope and kernel name
  (``trace_scopes.py``) before the file is deleted, and ``flash_s`` is
  the time of the kernels named ``flash_*`` alone: ``trace_reduce.py``
  counts every Mosaic custom call, which in this program includes the
  experts' grouped products.

What it shares with ``loops/train.py`` it imports from there; the body of
``measure`` is a copy (it is one function there).
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List

from benchmark.loops.train import (  # noqa: F401 — end_to_end is the loop's
    ANNOTATIONS, end_to_end, program_config, require_chips)
from benchmark.loops import train


def measure(config: Dict[str, Any], devs, marks: Dict[str, float]
            ) -> Dict[str, Any]:
    """Set up, check against the reference, warm up, run the window and
    (traced run) trace a few steps.  Returns plain data for ``run.py``."""
    import jax
    import numpy as np
    from jax import monitoring
    from jax.profiler import TraceAnnotation

    from benchmark.reference import olmoe
    from ray_tpu.air import session
    from ray_tpu.models.llama import loss_fn
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    events = {"hits": 0, "misses": 0, "compiles": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            events["hits"] += 1
        elif event.endswith("/cache_misses"):
            events["misses"] += 1

    def on_duration(event, duration, **_):
        if event.endswith("/backend_compile_duration"):
            events["compiles"] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)

    conf, job, seed = config["conf"], config["job"], config["seed"]
    if job["mesh"]:
        raise ValueError("the train_moe loop runs on one chip; an ep mesh "
                         "needs a cell of its own (PERF.md §7)")
    cfg = program_config(conf)
    rows, seq = job["rows"], job["seq"]
    opt = default_optimizer()
    marks["imports"] = time.time()
    state = jax.block_until_ready(
        init_train_state(jax.random.PRNGKey(seed), cfg, opt))
    marks["state_init"] = time.time()

    # The program's step-0 training loss against the plain reference, on a
    # seeded sample of the cell's own sequence length, outside the window.
    # At step 0 every norm's weight is 1 and what it norms has unit RMS
    # already, so a missing QK-norm would not show: the check runs on a
    # copy whose norm weights are drawn from the seed (the rest is shared).
    norm_rng = np.random.default_rng([seed, 2])

    def drawn(path, a):
        if not str(getattr(path[-1], "key", "")).endswith("norm"):
            return a
        return (a.astype(np.float32) * norm_rng.uniform(
            0.5, 1.5, a.shape).astype(np.float32)).astype(a.dtype)

    check_params = jax.tree_util.tree_map_with_path(drawn, state.params)
    sample = jax.device_put(
        np.random.default_rng([seed, 1]).integers(
            0, cfg.vocab_size, (job["check_rows"], seq + 1), dtype=np.int32),
        devs[0])
    program_loss, program_parts = jax.jit(
        lambda p, t: loss_fn(p, {"tokens": t}, cfg))(check_params, sample)
    program_loss = float(program_loss)
    program_parts = {k: float(v) for k, v in program_parts.items()}
    reference = olmoe.loss_parts(check_params, sample, conf)
    reference = {k: float(reference[k])
                 for k in ("total", "loss", "aux_loss", "z_loss")}
    del check_params
    del sample
    marks["reference_check"] = time.time()

    rng = np.random.default_rng([seed, 0])

    def new_batch():
        with TraceAnnotation("make_batch"):
            tokens = rng.integers(0, cfg.vocab_size, (rows, seq + 1),
                                  dtype=np.int32)
        with TraceAnnotation("device_put"):
            return {"tokens": jax.device_put(tokens, devs[0])}

    step_fn = make_train_step(cfg, opt)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, new_batch()).compile()
    step_load_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    marks["step_load"] = time.time()

    losses: List[float] = []
    dropped: List[float] = []
    load: List[float] = []
    pending = None

    def fetch_pending():
        nonlocal pending
        if pending is not None:
            got = jax.device_get({k: pending[k] for k in (
                "loss", "moe_dropped", "moe_load_max_over_mean")})
            losses.append(float(got["loss"]))
            dropped.append(float(got["moe_dropped"]))
            load.append(float(got["moe_load_max_over_mean"]))
            session.report({"step": len(losses), "loss": losses[-1]})
            pending = None

    def one_step():
        nonlocal state, pending
        batch = new_batch()
        with TraceAnnotation("step"):
            state, metrics = compiled(state, batch)
        with TraceAnnotation("report"):
            fetch_pending()
        pending = metrics

    def run_steps(n):
        for _ in range(n):
            one_step()
        fetch_pending()
        jax.block_until_ready(state)

    run_steps(job["warmup_steps"])

    # The measured window: whole steps until --seconds have passed.
    compiles_before = events["compiles"]
    n_warm = len(losses)
    attempted = failed = 0
    error = None
    window_start = time.time()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < config["seconds"]:
        attempted += 1
        try:
            one_step()
        except Exception as e:  # noqa: BLE001 — counted, reported, fatal
            failed, error = failed + 1, repr(e)
            break
    if error is None:
        fetch_pending()
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    window_losses = losses[n_warm:]
    window_dropped, window_load = sum(dropped[n_warm:]), load[n_warm:]
    failed += sum(1 for x in window_losses if not np.isfinite(x))
    compiles_in_window = events["compiles"] - compiles_before

    trace = None
    if config["trace"] and error is None:
        trace = _traced_steps(config, run_steps, f"jit_{step_fn.__name__}")

    return {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "loop_start": marks["loop_start"],
        "setup_marks": marks,
        "window_start": window_start,
        "check": {"program_loss": program_loss,
                  "reference_loss": reference["total"],
                  "rtol": olmoe.loss_rtol(job["check_rows"] * seq),
                  "program_parts": program_parts,
                  "reference_parts": reference},
        "window": {"attempted": attempted, "failed": failed,
                   "steps": len(window_losses),
                   "tokens": len(window_losses) * rows * seq,
                   "elapsed_s": elapsed, "error": error,
                   "first_loss": window_losses[0] if window_losses else None,
                   "last_loss": window_losses[-1] if window_losses else None,
                   "compiles": compiles_in_window,
                   "moe_dropped": window_dropped,
                   "moe_load_max_over_mean": window_load},
        "compile": {"step_load_s": step_load_s,
                    "cache_hits": events["hits"],
                    "cache_misses": events["misses"],
                    "cache_dir": jax.config.jax_compilation_cache_dir,
                    "argument_bytes": mem.argument_size_in_bytes,
                    "temp_bytes": mem.temp_size_in_bytes},
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devs],
        "trace": trace,
    }


def _traced_steps(config, run_steps, step_module):
    """As ``loops/train.py``: one lead-in step and ``traced_steps`` more
    under the profiler, reduced here.  Beside ``trace_reduce``'s numbers
    each device gets ``scopes``, ``kernels`` and ``unscoped_s`` (seconds a
    step), and its ``flash_s`` becomes the flash kernels' time alone."""
    import jax

    from benchmark import trace_reduce, trace_scopes

    keep = config.get("trace_dir")
    trace_dir = keep or tempfile.mkdtemp(prefix="benchmark-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the loop's own annotations suffice
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            run_steps(config["job"]["traced_steps"] + 1)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        path = max(files, key=os.path.getmtime)
        trace = trace_reduce.reduce_file(path, step_module=step_module,
                                         annotations=ANNOTATIONS)
        if trace is None:
            return None
        return by_scope(trace, trace_scopes.reduce_file(
            path, step_module=step_module))
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)


def by_scope(trace: Dict[str, Any], scoped: Dict[int, Dict[str, Any]]
             ) -> Dict[str, Any]:
    """``trace_reduce``'s reduction with ``trace_scopes``' laid over it."""
    for d in trace["devices"]:
        s = scoped.get(d["device"])
        if s is None:
            continue
        d["all_kernels_s"] = d["flash_s"]
        d["flash_s"] = s["flash_s"] * s["steps"]
        d["scopes"], d["kernels"] = s["scopes"], s["kernels"]
        d["unscoped_s"] = s["unscoped_s"]
    return trace


def correct(run: Dict[str, Any]) -> bool:
    """Driver side: ``loops/train.py``'s four conditions on the training
    loss (reference within its tolerance; finite losses and no failed
    step; even memory; nothing compiled in the window), and (e) no step
    of the window dropped an assignment."""
    return bool(train.correct(run)
                and run["worker"]["window"]["moe_dropped"] == 0)


def loop(config: Dict[str, Any]) -> None:
    marks = {"loop_start": time.time()}
    import jax

    from ray_tpu.air import session

    marks["import_jax"] = time.time()
    devs = jax.devices()
    marks["devices"] = time.time()
    require_chips(devs, config["chips"], config["peaks"])
    session.report(measure(config, devs, marks))

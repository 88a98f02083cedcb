"""The ``train`` loop: what a cell of kind ``"loop": "train"`` runs.

``loop(config)`` is the ``train_loop_per_worker`` that ``run.py`` hands to
``JaxTrainer.fit()``.  It runs in the worker that owns the cell's chips —
the only process of the run that imports JAX — and is written the way a
user's loop is: build the model's config, ``init_train_state`` from the
seed (made on the device, born sharded on a mesh), compile the one step
program, then step on a NEW batch every time (numpy on the host, then
``device_put``, as a loader hands it over), calling ``session.report``
once a step.  The loss is fetched one step late, so the fetch never
drains the device.

It runs EVERY training cell and holds no model's name.  What differs
between models is named by the configuration file: ``"reference"`` (a
module under ``benchmark/reference/``) supplies ``loss_parts`` (the loss
the check compares, and its parts where it has them), ``loss_rtol`` (the
tolerance) and ``STEP_METRICS`` (what the window fetches with each loss,
how it is kept, and what ``correct`` requires of it); optional
``"scopes"`` and ``"kernels"`` add to the names the trace is reduced by.

Shape copied from ``chip_smoke.py``'s ``_train_loop`` (PR 21), not
imported: the yardstick may not move when the program does.  From the
program it takes only the system under test (``ray_tpu.models.llama``,
``ray_tpu.train.core``, ``ray_tpu.parallel``, ``ray_tpu.air.session``).
"""

from __future__ import annotations

import glob
import importlib
import math
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List

ANNOTATIONS = ("make_batch", "device_put", "step", "report")
# The program's spans a step can run under (``ray_tpu.util.tracing``; on
# the trace's host lines where they opened while the profiler ran).
PROGRAM_SPANS = ("session.report", "train.loop")
KEEP = {"sum": sum, "max": max}


def require_chips(devs, chips: int, peaks: Dict) -> None:
    """Nothing falls back to the CPU: the worker holds exactly the
    cell's chips, of a kind the peaks table knows, or the run fails."""
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or len(devs) != chips:
        raise RuntimeError(
            f"the cell needs {chips} TPU chip(s); this worker sees "
            f"{len(devs)} x {devs[0].platform} ({kind})")
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")


def program_fields(conf: Dict) -> Dict[str, Any]:
    """The fields of the program's ``LlamaConfig`` for a configuration
    file, through the file's own ``llama_config`` map: field -> a key of
    the file, ``a/b`` of two keys, or ``<key>@published``, the PUBLISHED
    value of a key the file lists under ``reduced`` (one chip's share maps
    the router's width to ``n_routed_experts@published`` and the experts
    held to ``n_routed_experts``).  A key the file does not have fails by
    its name."""
    import jax.numpy as jnp

    def one(name: str):
        key, at, which = name.partition("@")
        if at and which != "published":
            raise KeyError(name)
        cut = conf.get("reduced", {}).get(key) if at else None
        return cut["published"] if cut else conf[key]

    def value(expr: str):
        a, slash, b = expr.partition("/")
        return one(a) // one(b) if slash else one(a)

    fields = {k: value(v) for k, v in conf["llama_config"].items()}
    for k in ("dtype", "param_dtype"):
        fields[k] = jnp.dtype(conf["assumed"][k]["value"])
    return fields


def program_config(conf: Dict):
    """The program's ``LlamaConfig`` for a configuration file."""
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(**program_fields(conf))


def draw_tokens(rng, cfg, rows: int, seq: int):
    """``rows`` sequences of ``seq`` + 1 ids below ``cfg.vocab_size``: of a
    file cut to a vocabulary slice, the slice, which the traffic and the
    check's sample never leave."""
    import numpy as np

    return rng.integers(0, cfg.vocab_size, (rows, seq + 1), dtype=np.int32)


def reference_module(conf: Dict):
    """The plain reference a configuration file names."""
    return importlib.import_module("benchmark.reference." + conf["reference"])


def placement(job: Dict, devs):
    """(mesh, sharding of a batch) for a job: its mesh over the worker's
    chips, rows split over the data axes; or the one chip."""
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import named_sharding

    if not job["mesh"]:
        return None, devs[0]
    mesh = make_mesh(MeshConfig(**job["mesh"]))
    return mesh, named_sharding(mesh, "batch", None)


def program_check(cfg, mesh):
    """What the check runs of the program, as one function to jit:
    ``loss_fn``'s loss and parts, and each position's next-token loss from
    the logits of ``forward``, the forward pass ``loss_fn`` calls."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import forward, loss_fn

    def check(params, tokens):
        loss, parts = loss_fn(params, {"tokens": tokens}, cfg, mesh=mesh)
        logits, _ = forward(params, tokens[:, :-1], cfg, mesh=mesh)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        token_nll = -jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return loss, parts, token_nll

    return check


def reference_check(reference, conf: Dict, job: Dict, cfg, params, seed: int,
                    mesh, batch_sharding, places=None) -> Dict[str, Any]:
    """(a) and (b) of ``compared``: the program against the plain
    reference on a seeded sample of the cell's own sequence length — the
    per-token losses as the root of their mean squared difference, and the
    step-0 training loss; its arrays are gone before the step program's
    temporaries are needed.
    At step 0 every norm's weight is 1 and what it norms has unit RMS
    already, so a missing norm would not show: the check runs on a copy
    whose norm weights are drawn from the seed (the rest is shared).
    ``places`` (``benchmark/control.py``): name -> ``f(check_params,
    sample)`` giving per-token losses, each read IN THE PROGRAM'S PLACE."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    norm_rng = np.random.default_rng([seed, 2])

    def drawn(path, a):
        if not str(getattr(path[-1], "key", "")).endswith("norm"):
            return a
        return jax.device_put(
            (a.astype(np.float32) * norm_rng.uniform(
                0.5, 1.5, a.shape).astype(np.float32)).astype(a.dtype),
            a.sharding)

    def rms_apart(a, b):
        return float(jnp.sqrt(jnp.mean(jnp.square(a - b))))

    check_params = jax.tree_util.tree_map_with_path(drawn, params)
    sample = jax.device_put(
        draw_tokens(np.random.default_rng([seed, 1]), cfg, job["check_rows"],
                    job["seq"]),
        batch_sharding)
    program_loss, program_parts, program_nll = jax.jit(
        program_check(cfg, mesh))(check_params, sample)
    program_parts = {k: float(v) for k, v in program_parts.items()}
    reference_parts = reference.loss_parts(check_params, sample, conf)
    reference_nll = reference_parts["token_nll"]
    reference_parts = {k: float(v) for k, v in reference_parts.items()
                       if k == "total" or k in program_parts}
    placed = {} if places is None else {"placed_nll_rms": {
        name: rms_apart(place(check_params, sample), reference_nll)
        for name, place in places.items()}}
    return {**placed,
            "token_nll_rms": rms_apart(program_nll, reference_nll),
            "token_nll_limit": conf["check"]["token_nll_rms"],
            "program_loss": float(program_loss),
            "reference_loss": reference_parts["total"],
            "rtol": reference.loss_rtol(job["check_rows"] * job["seq"]),
            "program_parts": program_parts,
            "reference_parts": reference_parts,
            "step_metrics": {k: want for k, (_, want)
                             in reference.STEP_METRICS.items()
                             if want is not None}}


def measure(config: Dict[str, Any], devs, marks: Dict[str, float]
            ) -> Dict[str, Any]:
    """Set up, check against the reference, warm up, run the window and
    (traced run) trace a few steps.  Returns plain data for ``run.py``."""
    import jax
    import numpy as np
    from jax import monitoring
    from jax.profiler import TraceAnnotation

    from ray_tpu.air import session
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step)

    events = {"hits": 0, "misses": 0, "compiles": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            events["hits"] += 1
        elif event.endswith("/cache_misses"):
            events["misses"] += 1

    def on_duration(event, duration, **_):
        # Fires once per program handed to the backend, from the
        # persistent cache or compiled.
        if event.endswith("/backend_compile_duration"):
            events["compiles"] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)

    conf, job, seed = config["conf"], config["job"], config["seed"]
    reference = reference_module(conf)
    cfg = program_config(conf)
    rows, seq = job["rows"], job["seq"]
    mesh, batch_sharding = placement(job, devs)
    opt = default_optimizer()
    marks["imports"] = time.time()
    state = jax.block_until_ready(
        init_train_state(jax.random.PRNGKey(seed), cfg, opt, mesh=mesh))
    marks["state_init"] = time.time()

    check = reference_check(reference, conf, job, cfg, state.params, seed,
                            mesh, batch_sharding)
    marks["reference_check"] = time.time()

    rng = np.random.default_rng([seed, 0])

    def new_batch():
        with TraceAnnotation("make_batch"):
            tokens = draw_tokens(rng, cfg, rows, seq)
        with TraceAnnotation("device_put"):
            return {"tokens": jax.device_put(tokens, batch_sharding)}

    step_fn = make_train_step(cfg, opt, mesh=mesh)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, new_batch()).compile()
    step_load_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    marks["step_load"] = time.time()

    losses: List[float] = []
    kept: Dict[str, List[float]] = {k: [] for k in reference.STEP_METRICS}
    pending = None

    def fetch_pending():
        nonlocal pending
        if pending is not None:
            got = jax.device_get({k: pending[k] for k in ("loss", *kept)})
            losses.append(float(got["loss"]))
            for k in kept:
                kept[k].append(float(got[k]))
            session.report({"step": len(losses), "loss": losses[-1]})
            pending = None

    def one_step():
        """What the window repeats: batch, hand-over, dispatch, then the
        PREVIOUS step's loss (the device is already busy with this one)."""
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
    failed += sum(1 for x in window_losses if not np.isfinite(x))
    compiles_in_window = events["compiles"] - compiles_before

    step_metrics = {k: KEEP[how](kept[k][n_warm:]) if kept[k][n_warm:]
                    else None
                    for k, (how, _) in reference.STEP_METRICS.items()}

    trace = None
    if config["trace"] and error is None:
        trace = _traced_steps(config, run_steps, f"jit_{step_fn.__name__}")

    return {
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "loop_start": marks["loop_start"],
        "setup_marks": marks,
        "window_start": window_start,
        "check": check,
        "window": {"attempted": attempted, "failed": failed,
                   "steps": len(window_losses),
                   "tokens": len(window_losses) * rows * seq,
                   "elapsed_s": elapsed, "error": error,
                   "first_loss": window_losses[0] if window_losses else None,
                   "last_loss": window_losses[-1] if window_losses else None,
                   "compiles": compiles_in_window,
                   "step_metrics": step_metrics},
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
    """After the window: one lead-in step and ``traced_steps`` more under
    the profiler, reduced here, before the trace file is deleted — only
    this process can trace its chips."""
    import jax

    from benchmark import trace_reduce

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
        conf = config["conf"]
        return trace_reduce.reduce_file(
            max(files, key=os.path.getmtime), step_module=step_module,
            annotations=ANNOTATIONS, spans=PROGRAM_SPANS,
            scopes=conf.get("scopes", ()), kernels=conf.get("kernels", ()))
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)


def compared(run: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Driver side.  Every number ``correct`` compares, beside its limit:
    (a) the program's per-token losses against the plain reference's, the
    root of their mean squared difference in nats, within the limit the
    configuration file states — the comparison that sees precision; (b) the program's
    step-0 training loss against the reference's, relative, within its
    stated tolerance — the structure of ``loss_fn``; (c) no step of the
    window raised or gave a loss that is not finite; (d) nothing compiled
    or loaded inside the window; (e) on several chips, per-chip memory
    peaks within 20 %; (f) each step metric the reference module holds to
    a value (kept over the window as it says) equal to it."""
    w = run["worker"]
    check, window, peaks = w["check"], w["window"], w["peak_bytes_in_use"]
    rms, rms_limit = check["token_nll_rms"], check["token_nll_limit"]
    apart = abs(check["program_loss"] - check["reference_loss"]) \
        / abs(check["reference_loss"])
    rows = [("per-token loss apart from the reference's, RMS in nats", rms,
             rms_limit, math.isfinite(rms) and rms <= rms_limit),
            ("loss apart from the reference's, relative", apart,
             check["rtol"], math.isfinite(apart) and apart <= check["rtol"]),
            ("failed steps", window["failed"], 0, window["failed"] == 0),
            ("compiles in the window", window["compiles"], 0,
             window["compiles"] == 0),
            ("memory peak, fullest chip over emptiest",
             max(peaks) / min(peaks) if min(peaks) > 0 else math.inf, 1.2,
             min(peaks) > 0 and max(peaks) <= 1.2 * min(peaks))]
    for name, want in check["step_metrics"].items():
        got = window["step_metrics"][name]
        rows.append((name, got, want, got == want))
    return [{"what": what, "value": value, "limit": limit, "ok": bool(ok)}
            for what, value, limit, ok in rows]


def correct(run: Dict[str, Any]) -> bool:
    """Driver side: every row of ``compared`` holds, and the window ran
    whole steps to its end."""
    window = run["worker"]["window"]
    return bool(window["steps"] > 0 and window["error"] is None
                and all(row["ok"] for row in compared(run)))


def runtime_start_s(run: Dict[str, Any]) -> float:
    """Driver side.  Seconds of set-up in which the worker waited for the
    TPU runtime to start the chips it was granted (``jax.local_devices()``:
    libtpu, 7-16 s by the machine's draw, PERF.md section 5): the program's
    span ``jax.backend_init`` (``ray_tpu/train/backend.py::bring_up``, whose
    body ``benchmark/tests/test_setup_reading.py`` holds to that ONE call),
    off ``Result.metrics["_spans"]``, which every run carries; one process
    starts all its chips under the one span, so it opens once, inside
    set-up.  A program that leaves the bring-up to the loop has no such
    span: the loop's own marks round ``jax.devices()`` time the same call.
    Neither, or a span that opened twice or outside set-up: the run fails
    by message, never a silent 0."""
    w = run["worker"]
    span = (w.get("_spans") or {}).get("jax.backend_init")
    if span is not None:
        inside = (run["process_start"] <= span["first_start"]
                  and span["last_end"] <= w["window_start"])
        if span["count"] != 1 or not inside:
            raise RuntimeError(
                "setup_s: the span jax.backend_init opened "
                f"{span['count']} time(s), {span['first_start']:.3f} to "
                f"{span['last_end']:.3f}; the reading takes ONE, between the "
                f"process start {run['process_start']:.3f} and the window's "
                f"{w['window_start']:.3f}")
        return span["total_s"]
    marks = w.get("setup_marks") or {}
    if "import_jax" in marks and "devices" in marks:
        return marks["devices"] - marks["import_jax"]
    raise RuntimeError(
        "setup_s: the worker reported neither the span jax.backend_init nor "
        "the loop's marks import_jax and devices: the runtime's start is "
        "not known, and set-up is not read without it")


def end_to_end(run: Dict[str, Any]) -> Dict[str, float]:
    """Driver side.  Tokens of all steps completed in the window over the
    time from the first measured step's start to the last one's
    ``block_until_ready`` (worker's host clock; for the cell as a whole,
    not per chip); and the PROGRAM's set-up: process start to the first
    measured step, LESS ``runtime_start_s`` (since PR 62; PERF.md section 2
    has why).  ``setup_s + runtime_start_s`` is the wall of the run, which
    the per-layer readers keep reading."""
    w = run["worker"]
    wall = w["window_start"] - run["process_start"]
    return {
        "train_tokens_per_s": w["window"]["tokens"] / w["window"]["elapsed_s"],
        "setup_s": wall - runtime_start_s(run),
    }


def loop(config: Dict[str, Any]) -> None:
    # Wall-clock marks that split set-up, from before JAX is imported
    # and brings the chips up.
    marks = {"loop_start": time.time()}
    import jax

    from ray_tpu.air import session

    marks["import_jax"] = time.time()
    devs = jax.devices()
    marks["devices"] = time.time()
    require_chips(devs, config["chips"], config["peaks"])
    session.report(measure(config, devs, marks))

#!/usr/bin/env python3
"""The readings a limit of the per-token check stands between, at a cell's
own size, over several seeds, in ONE process (which holds the chips itself:
no ``ray_tpu`` worker, no window, nothing timed).

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

Per seed, as the train loop does it (``loops/train.py::reference_check``:
the state from the seed, norm weights drawn, a seeded sample of the cell's
sequence length), the per-token losses apart from the plain float32
reference's, as the root of their mean squared difference in nats, of

- ``sound``: the program — what ``correct`` compares with ``limit``
  (``mean_rel``: its mean loss apart, relative, the check's other row);
- ``int8``: THE CONTROL.  The plain reference in the program's place with
  every matrix rounded to int8, one scale an output channel (a row of the
  embedding): the precision below the bfloat16 the configurations state
  for parameters and activations, and the step that tempts on a chip whose
  MXU runs int8 at twice the rate and whose memory the parameters fill.
  Only the WEIGHTS are lowered, so a program that also lowered its
  activations would stand further off;
- ``fp8``, ``bf16_logp``: read beside it, not what the limit is set from —
  the same with the matrices through ``float8_e4m3fn``, and the float32
  reference with only its log-probabilities kept in bfloat16 (the step
  below the float32 the configurations state for logits and loss).

One JSON line a seed, then the largest ``sound`` and each control's
smallest.  The control lowers the reference from OUTSIDE (it rounds the
parameters it is handed): the reference has no precision knob.  PERF.md
section 6 (PR 29) has what the v5e read.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@functools.lru_cache(maxsize=None)
def _round_one():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def one(a, axis, kind):
        a32 = a.astype(jnp.float32)
        top = {"int8": 127.0, "fp8": 224.0}[kind]
        scale = jnp.max(jnp.abs(a32), axis=axis, keepdims=True) / top
        q = a32 / scale
        # fp8 as 4 exponent and 3 mantissa bits by ``reduce_precision``: a
        # convert to float8 and back is a no-op to the TPU's compiler
        # (excess precision is allowed: it read 0.0 on the v5e)
        q = jnp.round(q) if kind == "int8" else jax.lax.reduce_precision(
            q, exponent_bits=4, mantissa_bits=3)
        return (q * scale).astype(a.dtype)

    return one


def rounded(params, kind: str):
    """``params`` with every matrix through int8 (symmetric, 127 levels a
    side) or an 8-bit float (e4m3), one scale an output channel, and back
    to its own dtype.  Norm weights (vectors) stay as they are."""
    import jax

    def leaf(path, a):
        name = str(getattr(path[-1], "key", ""))
        if name.endswith("norm") or a.ndim < 2:
            return a
        # (.., in, out): a scale an output; the embedding (V, d): a row
        return _round_one()(a, -1 if name == "embed" else -2, kind)

    return jax.tree_util.tree_map_with_path(leaf, params)


def readings(conf, job, seed: int, devs):
    """One seed on the devices ``devs``: name -> RMS, the limit too."""
    import jax
    import jax.numpy as jnp

    from benchmark.loops import train
    from ray_tpu.train.core import default_optimizer, init_train_state

    reference = train.reference_module(conf)
    cfg = train.program_config(conf)
    mesh, batch_sharding = train.placement(job, devs)
    state = init_train_state(jax.random.PRNGKey(seed), cfg,
                             default_optimizer(), mesh=mesh)

    def reference_nll(p, t):
        return reference.loss_parts(p, t, conf)["token_nll"]

    check = train.reference_check(
        reference, conf, job, cfg, state.params, seed, mesh, batch_sharding,
        places={
            "int8": lambda p, t: reference_nll(rounded(p, "int8"), t),
            "fp8": lambda p, t: reference_nll(rounded(p, "fp8"), t),
            "bf16_logp": lambda p, t: reference_nll(p, t).astype(
                jnp.bfloat16).astype(jnp.float32)})
    want = check["reference_loss"]
    return {"sound": check["token_nll_rms"], **check["placed_nll_rms"],
            "mean_rel": abs(check["program_loss"] - want) / abs(want),
            "limit": check["token_nll_limit"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, comma-separated")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from benchmark.loops import train

    def load(*path):
        with open(os.path.join(ROOT, *path)) as f:
            return json.load(f)

    cell, = [c for c in load("BENCHMARK.json")["workloads"]
             if c["name"] == args.workload]
    conf = load("benchmark", "configs", cell["config"] + ".json")
    job = load("benchmark", "jobs", cell["traffic"] + ".json")
    devs = jax.devices()
    train.require_chips(devs, cell["chips"], load("benchmark", "peaks.json"))
    rows = []
    for seed in map(int, args.seeds.split(",")):
        rows.append(readings(conf, job, seed, devs))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **rows[-1]}), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "largest_sound": max(r["sound"] for r in rows),
        "largest_mean_rel": max(r["mean_rel"] for r in rows),
        **{"smallest_" + k: min(r[k] for r in rows)
           for k in ("int8", "fp8", "bf16_logp")},
        "limit": rows[-1]["limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compile a cell's programs at their real size for a DESCRIBED v5e 2x2,
without a chip (on-chip-measurement guide, section 2, rehearsal 3).

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [<cell> ...]

Prints what ``cuts.py`` holds against the configuration's file (how it may
differ from its public ``config.json``), then what the chip's compiler counts
per device for the step program, the program's side of the check and one
layer of the plain reference the configuration names.  A compile
is not a run: nothing here is a time or a result.  It is how the cells
were cut to size (benchmark/README.md) and what to run before a chip call
after changing a size.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _gb(compiled):
    m = compiled.memory_analysis()
    return (f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB + "
            f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB = "
            f"{(m.argument_size_in_bytes + m.temp_size_in_bytes) / 1e9:.2f}"
            " GB per device")


def rehearse(cell, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from benchmark import cuts
    from benchmark.loops import train
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.core import (
        default_optimizer, init_train_state, make_train_step,
        train_state_shardings)

    # The model picks interpret mode from jax.default_backend(), which is
    # the CPU here: take the branch a chip worker takes.
    attention._interpret_default = lambda: False

    def load(kind, name):
        with open(os.path.join(HERE, kind, name + ".json")) as f:
            return json.load(f)

    conf, job = load("configs", cell["config"]), load("jobs", cell["traffic"])
    for fault in cuts.complaints(
            conf, load(os.path.join("testdata", "published"), cell["config"])):
        print(f"  {cell['config']}: THE CUT: {fault}")
    reference = train.reference_module(conf)
    cfg, opt = train.program_config(conf), default_optimizer()
    shapes = jax.eval_shape(lambda k: init_train_state(k, cfg, opt),
                            jax.random.PRNGKey(0))
    if job["mesh"]:
        mesh = make_mesh(MeshConfig(**job["mesh"]),
                         devices=topo.devices[:cell["chips"]])
        shardings = train_state_shardings(cfg, opt, mesh)
        batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    else:
        mesh = None
        batch_sharding = SingleDeviceSharding(topo.devices[0])
        shardings = jax.tree.map(lambda _: batch_sharding, shapes)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)

    def tokens(rows):
        return jax.ShapeDtypeStruct((rows, job["seq"] + 1), jnp.int32,
                                    sharding=batch_sharding)

    print(f"{cell['name']}: {job['rows']} x {job['seq']}, "
          f"{conf['num_hidden_layers']} layers, mesh {job['mesh']}")
    step = make_train_step(cfg, opt, mesh=mesh).lower(
        state, {"tokens": tokens(job["rows"])}).compile()
    print("  step program:   ", _gb(step),
          "| flash kernel in it:", "tpu_custom_call" in step.as_text())
    check = jax.jit(train.program_check(cfg, mesh)).lower(
        state.params, tokens(job["check_rows"])).compile()
    print("  check program:  ", _gb(check))
    x = jax.ShapeDtypeStruct(
        (job["check_rows"], job["seq"], conf["hidden_size"]), jnp.float32,
        sharding=(NamedSharding(mesh, P(("dp", "fsdp"), None, None))
                  if mesh else batch_sharding))
    with jax.default_matmul_precision("highest"):
        ref = reference.layer.lower(x, state.params["layers"], 0,
                                    **reference.layer_kwargs(conf)).compile()
    print(f"  reference layer ({conf['reference']}):", _gb(ref),
          "(arguments: all stacked layers)")


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache off.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    for cell in cells:
        if not argv or cell["name"] in argv:
            rehearse(cell, topo)


if __name__ == "__main__":
    main(sys.argv[1:])

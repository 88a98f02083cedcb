"""Whether a change to the program leaves the cells' programs as they were,
without a chip: lower (not compile) each cell's step and check programs for
a described v5e, from the checkout ``<root>``, and write the StableHLO text
under ``<out>/<cell>.{step,check}.txt`` with one line a program: its length
and a hash of the text with the serialized Mosaic kernel bodies masked (a
body embeds the source line numbers of ``ops/*.py``, so it moves with any
edit above a kernel, whatever the kernel computes).

    JAX_PLATFORMS=cpu python benchmark/lowered_text.py <root> <out> [<cell> ...]
    JAX_PLATFORMS=cpu python benchmark/lowered_text.py --compare <out_a> <out_b>

Run it once on a copy of the parent commit and once on the change (one
process each: both import ``ray_tpu`` and ``benchmark`` from their own
``<root>``), then ``--compare`` the two directories: ``same`` or ``DIFFERS``
a program, cells that only one side has named as such.  ``BENCHMARK.json``
is read from ``<root>``, so a cell the parent lacks is lowered on one side
only.
"""
import hashlib
import json
import os
import re
import sys

_BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]*(\\22)')


def masked_hash(text: str) -> str:
    return hashlib.sha256(_BODY.sub(r"\1\2", text).encode()).hexdigest()[:16]


def compare(out_a: str, out_b: str) -> int:
    names = sorted(set(os.listdir(out_a)) | set(os.listdir(out_b)))
    differs = 0
    for name in names:
        sides = [os.path.join(d, name) for d in (out_a, out_b)]
        if not all(map(os.path.isfile, sides)):
            print(name, "only in", out_a if os.path.isfile(sides[0]) else out_b)
            continue
        a, b = (masked_hash(open(p).read()) for p in sides)
        differs += a != b
        print(name, a, b, "same" if a == b else "DIFFERS")
    return 1 if differs else 0


def lower(root: str, out: str, wanted) -> None:
    sys.path.insert(0, root)
    os.chdir(root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from benchmark.loops import train
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.core import (default_optimizer, init_train_state,
                                    make_train_step, train_state_shardings)

    attention._interpret_default = lambda: False   # the chip's kernels
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    os.makedirs(out, exist_ok=True)

    def load(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    for cell in load("BENCHMARK.json")["workloads"]:
        if wanted and cell["name"] not in wanted:
            continue
        conf = load("benchmark", "configs", cell["config"] + ".json")
        job = load("benchmark", "jobs", cell["traffic"] + ".json")
        cfg, opt = train.program_config(conf), default_optimizer()
        shapes = jax.eval_shape(lambda k: init_train_state(k, cfg, opt),
                                jax.random.PRNGKey(0))
        if job["mesh"]:
            mesh = make_mesh(MeshConfig(**job["mesh"]),
                             devices=topo.devices[:cell["chips"]])
            shardings = train_state_shardings(cfg, opt, mesh)
            rows = NamedSharding(mesh, P(("dp", "fsdp"), None))
        else:
            mesh, rows = None, SingleDeviceSharding(topo.devices[0])
            shardings = jax.tree.map(lambda _: rows, shapes)
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)

        def tokens(n):
            return jax.ShapeDtypeStruct((n, job["seq"] + 1), jnp.int32,
                                        sharding=rows)

        texts = {
            "step": make_train_step(cfg, opt, mesh=mesh).lower(
                state, {"tokens": tokens(job["rows"])}).as_text(),
            "check": jax.jit(train.program_check(cfg, mesh)).lower(
                state.params, tokens(job["check_rows"])).as_text()}
        for kind, text in texts.items():
            with open(os.path.join(out, f"{cell['name']}.{kind}.txt"),
                      "w") as f:
                f.write(text)
            print(cell["name"], kind, len(text), masked_hash(text),
                  flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "--compare":
        sys.exit(compare(*sys.argv[2:4]))
    lower(os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2]),
          sys.argv[3:])

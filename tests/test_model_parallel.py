"""Model-level parallelism tests: every mesh strategy must reproduce the
single-device numerics (the reference tests multi-node semantics with an
in-process Cluster, SURVEY.md §4.2; here the analog is the virtual 8-device
CPU mesh)."""

import functools
import math

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import (
    LlamaConfig, init_params, forward, loss_fn, param_logical_axes,
)
from ray_tpu.models.llama import _embed, forward_pipelined
from ray_tpu.parallel import (MeshConfig, make_mesh, shard_pytree,
                              use_mesh)
from ray_tpu.parallel.sharding import DEFAULT_RULES, named_sharding
from ray_tpu.train import TrainState, init_train_state, make_train_step
from ray_tpu.train.core import STEP_SCOPES
from ray_tpu.util.tracing import scope_and_phase


KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=4, s=32):
    toks = jax.random.randint(KEY, (b, s + 1), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    return {"tokens": toks}


@pytest.mark.parametrize("name,cfg_kw,mesh_kw", [
    ("dp_fsdp_tp", {}, dict(dp=2, fsdp=2, tp=2)),
    ("flash_shmap", {"attn_impl": "flash"}, dict(dp=4, tp=2)),
    ("moe_ring_sp", {"num_experts": 4, "attn_impl": "ring"},
     dict(dp=2, sp=2, ep=2)),
    ("moe_ulysses", {"num_experts": 4, "attn_impl": "ulysses"},
     dict(sp=4, ep=2)),
])
def test_sharded_loss_matches_single_device(name, cfg_kw, mesh_kw):
    cfg = LlamaConfig.tiny(**cfg_kw)
    params = init_params(KEY, cfg)
    batch = _batch(cfg)
    ref, _ = jax.jit(lambda p: loss_fn(p, batch, cfg))(params)
    mesh = make_mesh(MeshConfig(**mesh_kw))
    with use_mesh(mesh):
        sp = shard_pytree(params, param_logical_axes(cfg), mesh)
        toks = jax.device_put(
            batch["tokens"], NamedSharding(mesh, P(("dp", "fsdp"), None)))
        got, _ = jax.jit(
            lambda p, t: loss_fn(p, {"tokens": t}, cfg, mesh=mesh))(sp, toks)
    assert abs(float(got) - float(ref)) < 1e-4, name


@pytest.mark.parametrize("attn", ["reference", "ring"])
def test_pipelined_forward_matches(attn):
    cfg = LlamaConfig.tiny(num_layers=4, attn_impl=attn)
    params = init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (8, 32), 0, cfg.vocab_size,
                              dtype=jnp.int32)
    ref_logits, _ = jax.jit(lambda p: forward(p, toks, cfg))(params)
    mesh = make_mesh(MeshConfig(dp=2, pp=2, sp=2 if attn == "ring" else 1,
                                tp=1 if attn == "ring" else 2))
    with use_mesh(mesh):
        sp = shard_pytree(params, param_logical_axes(cfg), mesh)
        ts = jax.device_put(toks, NamedSharding(mesh, P(("dp", "fsdp"),
                                                        None)))
        got, _ = jax.jit(lambda p, t: forward_pipelined(
            p, t, cfg, mesh=mesh, num_microbatches=4))(sp, ts)
    assert jnp.max(jnp.abs(got - ref_logits)) < 5e-4


def test_train_step_decreases_loss_single_device():
    cfg = LlamaConfig.tiny()
    opt = optax.adam(1e-2)
    state = init_train_state(KEY, cfg, opt)
    step = make_train_step(cfg, opt)
    batch = _batch(cfg)
    state, m0 = step(state, batch)   # step donates its input state
    for _ in range(10):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(m0["loss"])


def _one_step_both_ways(cfg, opt, batch):
    """One step from the same initial state on one device and on the
    dp=2 x fsdp=2 x tp=2 mesh: (initial params, single-device state and
    metrics, sharded state and metrics)."""
    state = init_train_state(KEY, cfg, opt)
    s1, m1 = make_train_step(cfg, opt, donate=False)(state, batch)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    with use_mesh(mesh):
        state_sh = init_train_state(KEY, cfg, opt, mesh=mesh)
        step_sh = make_train_step(cfg, opt, mesh=mesh, donate=False)
        toks = jax.device_put(
            batch["tokens"], NamedSharding(mesh, P(("dp", "fsdp"), None)))
        s2, m2 = step_sh(state_sh, {"tokens": toks})
        s2 = jax.device_get(s2)
    return state.params, s1, m1, s2, m2


def test_train_step_sharded_matches_single_device():
    cfg = LlamaConfig.tiny()
    batch = _batch(cfg, b=8)

    # A plain SGD step of lr 1 IS the gradient: every element of every
    # leaf agrees to 1e-6 (measured: 1.2e-7).
    p0, g1, n1, g2, n2 = _one_step_both_ways(cfg, optax.sgd(1.0), batch)
    assert abs(float(n1["loss"]) - float(n2["loss"])) < 1e-4
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     g1.params, g2.params)
    assert max(jax.tree.leaves(d)) < 1e-6

    # Params after one Adam step agree to 1e-4.  Adam's first step is
    # lr * g / (|g| + 1e-8): where |g| is within two decades of that
    # epsilon the last ulp of a reduction order decides the update (the
    # two differ by 1.8e-4 at an element of w_gate whose gradient is
    # 7e-9), so those elements — under 1% of any leaf — are held to the
    # gradient bound above instead, which is what a sharding fault
    # would move.  (Exactly-zero gradients, the embedding rows of unused
    # tokens, stay in the comparison.)
    _, s1, m1, s2, m2 = _one_step_both_ways(cfg, optax.adam(1e-2), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    tiny = jax.tree.map(
        lambda p, g: (p != g) & (jnp.abs(p - g) <= 1e-6), p0, g1.params)
    assert max(float(jnp.mean(m)) for m in jax.tree.leaves(tiny)) < 0.01
    d = jax.tree.map(
        lambda a, b, m: float(jnp.max(jnp.where(m, 0.0, jnp.abs(a - b)))),
        s1.params, s2.params, tiny)
    assert max(jax.tree.leaves(d)) < 1e-4


def test_sharded_flash_step_needs_no_mesh_context():
    """``make_train_step(cfg, opt, mesh=mesh)`` is complete on its own:
    called OUTSIDE any ``use_mesh`` block (how a trainer loop calls it)
    the flash shard_map and the logical constraints bind the explicit
    mesh — ``jax.set_mesh`` cannot be opened under the step's trace."""
    cfg = LlamaConfig.tiny(attn_impl="flash")
    opt = optax.adam(1e-2)
    batch = _batch(cfg, b=8)
    _, m1 = make_train_step(cfg, opt, donate=False)(
        init_train_state(KEY, cfg, opt), batch)

    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    state = init_train_state(KEY, cfg, opt, mesh=mesh)
    assert jax.sharding.get_abstract_mesh().empty
    _, m2 = make_train_step(cfg, opt, mesh=mesh)(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4


def test_reference_attention_repeats_kv_heads_without_a_mesh():
    """GQA through ``attn_impl="reference"`` on one device: 4 query
    heads over 2 KV heads give the loss the flash path gives."""
    kw = dict(num_heads=4, num_kv_heads=2)
    ref_cfg = LlamaConfig.tiny(attn_impl="reference", **kw)
    params = init_params(KEY, ref_cfg)
    batch = _batch(ref_cfg)
    got, want = (
        jax.jit(lambda p: loss_fn(p, batch, cfg))(params)[0]
        for cfg in (ref_cfg, LlamaConfig.tiny(attn_impl="flash", **kw)))
    assert abs(float(got) - float(want)) < 1e-4


@pytest.mark.parametrize("head_dim,kv_heads", [(128, 2), (16, 2), (16, 1)],
                         ids=["in-place-own-heads", "turned-own-heads",
                              "repeated"])
def test_flash_under_tp_takes_kv_heads_as_they_are(head_dim, kv_heads):
    """Under a mesh k and v enter the flash kernels' manual region with
    their OWN heads where 'tp' divides them (a rank's q heads are whole
    groups), repeated where it does not: loss and gradients are the
    unsharded reference's either way, at a head the kernels read in place
    and at one they turn round."""
    kw = dict(num_heads=4, num_kv_heads=kv_heads, head_dim=head_dim)
    cfg = LlamaConfig.tiny(attn_impl="flash", **kw)
    params = init_params(KEY, cfg)
    batch = _batch(cfg, b=4)
    loss = lambda cfg, mesh=None: jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, mesh=mesh)[0])
    want, want_g = jax.jit(
        loss(LlamaConfig.tiny(attn_impl="reference", **kw)))(params)
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    text = str(jax.make_jaxpr(loss(cfg, mesh))(params))
    # a rank's k: a head of its own, or (repeated) one for each q head
    own, repeated = (f"f32[2,32,{n},{head_dim}]" for n in (1, 2))
    assert own in text if kv_heads == 2 else own not in text
    assert text.count(repeated) > (0 if kv_heads == 2 else 4)
    got, got_g = jax.jit(loss(cfg, mesh))(params)
    assert abs(float(got) - float(want)) < 1e-4
    worst = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         got_g, want_g)
    assert max(jax.tree.leaves(worst)) < 1e-4


def _op_names(hlo_text, opcodes):
    """(opcode, op_name) of every instruction of these opcodes."""
    import re

    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"[\]})] ([a-z][a-z0-9-]*)\(", line)
        if m and m.group(1).startswith(opcodes):
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


VOCAB_OVER_FSDP = dict(DEFAULT_RULES, vocab="fsdp", kernel_in=None)
VOCAB_OVER_BOTH = dict(DEFAULT_RULES, vocab=("fsdp", "tp"), kernel_in=None)


def _embed_tokens(kind, vocab, b=8, s=32):
    """A batch of tokens: uniform; one token on three rows in four (its
    cotangents add up in ONE row of the table's gradient); or all inside the
    SECOND of two vocabulary shards (the first shard's part is zeros)."""
    toks = jax.random.randint(KEY, (b, s), 0, vocab, dtype=jnp.int32)
    if kind == "repeats":
        often = jax.random.bernoulli(jax.random.PRNGKey(5), 0.75, (b, s))
        return jnp.where(often, vocab // 2 + 3, toks)
    if kind == "one_shard":
        return vocab // 2 + toks % (vocab // 2)
    return toks


@pytest.mark.parametrize("tokens", ["uniform", "repeats", "one_shard"])
@pytest.mark.parametrize("mesh_kw,rules", [
    (dict(tp=2), None), (dict(fsdp=2, tp=2), None), (dict(ep=4), None),
    (dict(dp=2), None), (dict(fsdp=2, tp=2), VOCAB_OVER_FSDP),
    (dict(dp=2, fsdp=2, tp=2), VOCAB_OVER_BOTH)],
    ids=["tp2", "fsdp2_tp2", "ep4", "dp2", "vocab_over_fsdp",
         "vocab_over_fsdp_and_tp"])
def test_embedding_under_a_mesh_takes_the_rows_one_device_takes(
        mesh_kw, rules, tokens):
    """``_embed`` under a mesh, whatever axis the rules in force lay the
    vocabulary on: the values ``jnp.take`` gives on one device bit for bit
    (a row is read, not computed), and the table's gradient to float32's
    rounding (a row's cotangents are summed in another order)."""
    toks = _embed_tokens(tokens, 256)
    ct = jax.random.normal(jax.random.PRNGKey(7), (*toks.shape, 64))
    mesh = make_mesh(MeshConfig(**mesh_kw),
                     devices=jax.devices()[:math.prod(mesh_kw.values())])
    placed = functools.partial(named_sharding, mesh, rules=rules)

    def value_and_table_grad(cfg, mesh, rules, table, toks):
        def f(table):
            x = _embed({"embed": table}, toks, cfg, mesh, rules)
            return jnp.sum(x.astype(jnp.float32) * ct), x
        (_, x), g = jax.value_and_grad(f, has_aux=True)(table)
        return x, g

    # the gradients in float32: in bfloat16 the CPU's compiler drops the
    # cotangent's rounding from one of the two programs and not the other
    for dtype in (jnp.bfloat16, jnp.float32):
        cfg = LlamaConfig.tiny(dtype=dtype, embedding_multiplier=12.0)
        table = init_params(KEY, cfg)["embed"]
        want_x = (jnp.take(table, toks, axis=0).astype(dtype)
                  * jnp.asarray(12.0, dtype))
        x1, g1 = jax.jit(functools.partial(
            value_and_table_grad, cfg, None, None))(table, toks)
        assert x1.dtype == dtype and jnp.array_equal(x1, want_x)
        x, g = jax.jit(functools.partial(
            value_and_table_grad, cfg, mesh, rules))(
            jax.device_put(table, placed("vocab", "kernel_in")),
            jax.device_put(toks, placed("batch", "seq")))
        assert x.dtype == dtype and jnp.array_equal(x, want_x)
        assert x.sharding.is_equivalent_to(
            placed("batch", "seq", "embed"), 3)
        assert g.sharding.is_equivalent_to(placed("vocab", "kernel_in"), 2)
    # up to 200 cotangents of size 12 in a row: 1e-3 is five ulps of the sum
    assert float(jnp.max(jnp.abs(g - g1))) < 1e-3
    assert float(jnp.max(jnp.abs(g1))) > 100 or tokens != "repeats"


def test_embedding_over_tp_gathers_no_table_and_multiplies_nothing():
    """The compiled step under ``tp=2``: the vocabulary's shard stays a
    shard (no all-gather gives an array of the whole table's shape, which
    is what the partitioner makes of a plain gather from a table split by
    rows) and scope ``embed`` holds no matmul: the rows are taken, and the
    shards' parts summed by the scope's one all-reduce."""
    cfg = LlamaConfig.tiny(attn_impl="flash", remat=True, vocab_size=384)
    opt = optax.adam(1e-2)
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    state = init_train_state(KEY, cfg, opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, donate=False)
    text = step.lower(state, _batch(cfg)).compile().as_text()
    table = f"[{cfg.vocab_size},{cfg.embed_dim}]"
    shard = f"[{cfg.vocab_size // 2},{cfg.embed_dim}]"
    assert shard in text and not [
        line for line in text.splitlines()
        if "all-gather" in line and table in line.split("all-gather")[0]]
    in_scope = {op for op, name in _op_names(text, ("",))
                if scope_and_phase(name, STEP_SCOPES)[0] == "embed"}
    assert {"scatter", "all-reduce"} <= in_scope, in_scope
    assert not {"dot", "convolution", "all-gather"} & in_scope, in_scope


@pytest.mark.parametrize("mesh_kw,moe,hybrid", [
    (None, False, False), (dict(fsdp=2, tp=2), False, False),
    (None, True, False), (dict(fsdp=2, ep=2), True, False),
    (None, False, True), (dict(fsdp=2, tp=2), False, True)],
    ids=["one_device", "fsdp2_tp2", "moe_one_device", "moe_fsdp2_ep2",
         "hybrid_one_device", "hybrid_fsdp2_tp2"])
def test_step_program_is_named_by_scope(mesh_kw, moe, hybrid):
    """What a device trace can tell: the kernels by name, and on every
    matmul, kernel call and collective exactly one of STEP_SCOPES
    (``util.tracing.step_breakdown`` reads them off the profiler's
    ``op_name``).  An expert layer's four scopes take the place of
    ``ffn``, each in every phase; a Mamba layer's four stand beside the
    attention layer's three (a hybrid has both kinds of layer)."""
    import re

    from ray_tpu.util.tracing import KERNEL_NAMES

    cfg = LlamaConfig.tiny(attn_impl="flash", remat=True, num_kv_heads=2,
                           **(dict(num_experts=4, num_selected=2,
                                   z_loss_coef=0.001) if moe else {}),
                           **(dict(num_layers=3, layer_types=(
                               "mamba", "attention", "mamba"), ssm_heads=4,
                               ssm_head_dim=16, ssm_state=8, ssm_chunk=16,
                               tie_embeddings=True, logits_scaling=8.0,
                               residual_multiplier=0.22) if hybrid else {}))
    opt = optax.adam(1e-2)
    mesh = None
    if mesh_kw:
        mesh = make_mesh(MeshConfig(**mesh_kw), devices=jax.devices()[:4])
    state = init_train_state(KEY, cfg, opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh, donate=False)
    batch = _batch(cfg)

    traced = step.trace(state, batch)       # one trace for both readings
    jaxpr = str(traced.jaxpr)
    for kernel in ("flash_fwd", "flash_dkv") + (
            ("moe_gmm", "moe_tgmm") if moe else ()):
        assert re.search(rf"\bname={kernel}\b", jaxpr), kernel
    assert "flash_dq" not in jaxpr      # the backward is ONE kernel

    # As compiled (CPU): every matmul, the partitioner's collectives,
    # and the interpret-mode kernels' own dots under attention/<kernel>.
    named = _op_names(traced.lower().compile().as_text(), (
        "dot", "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute"))
    assert sum(op == "dot" for op, _ in named) >= 20
    if mesh is not None:
        assert any(op.startswith("all-") for op, _ in named)
    seen = set()
    for op, name in named:
        tokens = re.findall(r"[^/()]+", name)
        scopes = [t for t in tokens if t in STEP_SCOPES]
        if moe and not scopes and name.endswith("moe_block)/shard_map/psum"):
            # the expert region's own boundary: gradients of what every
            # token shard holds, summed over the token axes; no scope
            assert op == "all-reduce" and mesh is not None, (op, name)
            continue
        if hybrid and op == "dot" and not name:
            # the CPU compiler rewrites the chunked scan's einsums (batch
            # dimensions that do not lead) into dots that carry no name;
            # the scan's other ops keep theirs (``every`` below)
            continue
        assert len(scopes) == 1, (op, name)
        seen.add(scope_and_phase(name, STEP_SCOPES))
        kernels = [t for t in tokens if t.startswith(KERNEL_NAMES)]
        assert not kernels or scopes == [
            "attention" if kernels[0].startswith("flash_")
            else "moe_experts"], name
    # Every scope has a matmul or a collective of its own but the loss
    # (elementwise and reductions) — and each phase is told apart.
    moe_scopes = {"moe_route", "moe_exchange", "moe_dispatch", "moe_experts",
                  "moe_combine"}
    ssm_scopes = {"ssm_in", "ssm_conv", "ssm_scan", "ssm_out"}
    want = set(STEP_SCOPES) - ({"ffn"} if moe else moe_scopes)
    # one residual stream, no predicted-ahead module (tests/test_latent_streams.py
    # has a model that opens these three)
    want -= {"hc_map", "hc_mix", "mtp_in"}
    # the convolution is shifted adds: no matmul, no collective; the
    # scan's matmuls lose their names on the CPU (above)
    want -= {"ssm_conv", "ssm_scan"} if hybrid else ssm_scopes
    # no delta-rule layer (tests/test_delta.py has a model that opens these)
    want -= {"gdn_in", "gdn_conv", "gdn_scan", "gdn_out"}
    # ... nor one whose decay is a vector (tests/test_kimi_linear.py has one)
    want -= {"kda_in", "kda_conv", "kda_scan", "kda_out"}
    # no short-convolution layer (tests/test_lfm2.py has such a model)
    want -= {"sconv_in", "sconv_gate", "sconv_out"}
    # no latent round the routed experts (tests/test_nemotron3.py has one)
    want -= {"moe_latent"}
    # no indexer that picks a query's keys (tests/test_keye.py has one)
    want -= {"dsa_index", "dsa_select", "dsa_loss"}
    # no Mamba-1, differential-attention or memory-unit layer
    # (tests/test_phi4flash.py has a model that opens these)
    want -= {"s6_in", "s6_conv", "s6_scan", "s6_out", "attn_diff", "gmu"}
    # ... nor a denoising objective's noise (tests/test_sdar.py)
    want -= {"bd_noise"}
    # ... nor a looped model's exit gate and objective (tests/test_ouro.py)
    want -= {"ut_exit"}
    want -= {"loss"} if mesh is None or moe else set()  # tp splits it
    # the embedding takes the rows its tokens name: a gather, never a
    # matmul.  On one device the scope shows nothing here; under a mesh
    # its collectives: the sum of the shards' parts where the vocabulary
    # is split (tp), the moves between the table's columns (fsdp) and the
    # batch's rows, the gradient's sum over the ranks that share the table
    want -= {"embed"} if mesh is None else set()
    in_embed = {op for op, name in named
                if scope_and_phase(name, STEP_SCOPES)[0] == "embed"}
    assert "dot" not in in_embed, in_embed
    assert not (mesh_kw or {}).get("tp") or {
        ("embed", "forward"), ("embed", "backward")} <= seen, seen
    want -= {"optimizer"} if mesh is None else set()
    # sort, gathers and the weighted sum: a matmul only in the interpreted
    # kernels and the router
    want -= {"moe_dispatch", "moe_combine"}
    # the exchange is collectives over an ``ep`` axis, and only there
    want -= {"moe_exchange"} if mesh is None else set()
    assert want <= {s for s, _ in seen}, seen
    block = "moe_experts" if moe else "ffn"
    assert {(block, "forward"), (block, "remat"), (block, "backward"),
            ("attn_qkv", "remat"), ("lm_head", "backward")} <= seen
    # The layer checkpoint keeps the flash kernel's output and log-sum-exp
    # (``models/llama.py::_checkpoint``): no second flash_fwd, so no matmul
    # in the rematerialised attention.
    assert {("attention", "forward"), ("attention", "backward")} <= seen
    assert ("attention", "remat") not in seen
    if hybrid:
        # a Mamba layer keeps nothing in the checkpoint: its scan runs
        # again under remat, and every scope has ops in every phase
        every = {scope_and_phase(name, STEP_SCOPES) for _, name in _op_names(
            step.lower(state, batch).compile().as_text(), ("",))}
        assert {(s, p) for s in ssm_scopes
                for p in ("forward", "remat", "backward")} <= every
    if moe:
        # all four scopes in every phase, on ops of any kind — but the
        # rematerialised combine (nothing of the backward reads its sum);
        # the rematerialised dispatch is the row gather alone (the
        # checkpoint keeps the row index, not the rows)
        every = {scope_and_phase(name, STEP_SCOPES) for _, name in _op_names(
            step.lower(state, batch).compile().as_text(), ("",))}
        gone = {("moe_combine", "remat")}
        opened = moe_scopes - ({"moe_exchange"} if mesh is None else set())
        assert {(s, p) for s in opened
                for p in ("forward", "remat", "backward")} - gone <= every
        assert not gone & every, gone & every


@pytest.mark.slow  # ~38s of multichip mesh dryruns (the single biggest
# tier-1 sink); sharding coverage keeps its tier-1 representatives via
# test_train_step_sharded_matches_single_device and the
# test_sharded_loss_matches_single_device battery above.
def test_graft_entry_dryrun():
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import __graft_entry__ as g
    g.dryrun_multichip(8)
    fn, args = g.entry()
    jax.eval_shape(fn, *args)  # traceability; full compile covered by driver

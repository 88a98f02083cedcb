"""The seam between the decoder (``models/llama.py``) and what a layer is
made of (``models/blocks``): every registered mixer and FFN keeps the
contract its ``Block`` declares — tensors, saved residuals, scopes, step
statistics —, ``STEP_SCOPES`` and the parameter trees are what they were
before the blocks were modules, and a mixer the decoder has never heard of
trains once it is registered."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, llama
from ray_tpu.models.blocks import FFNS, MIXERS
from ray_tpu.models.blocks.base import Block, Param, fold
from ray_tpu.models.blocks.residual import add
from ray_tpu.models.llama import (
    LlamaConfig, init_params, loss_and_counts, param_logical_axes)
from ray_tpu.train.core import STEP_SCOPES, init_train_state, make_train_step
from ray_tpu.util.tracing import scope_and_phase
import tiny_models

SEQ = 64
TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ + 1), 0, 256)
# what a tiny model needs to have a layer of each kind; flash attention
# (interpreted here) so that its kernel's residuals are made
FIELDS = {
    "attention": {}, "full_attention": {},
    # a third of the later rows' keys lie outside the window
    "sliding_attention": dict(sliding_window=24, attn_output_gate=True,
                              position_embedding="rope_windowed"),
    "latent": dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16),
    # 16 keys a query of the 64
    "indexed": dict(sa_config={"indexer_num_heads": 2, "indexer_head_dim": 8,
                               "indexer_num_kv_heads": 1, "topk": 16}),
    # blocks of 4 of the 64 positions, over [noised ; clean]
    "block_attention": dict(block_diffusion={
        "block_length": 4, "mask_token_id": 255, "eps": 1e-3,
        "noise_seed": 0}),
    "mamba": dict(ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
                  ssm_chunk=8),
    "linear_attention": dict(gdn_heads=4, gdn_key_dim=8, gdn_value_dim=16),
    # the published head of 128 / 128, so that the Pallas pair runs
    # (interpreted) and ITS residuals are made
    "kda": dict(linear_attn_config={"num_heads": 1, "head_dim": 128,
                                    "short_conv_kernel_size": 4}),
    "conv": {},
    # an inner width of 1024 (16 x 64), so that the Pallas pair runs
    # (interpreted) and ITS residual is made
    "mamba1": dict(s6_expand=16, s6_state=4),
    "diff_sliding": dict(sliding_window=24, attn_bias=True),
    "diff_full": {},
    # the two readers stand behind a layer that publishes what they read
    "diff_cross": dict(after="diff_full"),
    "gmu": dict(after="mamba1", s6_state=4),
    "none": {},   # the empty block, a mixer and an FFN
    "dense": {},
    # the routed experts in a latent, so that its scope opens
    "moe": dict(num_experts=4, num_selected=2, shared_experts=1,
                experts_held=2, first_expert=2, z_loss_coef=0.001,
                moe_latent=16),
}
ENTRIES = [("mixer", name) for name in MIXERS] + [
    ("ffn", name) for name in FFNS]


def _model(role, name):
    """A two-layer model whose layers hold the block ``name``, beside the
    plain partner (a dense FFN for a mixer, softmax attention for an
    FFN): ``(cfg, kind, the two blocks)``.  A layer without an FFN is
    spelled by a pattern string, as the public files of such models do."""
    mixer, ffn = (name, "dense") if role == "mixer" else ("attention", name)
    fields = dict(FIELDS[name])
    before = fields.pop("after", None)   # a reader's publisher: one layer
    layers = (dict(layer_pattern="**") if ffn == "none" else dict(
        layer_types=() if mixer == "latent" else (mixer,) * 2))
    if before:
        layers = dict(layer_types=(before, mixer, mixer), num_layers=3)
    cfg = LlamaConfig.tiny(
        attn_impl="flash", remat=True, max_seq_len=SEQ, **layers, **fields)
    assert cfg.kind_runs[-1] == ((mixer, ffn), 2)
    assert cfg.kind_runs[:-1] == (((before, "dense"), 1),) * bool(before)
    return cfg, (mixer, ffn), (MIXERS[mixer], FFNS[ffn])


def _publisher(cfg):
    """The block ``_model`` put before a reader's two layers, or the empty
    one: what it opens and reports stands beside the reader's."""
    return MIXERS[cfg.layer_kinds[0][0]] if len(cfg.kind_runs) > 1 else (
        MIXERS["none"])


# -- (a) the contract, every registered block --------------------------------

@pytest.mark.parametrize("role,name", ENTRIES)
def test_a_model_holds_exactly_the_tensors_a_block_declares(role, name):
    cfg, _, (mixer, ffn) = _model(role, name)
    declared = {**mixer.shapes(cfg), **ffn.shapes(cfg)}
    stack = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    axes = param_logical_axes(cfg)["layers"]
    if len(cfg.kind_runs) > 1:  # behind a publisher: the block's own run
        stack, axes = stack[-1], axes[-1]
    assert list(stack) == list(declared) == list(axes)
    for key, p in declared.items():
        assert isinstance(p, Param)
        assert stack[key].shape == (2, *p.shape), key
        assert stack[key].dtype == (p.dtype or cfg.param_dtype), key
        assert axes[key] == p.axes and p.axes[0] == "layer", key
        assert len(p.axes) == len(p.shape) + 1, key


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (as
    ``tests/test_remat_policy.py`` walks them)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _names_made(cfg, kind):
    """The ``checkpoint_name``s in the gradient's program of one layer of
    ``kind`` under the layer checkpoint."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, cfg.embed_dim))
    reads = _published_by(cfg, MIXERS[kind[0]], x)
    layer_fn = llama._checkpoint(llama._make_layer_fn(
        cfg, None, None, kind=kind, shared=reads))
    stacks = init_params(jax.random.PRNGKey(0), cfg)["layers"]
    lp = jax.tree.map(lambda a: a[0],
                      dict(llama._runs(stacks, cfg.kind_runs))[kind])
    if MIXERS[kind[0]].indexed:
        lp = dict(lp, layer_index=jnp.float32(1.0))

    def out(x, lp):
        (y, _), _ = layer_fn((x, llama._zero_aux(cfg)), lp)
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(out, argnums=(0, 1)))(x, lp).jaxpr
    return {e.params["name"] for e in _eqns(jaxpr)
            if e.primitive.name == "name"}


def _published_by(cfg, reader, x):
    """What ``reader`` reads, made by the layer before it (``_model`` puts
    its publisher there), from the stream ``x``: {} for a block that reads
    nothing."""
    if not reader.reads:
        return {}
    kind = cfg.layer_kinds[0]
    stack = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    lp = jax.tree.map(lambda a: a[0], stack)
    if MIXERS[kind[0]].indexed:
        lp = dict(lp, layer_index=jnp.float32(0.0))
    made = llama._make_layer_fn(
        cfg, None, None, kind=kind, publish=reader.reads)(
            (x, llama._zero_aux(cfg)), lp)[1][1]
    assert set(made) == set(reader.reads) <= set(MIXERS[kind[0]].publishes)
    return made


@pytest.mark.parametrize("role,name", ENTRIES)
def test_a_block_publishes_and_reads_what_it_declares(role, name):
    """A mixer's ``apply`` returns a third value, the arrays it
    ``publishes`` by name, exactly where it declares any; one that
    ``reads`` takes them as ``shared`` and fails without; FFNs do neither;
    ``indexed`` is a mixer's that reads ``lp["layer_index"]``."""
    cfg, kind, (mixer, ffn) = _model(role, name)
    assert not (ffn.publishes or ffn.reads or ffn.indexed)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, SEQ, cfg.embed_dim))
    ctx = llama.Ctx(cfg, None, lambda x, ax: x, False)
    lp = jax.tree.map(lambda a: a[0], dict(llama._runs(
        init_params(jax.random.PRNGKey(0), cfg)["layers"],
        cfg.kind_runs))[kind])
    shared = _published_by(cfg, mixer, x)
    kw = dict(shared=shared) if mixer.reads else {}
    aux = llama._zero_aux(cfg)
    if mixer.indexed:
        with pytest.raises(KeyError, match="layer_index"):
            mixer.apply(ctx, x, aux, lp, **kw)
        lp = dict(lp, layer_index=jnp.float32(1.0))
    out = mixer.apply(ctx, x, aux, lp, **kw)
    assert len(out) == (3 if mixer.publishes else 2)
    if mixer.publishes:
        assert set(out[2]) == set(mixer.publishes)
        assert all(a.shape[:2] == x.shape[:2] for a in out[2].values())
    if mixer.reads:
        with pytest.raises(TypeError, match="shared"):
            mixer.apply(ctx, x, aux, lp)


@pytest.mark.parametrize("role,name", ENTRIES)
def test_a_block_makes_the_names_it_declares_and_the_policy_keeps_them(
        role, name):
    cfg, kind, (mixer, ffn) = _model(role, name)
    assert _names_made(cfg, kind) == set(mixer.saved) | set(ffn.saved)
    assert set(mixer.saved) | set(ffn.saved) <= set(llama._saved_names())


@pytest.mark.parametrize("role,name", ENTRIES)
def test_a_step_opens_the_scopes_a_block_declares_and_no_other(role, name):
    cfg, _, (mixer, ffn) = _model(role, name)
    opt = optax.adam(1e-2)
    state = jax.eval_shape(
        lambda k: init_train_state(k, cfg, opt), jax.random.PRNGKey(0))
    text = make_train_step(cfg, opt).lower(
        state, {"tokens": TOKENS}).as_text(debug_info=True)
    seen = {scope_and_phase(n, STEP_SCOPES)[0]
            for n in re.findall(r'loc\("([^"]*)"', text)}
    # on one device: the exchange opens its scope only over an ``ep`` axis
    # (tests/test_moe.py::test_tokens_are_split_over_ep_outside_the_experts)
    # ... and the denoising objective's noise is the decoder's scope, not
    # a block's (a model with a ``block_diffusion`` group opens it)
    assert seen - {None, "scan"} == {
        "embed", *mixer.scopes, *ffn.scopes, *_publisher(cfg).scopes,
        *(["bd_noise"] if cfg.block_diffusion else []),
        "lm_head", "loss", "optimizer"} - {"moe_exchange"}
    assert set(mixer.scopes) | set(ffn.scopes) <= set(STEP_SCOPES)


@pytest.mark.parametrize("role,name", ENTRIES)
def test_every_statistic_a_block_declares_is_a_metric(role, name):
    cfg, _, (mixer, ffn) = _model(role, name)
    declared = {**mixer.stats(cfg), **ffn.stats(cfg),
                **_publisher(cfg).stats(cfg)}
    assert set(declared.values()) <= {"sum", "max", "min", "mean"}
    params = init_params(jax.random.PRNGKey(0), cfg)
    _, (metrics, _) = jax.jit(lambda p: loss_and_counts(
        p, {"tokens": TOKENS}, cfg))(params)
    assert set(metrics) == {
        "loss", "aux_loss", "perplexity", *declared,
        *([llama.BD_MASKED_SHARE] if cfg.block_diffusion else [])}
    assert all(np.isfinite(float(v)) for v in metrics.values())


# -- (b) the step's scopes, as they were --------------------------------------

def test_step_scopes_are_the_43_names_in_their_order():
    assert STEP_SCOPES == (
        "embed", "attn_qkv", "attention", "attn_out", "ffn",
        "moe_route", "moe_exchange", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_latent",
        "dsa_index", "dsa_select", "dsa_loss",
        "ssm_in", "ssm_conv", "ssm_scan", "ssm_out",
        "gdn_in", "gdn_conv", "gdn_scan", "gdn_out",
        "kda_in", "kda_conv", "kda_scan", "kda_out",
        "sconv_in", "sconv_gate", "sconv_out",
        "s6_in", "s6_conv", "s6_scan", "s6_out", "attn_diff", "gmu",
        "hc_map", "hc_mix", "mtp_in", "bd_noise", "ut_exit",
        "lm_head", "loss", "optimizer")


# -- (c) the parameter trees, as they were ------------------------------------
# The pin guards a tree's tensors and their ORDER: a stack is its tensors
# in insertion order, which a checkpoint's layout, the optimizer's state
# and the sharding rules all follow, and which a block that declares its
# tensors in another order would change without a test of values noticing.
# The tiny configurations are ``tests/tiny_models.py``'s rows (granite with
# two groups, Trinity at three layers and eight experts: the shapes pinned
# below were taken at those).

TINY = {
    "dense": {}, "moe": {}, "olmoe": {}, "granite": dict(ssm_groups=2),
    "olmo_hybrid": {}, "lfm2": {}, "xing4": {},
    "trinity": dict(num_layers=3, num_experts=8, layer_types=(
        "sliding_attention", "full_attention", "sliding_attention")),
}
_TRINITY_MIXER = (
    "attn_norm=f32[1,64] attn_post_norm=f32[1,64] wq=f32[1,64,64] "
    "wk=f32[1,64,32] wv=f32[1,64,32] wo=f32[1,64,64] q_norm=f32[1,16] "
    "k_norm=f32[1,16] wg=f32[1,64,64] mlp_norm=f32[1,64] "
    "mlp_post_norm=f32[1,64] ")
_TRINITY_EXPERTS = (
    "router=f32[1,64,8] w_gate=f32[1,4,64,32] w_up=f32[1,4,64,32] "
    "w_down=f32[1,4,32,64] router_bias=f32[1,8] shared_gate=f32[1,64,32] "
    "shared_up=f32[1,64,32] shared_down=f32[1,32,64]")
AT_PARENT = {"trinity": {"embed": "f32[128,64]",
             "layers": (_TRINITY_MIXER + "w_gate=f32[1,64,96] "
                        "w_up=f32[1,64,96] w_down=f32[1,96,64]",
                        _TRINITY_MIXER + _TRINITY_EXPERTS,
                        _TRINITY_MIXER + _TRINITY_EXPERTS),
             "final_norm": "f32[64]",
             "lm_head": "f32[64,128]"},
 "dense": {"embed": "f32[256,64]",
           "layers": "attn_norm=f32[2,64] wq=f32[2,64,64] wk=f32[2,64,64] "
                     "wv=f32[2,64,64] wo=f32[2,64,64] mlp_norm=f32[2,64] "
                     "w_gate=f32[2,64,128] w_up=f32[2,64,128] "
                     "w_down=f32[2,128,64]",
           "final_norm": "f32[64]",
           "lm_head": "f32[64,256]"},
 "moe": {"embed": "f32[256,64]",
         "layers": "attn_norm=f32[2,64] wq=f32[2,64,64] wk=f32[2,64,64] "
                   "wv=f32[2,64,64] wo=f32[2,64,64] q_norm=f32[2,64] "
                   "k_norm=f32[2,64] mlp_norm=f32[2,64] router=f32[2,64,4] "
                   "w_gate=f32[2,4,64,128] w_up=f32[2,4,64,128] "
                   "w_down=f32[2,4,128,64]",
         "final_norm": "f32[64]",
         "lm_head": "f32[64,256]"},
 "olmoe": {"embed": "f32[256,64]",
           "layers": "attn_norm=f32[2,64] wq=f32[2,64,64] wk=f32[2,64,64] "
                     "wv=f32[2,64,64] wo=f32[2,64,64] q_norm=f32[2,64] "
                     "k_norm=f32[2,64] mlp_norm=f32[2,64] router=f32[2,64,8] "
                     "w_gate=f32[2,8,64,128] w_up=f32[2,8,64,128] "
                     "w_down=f32[2,8,128,64]",
           "final_norm": "f32[64]",
           "lm_head": "f32[64,256]"},
 "granite": {"embed": "f32[256,64]",
             "layers": ("ssm_norm=f32[1,64] ssm_in=f32[1,64,296] "
                        "conv_w=f32[1,4,160] conv_b=f32[1,160] "
                        "dt_bias=f32[1,8] A_log=f32[1,8] D=f32[1,8] "
                        "gate_norm=f32[1,128] ssm_out=f32[1,128,64] "
                        "mlp_norm=f32[1,64] w_gate=f32[1,64,96] "
                        "w_up=f32[1,64,96] w_down=f32[1,96,64]",
                        "attn_norm=f32[1,64] wq=f32[1,64,64] wk=f32[1,64,32] "
                        "wv=f32[1,64,32] wo=f32[1,64,64] mlp_norm=f32[1,64] "
                        "w_gate=f32[1,64,96] w_up=f32[1,64,96] "
                        "w_down=f32[1,96,64]",
                        "ssm_norm=f32[1,64] ssm_in=f32[1,64,296] "
                        "conv_w=f32[1,4,160] conv_b=f32[1,160] "
                        "dt_bias=f32[1,8] A_log=f32[1,8] D=f32[1,8] "
                        "gate_norm=f32[1,128] ssm_out=f32[1,128,64] "
                        "mlp_norm=f32[1,64] w_gate=f32[1,64,96] "
                        "w_up=f32[1,64,96] w_down=f32[1,96,64]"),
             "final_norm": "f32[64]"},
 "olmo_hybrid": {"embed": "f32[256,64]",
                 "layers": ("gdn_norm=f32[3,64] gdn_in=f32[3,64,200] "
                            "gdn_conv_w=f32[3,4,128] gdn_dt_bias=f32[3,4] "
                            "gdn_A_log=f32[3,4] gdn_gate_norm=f32[3,16] "
                            "gdn_out=f32[3,64,64] mlp_norm=f32[3,64] "
                            "w_gate=f32[3,64,96] w_up=f32[3,64,96] "
                            "w_down=f32[3,96,64]",
                            "attn_norm=f32[1,64] wq=f32[1,64,64] "
                            "wk=f32[1,64,64] wv=f32[1,64,64] wo=f32[1,64,64] "
                            "q_norm=f32[1,64] k_norm=f32[1,64] "
                            "mlp_norm=f32[1,64] w_gate=f32[1,64,96] "
                            "w_up=f32[1,64,96] w_down=f32[1,96,64]"),
                 "final_norm": "f32[64]",
                 "lm_head": "f32[64,256]"},
 "lfm2": {"embed": "f32[128,64]",
          "layers": ("sconv_norm=f32[2,64] sconv_in=f32[2,64,192] "
                     "sconv_w=f32[2,3,64] sconv_out=f32[2,64,64] "
                     "mlp_norm=f32[2,64] w_gate=f32[2,64,96] "
                     "w_up=f32[2,64,96] w_down=f32[2,96,64]",
                     "attn_norm=f32[1,64] wq=f32[1,64,64] wk=f32[1,64,32] "
                     "wv=f32[1,64,32] wo=f32[1,64,64] q_norm=f32[1,16] "
                     "k_norm=f32[1,16] mlp_norm=f32[1,64] router=f32[1,64,8] "
                     "w_gate=f32[1,4,64,32] w_up=f32[1,4,64,32] "
                     "w_down=f32[1,4,32,64] router_bias=f32[1,8]",
                     "sconv_norm=f32[3,64] sconv_in=f32[3,64,192] "
                     "sconv_w=f32[3,3,64] sconv_out=f32[3,64,64] "
                     "mlp_norm=f32[3,64] router=f32[3,64,8] "
                     "w_gate=f32[3,4,64,32] w_up=f32[3,4,64,32] "
                     "w_down=f32[3,4,32,64] router_bias=f32[3,8]",
                     "attn_norm=f32[1,64] wq=f32[1,64,64] wk=f32[1,64,32] "
                     "wv=f32[1,64,32] wo=f32[1,64,64] q_norm=f32[1,16] "
                     "k_norm=f32[1,16] mlp_norm=f32[1,64] router=f32[1,64,8] "
                     "w_gate=f32[1,4,64,32] w_up=f32[1,4,64,32] "
                     "w_down=f32[1,4,32,64] router_bias=f32[1,8]",
                     "sconv_norm=f32[1,64] sconv_in=f32[1,64,192] "
                     "sconv_w=f32[1,3,64] sconv_out=f32[1,64,64] "
                     "mlp_norm=f32[1,64] router=f32[1,64,8] "
                     "w_gate=f32[1,4,64,32] w_up=f32[1,4,64,32] "
                     "w_down=f32[1,4,32,64] router_bias=f32[1,8]"),
          "final_norm": "f32[64]"},
 "xing4": {"embed": "f32[128,64]",
           "layers": ("attn_norm=f32[2,64] wq_a=f32[2,64,24] "
                      "q_a_norm=f32[2,24] wq_b=f32[2,24,96] "
                      "wkv_a=f32[2,64,24] kv_a_norm=f32[2,16] "
                      "wkv_b=f32[2,16,128] wo=f32[2,64,64] mlp_norm=f32[2,64] "
                      "w_gate=f32[2,64,96] w_up=f32[2,64,96] "
                      "w_down=f32[2,96,64] hc_attn_proj=f32[2,256,24] "
                      "hc_attn_bias=f32[2,24] hc_attn_scale=f32[2,3] "
                      "hc_ffn_proj=f32[2,256,24] hc_ffn_bias=f32[2,24] "
                      "hc_ffn_scale=f32[2,3]",
                      "attn_norm=f32[2,64] wq_a=f32[2,64,24] "
                      "q_a_norm=f32[2,24] wq_b=f32[2,24,96] "
                      "wkv_a=f32[2,64,24] kv_a_norm=f32[2,16] "
                      "wkv_b=f32[2,16,128] wo=f32[2,64,64] mlp_norm=f32[2,64] "
                      "router=f32[2,64,16] w_gate=f32[2,4,64,32] "
                      "w_up=f32[2,4,64,32] w_down=f32[2,4,32,64] "
                      "router_bias=f32[2,16] shared_gate=f32[2,64,32] "
                      "shared_up=f32[2,64,32] shared_down=f32[2,32,64] "
                      "hc_attn_proj=f32[2,256,24] hc_attn_bias=f32[2,24] "
                      "hc_attn_scale=f32[2,3] hc_ffn_proj=f32[2,256,24] "
                      "hc_ffn_bias=f32[2,24] hc_ffn_scale=f32[2,3]"),
           "final_norm": "f32[64]",
           "lm_head": "f32[64,128]",
           "mtp": {"h_norm": "f32[64]",
                   "e_norm": "f32[64]",
                   "proj": "f32[128,64]",
                   "final_norm": "f32[64]",
                   "layers": "attn_norm=f32[1,64] wq_a=f32[1,64,24] "
                             "q_a_norm=f32[1,24] wq_b=f32[1,24,96] "
                             "wkv_a=f32[1,64,24] kv_a_norm=f32[1,16] "
                             "wkv_b=f32[1,16,128] wo=f32[1,64,64] "
                             "mlp_norm=f32[1,64] router=f32[1,64,16] "
                             "w_gate=f32[1,4,64,32] w_up=f32[1,4,64,32] "
                             "w_down=f32[1,4,32,64] router_bias=f32[1,16] "
                             "shared_gate=f32[1,64,32] shared_up=f32[1,64,32] "
                             "shared_down=f32[1,32,64] "
                             "hc_attn_proj=f32[1,256,24] "
                             "hc_attn_bias=f32[1,24] hc_attn_scale=f32[1,3] "
                             "hc_ffn_proj=f32[1,256,24] hc_ffn_bias=f32[1,24] "
                             "hc_ffn_scale=f32[1,3]"}}}


def _sketch(tree):
    """A tree of arrays as ``AT_PARENT`` writes it."""
    def leaf(a):
        dtype = {"float32": "f32", "bfloat16": "bf16"}[str(a.dtype)]
        return f"{dtype}[{','.join(map(str, a.shape))}]"

    if isinstance(tree, tuple):
        return tuple(map(_sketch, tree))
    if not isinstance(tree, dict):
        return leaf(tree)
    if all(hasattr(v, "shape") for v in tree.values()):
        return " ".join(f"{k}={leaf(v)}" for k, v in tree.items())
    return {k: _sketch(v) for k, v in tree.items()}


@pytest.mark.parametrize("kind", list(TINY))
def test_the_parameter_tree_is_what_the_parent_built(kind):
    # not ``eval_shape``: a tree that went through JAX has its keys sorted
    got = _sketch(init_params(jax.random.PRNGKey(0),
                              tiny_models.tiny(kind, **TINY[kind])))
    assert got == AT_PARENT[kind]
    assert list(got) == list(AT_PARENT[kind])   # dicts compare unordered


def test_every_registered_block_stands_in_a_tiny_model():
    """A new mixer or FFN cannot arrive without a row of the table that
    runs it."""
    kinds = {kind for name in tiny_models.ROWS
             for kind in tiny_models.tiny(name).layer_kinds}
    assert set(MIXERS) <= {mixer for mixer, _ in kinds}
    assert set(FFNS) <= {ffn for _, ffn in kinds}


# -- (d) the seam, shown -------------------------------------------------------

def test_a_mixer_registered_here_trains_with_no_edit_anywhere_else(
        monkeypatch):
    """One parameter, one scope, one saved name, one ``max`` statistic: a
    mixer this file alone knows is named in ``layer_types``, initialised,
    scanned beside a softmax layer, kept by the layer checkpoint, trained
    and reported."""
    stats = {"probe_absmax": "max"}

    def shapes(cfg):
        d = cfg.embed_dim
        return {"probe_w": Param((d, d), ("layer", "kernel_in", None))}

    def apply(ctx, x, aux, lp, residual=True):
        with jax.named_scope("probe"):
            y = checkpoint_name(x @ lp["probe_w"].astype(ctx.cfg.dtype),
                                "probe_out")
            peak = jnp.max(jnp.abs(y)).astype(jnp.float32)
            return add(ctx, x, y, residual), fold(
                aux, {"probe_absmax": peak}, stats)

    monkeypatch.setitem(MIXERS, "probe", Block(
        shapes, apply, saved=("probe_out",), scopes=("probe",),
        stats=lambda cfg: stats))
    cfg = LlamaConfig.tiny(layer_types=("probe", "attention", "probe"),
                           num_layers=3, remat=True)
    assert cfg.layer_runs == (("probe", 1), ("attention", 1), ("probe", 1))
    assert "probe_out" in llama._saved_names()
    assert "probe" in blocks.layer_scopes()
    assert "probe_out" in _names_made(cfg, ("probe", "dense"))
    opt = optax.adam(1e-2)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    assert state.params["layers"][0]["probe_w"].shape == (1, 64, 64)
    step = make_train_step(cfg, opt, donate=False)
    losses = []
    for _ in range(2):
        state, metrics = step(state, {"tokens": TOKENS})
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    assert float(metrics["probe_absmax"]) > 0.0

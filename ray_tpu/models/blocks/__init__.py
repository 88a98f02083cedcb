"""ray_tpu.models.blocks — what a decoder layer is composed of: a MIXER
(``MIXERS``) followed by an FFN (``FFNS``), each on a RESIDUAL
(``residual.py``); either may be ``none``, the empty block
(``base.EMPTY_MIXER``, ``base.EMPTY_FFN``), in a model whose layers are one
sub-block each.  A mixer or FFN is ONE module that ends in ONE
``base.Block`` (the softmax mixer is registered three times: ``attention``
and ``full_attention`` see every earlier token, ``sliding_attention`` is the
same module under the model's ``sliding_window``; ``indexed``, what a model
with an ``sa_config`` runs for ``attention``, is its q, k, v and output under
a learned selection of keys; differential attention three times likewise:
under the window, in full — the layer whose keys and values later layers
read — and ``diff_cross``, which reads them).  To add one: write the
module, register its ``Block`` below
under the name ``layer_types`` gives it, and list its scopes in the
``"scopes"`` of the benchmark configuration that uses it; its
``LlamaConfig`` fields sit in ``models/llama.py`` with the others (the
benchmark builds the configuration from flat keys).  Nothing else in the
tree names a mixer.
"""

from ray_tpu.models.blocks import (
    attention, base, conv, delta, ffn, gmu, kda, mamba, mamba1, residual)

MIXERS = {
    "attention": attention.SOFTMAX,
    "full_attention": attention.SOFTMAX,   # as the public files spell it
    # ... the same mixer under the model's ``sliding_window``
    "sliding_attention": attention.SLIDING,
    "latent": attention.LATENT,
    # softmax attention over the keys a learned indexer picks: what a model
    # with an ``sa_config`` runs for ``attention``
    "indexed": attention.INDEXED,
    # ... under the block rule, over two streams of one sequence: what a
    # model with a ``block_diffusion`` group runs for ``attention``
    "block_attention": attention.BLOCK_RULE,
    "mamba": mamba.BLOCK,
    "linear_attention": delta.BLOCK,
    "kda": kda.BLOCK,      # ... its decay a vector over the key channels
    "conv": conv.BLOCK,
    # a SambaY decoder's five (``LlamaConfig.mb_per_layer``): a selective
    # scan; differential attention under the model's ``sliding_window``, over
    # every earlier token (its keys and values are what it PUBLISHES), and
    # from later layers onto those keys and values; a gated memory unit that
    # READS the nearest earlier scan's output
    "mamba1": mamba1.BLOCK,
    "diff_sliding": attention.DIFF_SLIDING,
    "diff_full": attention.DIFF_FULL,
    "diff_cross": attention.DIFF_CROSS,
    "gmu": gmu.BLOCK,
    "none": base.EMPTY_MIXER,
}
FFNS = {"dense": ffn.DENSE, "moe": ffn.MOE, "none": base.EMPTY_FFN}


def layer_scopes():
    """The scopes a layer can open, each once: the plain layer's first (the
    softmax mixer's, then every FFN's), then the other mixers' in the order
    they are registered, then the residual's."""
    blocks = (MIXERS["attention"], *FFNS.values(), *MIXERS.values())
    return tuple(dict.fromkeys(
        [s for b in blocks for s in b.scopes] + list(residual.SCOPES)))

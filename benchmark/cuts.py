"""How a configuration file may differ from its public ``config.json``: the
rule, in one place.  ``complaints(conf, published)`` returns what is wrong
with a file of ``benchmark/configs`` held against the copy of its source
under ``testdata/published``, one line a fault, none for a sound file.  The
benchmark's tests call it on every file; ``rehearse_compile.py`` prints it
before it compiles.

A key of both files is equal unless the file lists it under ``reduced``; no
number of the public file is left out (``NOT_READ`` apart); nothing is added
that is not the benchmark's own (``OWN_KEYS``) or explained under
``assumed``.  Each ``reduced`` entry holds ``published``, ``run`` and
``why``, and is of one of five kinds, which it names under ``"kind"``
(without the key it is ``depth``):

- ``depth``: the key that counts the layers, which is the one that the
  file's ``llama_config`` gives the program as ``num_layers``.
- ``experts_held``: the key that counts the routed experts (``EXPERTS``
  below: ``n_routed_experts``, ``num_experts``, ``num_local_experts``)
  holds how many THIS chip holds.
- ``vocabulary``: ``vocab_size`` holds the rows of this chip's slice.
- ``pattern``: a list of the public file with one entry a layer; ``run`` is
  the first ``<depth>`` entries of ``published``.
- ``leading_dense``: the key that the file's ``share.leading_dense`` names,
  where the public file gives it as a WHOLE NUMBER (``num_dense_layers``,
  ``first_k_dense_replace``, ``moe_layer_start_index``), holds how many of
  the leading dense layers THIS chip keeps, from 1 to the published count.
  They are of one kind, so one of them is "the leading dense layers once";
  those left out lie on further chips as the stages of a pipeline, which
  the entry's ``why`` says.  Only in a file that states a share; a key
  whose public value is a list (``mlp_layer_types``, ``mlp_only_layers``)
  is not cut by it.

A kind admits the keys named here and no other, so nothing else is ever cut:
no width, no count of heads, of groups or of shared experts.  A file with
an ``experts_held``, a ``vocabulary`` or a ``leading_dense`` entry is one
chip's share of a stated deployment (model-configs guide, section 4): it
says so under ``"share"`` (``{"chips_per_layer": n, "how": "..."}``; with
experts held ``"leading_dense"``: the key of the public file that says how
many dense layers lead, see ``leading_dense`` below, or null where it has
none; optionally ``"vocabulary_over"``, below) and ``"deployment"``.  The
two shares are stated apart: ``experts held x chips_per_layer`` is the
published count of experts, and ``rows x vocabulary_over`` the published
vocabulary, where ``vocabulary_over`` is a whole number from 2 that
divides ``chips_per_layer`` (256 experts over 32 chips, the rows over 8 of
them; ``how`` says which chips split the rows) and, left unsaid, is
``chips_per_layer`` itself.  The file keeps to the guide's floors: at
least 8 experts held, at least an eighth of the vocabulary (so
``vocabulary_over`` is at most 8), the leading dense layers once with at
least four layers after them, and a whole period of every per-layer list
of the public file.  The layers after the dense ones are the depth less
the leading dense layers KEPT (the ``leading_dense`` entry's ``run``; the
published count where no entry cuts it), and a list's period is read
behind the PUBLISHED count of dense layers.  The floors bind files that
state a share only, and experts are held only by a file that states one.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))

# keys of a configuration file that are the benchmark's own
# (``architectures`` is the public file's; the catalog's rows leave it out)
OWN_KEYS = {"source", "paper", "reduced", "assumed", "deployment", "share",
            "llama_config", "reference", "flops", "scopes", "kernels",
            "check", "architectures"}
# numbers of a public file that no model code reads: a file may leave them out
NOT_READ = {"bos_token_id", "eos_token_id", "pad_token_id",
            "initializer_range", "pretraining_tp"}
KINDS = ("depth", "experts_held", "vocabulary", "pattern",
         "leading_dense")
# the public key that counts the routed experts, by its name
EXPERTS = re.compile(r"^(n|num)_(routed_|local_)?experts$")
MIN_EXPERTS_HELD = 8
MIN_VOCABULARY_SHARE = 8    # at least an eighth
MIN_LAYERS_AFTER_DENSE = 4


def _is_count(x: Any) -> bool:
    return isinstance(x, int) and not isinstance(x, bool)


def period(entries: Sequence) -> int:
    """The leading period of a per-layer list: the fewest entries that hold
    every kind the list has and come again right after themselves, as far
    as the list goes (so an irregular tail does not count); the whole list
    where nothing shorter does."""
    entries = list(entries)
    kinds = set(map(str, entries))
    for p in range(1, len(entries)):
        again = entries[p:2 * p]
        if set(map(str, entries[:p])) == kinds \
                and again == entries[:len(again)]:
            return p
    return len(entries)


def leading_dense(published: Dict[str, Any], key: str):
    """How many dense layers lead, read from ``key`` of the public file,
    whatever that file calls it.  A whole number is the count
    (``first_k_dense_replace``, ``moe_layer_start_index``); a list of layer
    numbers (``mlp_only_layers``) counts those that lead from 0; a list with
    one entry a layer (``mlp_layer_types``) counts the entries before the
    first of the last layer's kind.  None where the value is none of these."""
    value = published.get(key) if isinstance(key, str) else None
    if _is_count(value):
        return value
    if isinstance(value, list) and all(map(_is_count, value)):
        return next(i for i in range(len(value) + 1) if i not in value)
    if isinstance(value, list):
        return value.index(value[-1])
    return None


def complaints(conf: Dict[str, Any], published: Dict[str, Any]) -> List[str]:
    """What is wrong with ``conf`` as a cut of ``published``; [] if nothing."""
    out: List[str] = []
    reduced = conf.get("reduced", {})
    assumed = conf.get("assumed", {})

    for key, value in published.items():
        if key in conf and conf[key] != value and key not in reduced:
            out.append(f"{key}: differs from the published file and is not "
                       "listed under reduced")
    left_out = sorted(k for k, v in published.items() if k not in conf
                      and isinstance(v, (int, float))
                      and not isinstance(v, bool) and k not in NOT_READ)
    if left_out:
        out.append(f"{', '.join(left_out)}: numbers of the published file "
                   "left out")
    for key in sorted(set(conf) - set(published) - OWN_KEYS):
        if key not in assumed:
            out.append(f"{key}: neither a key of the published file, nor the "
                       "benchmark's own, nor explained under assumed")
        elif conf[key] != assumed[key].get("value"):
            out.append(f"{key}: assumed states another value than the file "
                       "holds")
    for kind, module in (("reference", conf.get("reference")),
                         ("", conf.get("flops"))):
        if not module or not os.path.isfile(
                os.path.join(HERE, kind, module + ".py")):
            out.append(f"{kind or 'flops'}: no module "
                       f"benchmark/{os.path.join(kind, str(module))}.py")

    by_kind: Dict[str, List[str]] = {k: [] for k in KINDS}
    layers_key = conf.get("llama_config", {}).get("num_layers")
    share = conf.get("share")
    dense_key = share.get("leading_dense") if isinstance(share, dict) else None
    for key, cut in reduced.items():
        if not {"published", "run", "why"} <= set(cut):
            out.append(f"reduced[{key}]: needs published, run and why")
            continue
        if key not in published or key not in conf:
            out.append(f"reduced[{key}]: not a key of both files")
            continue
        if (cut["published"], cut["run"]) != (published[key], conf[key]):
            out.append(f"reduced[{key}]: published and run are not what the "
                       "two files hold")
        if conf[key] == published[key]:
            out.append(f"reduced[{key}]: listed, and equal to the published "
                       "value")
        kind = cut.get("kind", "depth")
        if kind not in KINDS:
            out.append(f"reduced[{key}]: kind {kind!r} is none of "
                       f"{', '.join(KINDS)}")
        elif kind == "depth" and key != layers_key:
            out.append(f"reduced[{key}]: kind depth is for the key that "
                       "llama_config gives the program as num_layers "
                       f"({layers_key}); no other count and no width is "
                       "ever cut")
        elif kind == "experts_held" and not EXPERTS.match(key):
            out.append(f"reduced[{key}]: kind experts_held is for the key "
                       "that counts the routed experts (n_routed_experts, "
                       "num_experts, num_local_experts)")
        elif kind == "vocabulary" and key != "vocab_size":
            out.append(f"reduced[{key}]: kind vocabulary is for vocab_size")
        elif kind == "leading_dense" and key != dense_key:
            out.append(f"reduced[{key}]: kind leading_dense is for the key "
                       "that the file's share.leading_dense names "
                       f"({dense_key}), in a file that states a share; no "
                       "other count and no width is ever cut")
        elif kind == "pattern" and not (isinstance(published[key], list)
                                        and isinstance(conf[key], list)):
            out.append(f"reduced[{key}]: kind pattern is for a list")
        elif kind != "pattern" and not (
                _is_count(published[key]) and _is_count(conf[key])
                and 1 <= conf[key] <= published[key]):
            out.append(f"reduced[{key}]: kind {kind} is for whole numbers, "
                       "run from 1 to published")
        else:
            by_kind[kind].append(key)
    if len(by_kind["experts_held"]) > 1:
        out.append(f"reduced: {' and '.join(by_kind['experts_held'])} are "
                   "both of kind experts_held")

    depth_key = by_kind["depth"][0] if by_kind["depth"] else None
    for key in by_kind["pattern"]:
        if depth_key is None:
            out.append(f"reduced[{key}]: a pattern is cut with the depth, "
                       "and no entry of kind depth is listed")
        elif len(published[key]) != published[depth_key]:
            out.append(f"reduced[{key}]: the published list has "
                       f"{len(published[key])} entries for "
                       f"{published[depth_key]} layers")
        elif conf[key] != published[key][:conf[depth_key]]:
            out.append(f"reduced[{key}]: run is not the first "
                       f"{conf[depth_key]} entries of the published list")

    if (by_kind["experts_held"] or by_kind["vocabulary"]
            or by_kind["leading_dense"]):
        out += _share_complaints(conf, published, by_kind, depth_key)
    return out


def _share_complaints(conf, published, by_kind, depth_key) -> List[str]:
    """The guide's section 4, for a file that holds one chip's share."""
    out: List[str] = []
    share = conf.get("share")
    if not (isinstance(share, dict) and _is_count(share.get("chips_per_layer"))
            and share["chips_per_layer"] >= 2 and share.get("how")):
        return ["share: a file with experts held or a vocabulary slice "
                'states {"chips_per_layer": n, "how": "..."}']
    if not conf.get("deployment"):
        out.append("deployment: a share states the deployment it is one "
                   "chip's part of")
    chips = share["chips_per_layer"]
    # the chips that split the vocabulary's rows: all that share a layer,
    # unless the file states them apart
    rows_over = "vocabulary_over" if "vocabulary_over" in share \
        else "chips_per_layer"
    over = share[rows_over]
    if not (_is_count(over) and over >= 2 and chips % over == 0):
        out.append(f"share: vocabulary_over {over!r} is no whole number "
                   f"from 2 that divides chips_per_layer {chips}")
        over = None
    for key in by_kind["experts_held"]:
        if conf[key] * chips != published[key]:
            out.append(f"reduced[{key}]: run {conf[key]} x chips_per_layer "
                       f"{chips} is not the published {published[key]}")
        if conf[key] < MIN_EXPERTS_HELD:
            out.append(f"reduced[{key}]: {conf[key]} experts held; a share "
                       f"keeps at least {MIN_EXPERTS_HELD}")
    for key in by_kind["vocabulary"] if over else ():
        if conf[key] * over != published[key]:
            out.append(f"reduced[{key}]: run {conf[key]} x {rows_over} "
                       f"{over} is not the published {published[key]}")
        if conf[key] * MIN_VOCABULARY_SHARE < published[key]:
            out.append(f"reduced[{key}]: {conf[key]} rows are under an "
                       "eighth of the vocabulary")
    dense = 0
    if by_kind["experts_held"] and "leading_dense" not in share:
        out.append("share: a file with experts held states leading_dense, "
                   "the key of the published file that says how many dense "
                   "layers lead (null where it has none)")
    elif share.get("leading_dense") is not None:
        dense = leading_dense(published, share["leading_dense"])
        if dense is None:
            out.append(f"share: leading_dense names "
                       f"{share['leading_dense']!r}, which is no whole "
                       "number and no list of the published file")
            dense = 0
    if depth_key is None:
        return out
    # the dense layers KEPT: the published count unless an entry cuts it
    kept = conf[by_kind["leading_dense"][0]] if by_kind["leading_dense"] \
        else dense
    after = conf[depth_key] - kept
    if after < MIN_LAYERS_AFTER_DENSE:
        out.append(f"reduced[{depth_key}]: {after} layers after the {kept} "
                   "leading dense ones; a share keeps at least "
                   f"{MIN_LAYERS_AFTER_DENSE}")
    for key, value in published.items():
        if not (isinstance(value, list) and key in conf
                and len(value) == published[depth_key]):
            continue
        whole = period(value[dense:])
        if after < whole:
            out.append(f"reduced[{depth_key}]: {after} layers after the "
                       f"{kept} leading dense ones are not a whole period "
                       f"of {key} ({whole})")
    return out

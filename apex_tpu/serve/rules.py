"""Regex-driven serve layout rules: leaf path -> PartitionSpec.

Generalizes :mod:`apex_tpu.zero.rules` (the ``match_partition_rules``
shape, SNIPPETS.md [2]) from ZeRO's binary shard/replicate decisions to
real ``PartitionSpec`` construction: an ordered ``(regex, decision)``
table matched with ``re.search`` against the leaf's slash-joined tree
path, first match wins, no-match is an error. Decisions:

- ``"replicate"`` — full copy per rank (``P()``);
- ``"shard:<axis>"`` — put the tensor-parallel mesh axis at tensor
  dimension ``<axis>`` (``"shard:1"`` on a ``[in, out]`` kernel is the
  Megatron column shard);
- ``"heads"`` — shorthand for ``"shard:1"``, the heads dimension of the
  KV-cache's stacked ``[L, kv_heads, num_pages]`` scale arrays. Every
  cache leaf shards its heads dimension over the tensor axis (a layer's
  pool leaf, ``[kv_heads, ...]``, is ``"shard:0"``), so each rank's
  pool holds its local heads' pages and the paged-attention reads stay
  rank-local.

Two default tables ship: :data:`CACHE_RULES` for the paged KV-cache
state and :data:`GPT_PARAM_RULES` for the GPT parameter tree the serve
model reads (column layers split their output dim, row layers their
input dim, the embedding its vocab dim — matching what the TP layers'
sliced init produces, so a full tp=1 tree fed through ``shard_map``
``in_specs`` lands each rank exactly its training-time shard).
"""

from __future__ import annotations

import re
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state as ps
from apex_tpu.zero.rules import first_match, leaf_path_names

REPLICATE = "replicate"
HEADS = "heads"

#: KV-cache layout: one pool leaf a layer, [kv_heads, num_pages,
#: page_size, 2*d] (heads dim 0); per-page fp8 scales are [L, kv_heads,
#: num_pages] (heads dim 1) — the heads dim sharded over the tensor axis.
CACHE_RULES: tuple = (
    (r"pools/\d+", "shard:0"),
    (r"(k|v)_scale", HEADS),
    (r".*", REPLICATE),
)

#: The GPT param tree under serve TP: same layout the training TP
#: layers shard to (qkv packs per-head [q|k|v] column groups, so the
#: contiguous column split IS the head split).
GPT_PARAM_RULES: tuple = (
    (r"attn/qkv/kernel", "shard:1"),
    (r"attn/qkv/bias", "shard:0"),
    (r"attn/proj/kernel", "shard:0"),
    (r"mlp/fc1/kernel", "shard:1"),
    (r"mlp/fc1/bias", "shard:0"),
    (r"mlp/fc2/kernel", "shard:0"),
    (r"wte/embedding", "shard:0"),
    (r".*", REPLICATE),
)


def _parse_decision(rx: str, decision: str) -> int | None:
    """None = replicate, int = tensor dim carrying the tp axis."""
    if decision == REPLICATE:
        return None
    if decision == HEADS:
        return 1
    m = re.fullmatch(r"shard:(\d+)", decision)
    if m is None:
        raise ValueError(
            f"serve rule ({rx!r}, {decision!r}): decision must be "
            f"{REPLICATE!r}, {HEADS!r} or 'shard:<dim>'")
    return int(m.group(1))


def match_serve_rules(
    rules: Sequence[tuple[str, str]],
    tree: Any,
    *,
    axis_name: str = ps.TENSOR_AXIS,
    world: int | None = None,
    validate: bool | str = True,
) -> Any:
    """Pytree of ``PartitionSpec`` matching ``tree``.

    ``world``: the tensor-parallel size the specs must divide
    (default: the installed mesh's tensor axis). ``world == 1`` is the
    structural override — everything replicates (``P()``) so the same
    code path serves the single-chip engine. A sharded leaf whose
    target dim does not divide by ``world`` is an error at rule time,
    not a shard_map crash later.

    ``validate``: run the apexlint APXR table checks
    (:mod:`apex_tpu.lint.rules_tables`) against THIS tree at
    config-build time, raising with the finding text on shadowed rules
    (APXR202) or bad / out-of-range / non-divisible decisions
    (APXR203). ``"strict"`` additionally rejects dead rules and
    uncovered leaves (APXR201); ``False`` opts out for exploratory
    tables.
    """
    rules = tuple(rules)
    parsed = [(rx, _parse_decision(rx, d)) for rx, d in rules]
    w = ps.get_tensor_model_parallel_world_size() if world is None \
        else int(world)
    if validate:
        from apex_tpu.lint.rules_tables import constructor_validate
        constructor_validate(rules, [tree],
                             table_name="match_serve_rules",
                             kind="serve", world=max(w, 1),
                             strict=validate == "strict")

    def decide(path, leaf):
        name = "/".join(leaf_path_names(path))
        if w <= 1 or leaf is None:
            return P()
        idx = first_match(rules, name)
        if idx is None:
            raise ValueError(
                f"no serve layout rule matched leaf {name!r} — add a "
                f"rule (('.*', 'replicate') is the safe catch-all)")
        rx, dim = parsed[idx]
        if dim is None:
            return P()
        shape = np.shape(leaf)
        if dim >= len(shape) or shape[dim] % w:
            raise ValueError(
                f"serve rule {rx!r} shards dim {dim} of "
                f"{name!r} (shape {shape}) over {axis_name}="
                f"{w}: not divisible")
        spec = [None] * len(shape)
        spec[dim] = axis_name
        return P(*spec)

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [decide(p, x) for p, x in flat])

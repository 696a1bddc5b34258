"""PartitionSpecs for parameters, optimizer state, inputs, and caches.

Layout policy (DESIGN.md §6):
  * 2-D param sharding: "width" dims (d_model) over ``data`` (FSDP/ZeRO-3),
    "parallel" dims (heads*hd, d_ff, vocab, experts) over ``model`` (TP/EP).
    Params are replicated over ``pod`` (DP between pods).
  * MoE experts shard over ``model`` when divisible (llama4 128/16) else TP
    inside the expert FFN (qwen2-moe 60 experts).
  * Batch dims shard over ("pod","data") when divisible, falling back to
    "data" or replication (long_500k has batch=1).
  * Decode KV caches shard sequence over ``model`` (flash-decode combine) and
    batch over data axes.

Every rule validates divisibility against the actual mesh before applying;
non-divisible dims degrade to replication (never a wrong-answer shard).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import batch_axes


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fit(mesh, spec: P, shape: tuple) -> P:
    """Drop spec axes that don't divide the corresponding dim."""
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is not None and dim % _axis_size(mesh, axes) == 0:
            out.append(axes)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

_COL = {  # (..., d_in, parallel_out): d_in over data, out over model
    "wq", "wk", "wv", "w_gate", "w_up", "w_x", "w_r", "w_i",
    "w_k", "w_v", "w_g", "cm_k", "cm_r", "decay_a", "mu_a", "lm_head",
}
_ROW = {  # (..., parallel_in, d_out): in over model, d_out over data
    "wo", "w_down", "w_out", "w_o", "cm_v", "decay_b", "mu_b",
}
_REPL = {"norm1", "norm2", "scale", "bias", "lam", "decay_base", "mu_base",
         "bonus", "conv", "router", "bq", "bk", "bv"}


def param_spec(cfg, path: tuple, leaf) -> P:
    """PartitionSpec for one parameter leaf, identified by its tree path."""
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    stacked = "units" in names            # leading num_units dim from vmap
    lead = (None,) if stacked else ()
    expert = any("moe" in n for n in names) and name in (
        "w_gate", "w_up", "w_down") and not any(n == "shared" for n in names)

    if name == "embed":
        # vocab over model, d over data. The token gather does force an
        # all-gather of the table (XLA "involuntary full rematerialization"
        # warning), but that transient is CHEAPER than d-sharding the table:
        # measured 21.9 vs 25.8 GiB/device on recurrentgemma train_4k
        # (refuted hypothesis logged in EXPERIMENTS.md §Perf).
        return P("model", "data")
    if name == "frontend_proj":
        return P("data", "model")
    if name == "lm_head":
        return P("data", "model")
    if expert:
        # (E, d, f) or (E, f, d)
        if cfg.num_experts % 16 == 0:     # EP over model
            return P(*lead, "model", "data", None)
        if name in ("w_gate", "w_up"):    # TP inside expert
            return P(*lead, None, "data", "model")
        return P(*lead, None, "model", "data")
    if name in _COL:
        return P(*lead, "data", "model")
    if name in _ROW:
        return P(*lead, "model", "data")
    return P()  # norms, scalars, biases, router — replicate


def make_param_shardings(cfg, mesh, params_shape):
    """Pytree of NamedShardings matching an eval_shape'd params pytree."""
    def one(path, leaf):
        spec = _fit(mesh, param_spec(cfg, path, leaf), leaf.shape)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params_shape)


def make_opt_shardings(cfg, mesh, opt_shape):
    """Shardings for an AdamWState pytree (any moment dtype).

    Moments mirror their param's sharding; int8-quantized moments add {q, s}
    leaves — q shards like its param, s (a (..., 1) row scale) drops the
    trailing-axis spec via divisibility fitting.
    """
    def one(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        if names and names[-1] in ("q", "s"):
            path = path[:-1]
        spec = _fit(mesh, param_spec(cfg, path, leaf), leaf.shape)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, opt_shape)


# ---------------------------------------------------------------------------
# input / batch specs
# ---------------------------------------------------------------------------

def _batch_spec_axes(mesh, batch: int):
    from repro.models import partition
    if partition.BATCH_AXES_OVERRIDE:
        want = tuple(a for a in partition.BATCH_AXES_OVERRIDE
                     if a in mesh.axis_names)
        for k in range(len(want), 0, -1):  # longest dividing prefix
            if batch % _axis_size(mesh, want[:k]) == 0:
                return want[:k]
    ba = batch_axes(mesh)
    if ba and batch % _axis_size(mesh, ba) == 0:
        return ba
    if "data" in mesh.axis_names and batch % mesh.shape["data"] == 0:
        return "data"
    return None


def train_batch_shardings(cfg, mesh, batch: int):
    ba = _batch_spec_axes(mesh, batch)
    tok = NamedSharding(mesh, P(ba, None))
    if cfg.frontend != "tokens":
        tok = NamedSharding(mesh, P(ba, None, None))
    return {
        "inputs": tok,
        "labels": NamedSharding(mesh, P(ba, None)),
        "positions": NamedSharding(mesh, P(ba, None)),
    }


def tree_cache_shardings(cfg, mesh, cache_shape, batch: int):
    """Shardings matching serve.init_cache: KV caches shard sequence over
    ``model`` (flash-decode partial-softmax combine) and batch over data axes;
    recurrent states shard their width dims over ``model``."""
    ba = _batch_spec_axes(mesh, batch)

    def one(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        stacked = "units" in names       # leading num_units dim
        lead = (None,) if stacked else ()
        nd = leaf.ndim - len(lead)
        if names[-1] in ("k", "v") and nd == 4:     # (B, S_c, KV, hd)
            spec = P(*lead, ba, "model", None, None)
        elif nd == 4:                               # rwkv wkv (B, H, hdk, hdv)
            spec = P(*lead, ba, None, "model", None)
        elif nd == 3:                               # rec conv (B, K-1, d)
            spec = P(*lead, ba, None, "model")
        elif nd == 2:                               # shift/h states (B, d)
            spec = P(*lead, ba, "model")
        else:
            spec = P()
        return NamedSharding(mesh, _fit(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Hippo shard placement (core.partition): shard axis over ``data``
# ---------------------------------------------------------------------------

def sharded_hippo_shardings(mesh, state):
    """NamedShardings for a ``core.partition.ShardedHippoState``.

    Every stacked leaf's leading shard axis goes over the mesh ``data`` axis
    (divisibility-fitted, degrading to replication like every other rule
    here) — including the per-shard histogram ``bounds``, which gained a
    shard axis with the drift-resummarization layer. Under this placement
    the shard-axis sums in ``core.index.search_compact_many_sharded`` lower
    to the cross-device AllReduce — the ``jax.lax.psum`` of the count-reduce
    engine.
    """
    from repro.core import index as hix
    from repro.core.partition import ShardedHippoState

    def one(leaf, lead_sharded):
        spec = P("data") if lead_sharded else P()
        return NamedSharding(mesh, _fit(mesh, spec, leaf.shape))

    shards = hix.HippoState(*(
        one(leaf, ax == 0)
        for leaf, ax in zip(state.shards, hix.SHARD_AXES)))
    return ShardedHippoState(shards=shards,
                             summaries=one(state.summaries, True))


def shard_slab_shardings(mesh, slab):
    """Sharding for (S, PPS, page_card) table slabs: shard axis over ``data``."""
    return NamedSharding(mesh, _fit(mesh, P("data"), slab.shape))


def place_sharded(mesh, state, keys, valid):
    """device_put a ``ShardedHippoState`` + its table slabs onto the mesh.

    Returns (state, keys, valid) with every shard-axis array placed over the
    ``data`` axis; pass them straight to ``search_compact_many_sharded``.
    """
    st = jax.device_put(state, sharded_hippo_shardings(mesh, state))
    k = jax.device_put(keys, shard_slab_shardings(mesh, keys))
    v = jax.device_put(valid, shard_slab_shardings(mesh, valid))
    return st, k, v

"""A routed feed-forward layer that is told which experts it holds.

The layer of a model whose every token chooses ``experts_per_token`` of
``n_experts`` experts, as one chip of an expert-parallel group runs it: the
router keeps its whole width and chooses over all experts; this chip holds
the experts ``[first, first + count)`` (``cfg.experts_held``) and computes
**their part of the result** for the tokens that chose them, and the shared
expert, which every chip computes alike.  What the absent experts would
have added is left out, here as in the plain reference
(``chipbench/solar_ref.py``), and nothing stands in for the other chips or
their exchange; ``tests/test_routed_delta_serving.py`` adds the shares up
to the uncut layer.

    s = sigmoid(W_r u);  chosen = the k largest of s + b
    w_e = s_e / sum of the chosen s  (norm_topk), times routed_scale
    moe(u) = sum over chosen e held here of w_e E_e(u) + E_shared(u)
    E(u) = W_down (silu(W_gate u) * (W_up u))

**Dropless**: no capacity and no dropped token.  The token-expert pairs on
held experts are sorted by expert and go through two grouped products
(``jax.lax.ragged_dot``: every expert's rows by its own weights, an expert
nobody chose costs nothing), not every token through every expert.  The
weights of a run of layers are stacked ``(layers, count, in, out)`` and go
to the grouped product whole, as ``layers * count`` groups of which only
this layer's have rows: a layer sliced out of the stack inside a scan
would be copied, a third of a gigabyte a layer, before a product that is
bound by reading it once.

Precision: the routing decision (the router's product, the sigmoid, the
choice, the normalisation) in float32 at the highest precision; the
experts' products as every other of the block (:func:`.hybrid._mm`: the
bfloat16 weights exact, the activations as bfloat16 terms).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import hybrid as _hybrid

__all__ = ["COUNTS", "EXPERT_LEAVES", "route", "routed_feed_forward"]

#: A run's leaves that hold the routed experts: ``we_in`` (layers, count,
#: units, 2 * width), gate beside up, and ``we_out`` (layers, count, width,
#: units), both (in, out) as the grouped product takes them.
EXPERT_LEAVES = ("we_in", "we_out")

#: What a routed layer counts in a launch (summed over layers and launches
#: in the pools' ``counts``): the token-expert pairs on experts held here,
#: the pairs on experts of other chips, the held experts that at least one
#: token chose, the pairs of the fullest held expert, and 1 (the layer
#: launches, for the means).
COUNTS = ("pairs", "pairs_elsewhere", "experts_hit", "pairs_fullest",
          "layer_launches")


def route(u, lp, cfg):
    """The experts each token of ``u`` (T, units) chooses and their weights:
    ``(idx, w)``, both (T, experts_per_token).  Float32, highest precision."""
    s = jax.nn.sigmoid(_hybrid._mm_hi(u, lp["w_router"]))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           cfg.experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    return idx, w * cfg.routed_scale


def _grouped(x, w, sizes):
    """``x[r] @ w[group of r]`` for rows sorted by group, float32.  bfloat16
    weights: a row goes in as its two bfloat16 terms, one below the other,
    so the weights are read once (:func:`.hybrid._mm`)."""
    if w.dtype != jnp.bfloat16:
        return jax.lax.ragged_dot(x.astype(w.dtype), w, sizes,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
    rows = _hybrid._split(x).swapaxes(0, 1).reshape(-1, x.shape[-1])
    out = jax.lax.ragged_dot(rows, w, sizes * 2,
                             preferred_element_type=jnp.float32)
    return out.reshape(x.shape[0], 2, -1).sum(1)


def routed_feed_forward(u, lp, experts, j, cfg, live):
    """``moe(u)`` of the normed input ``u`` (.., units) and the layer's
    :data:`COUNTS` (uint32).  ``experts``: the run's :data:`EXPERT_LEAVES`,
    this layer the ``j``-th of them; ``live`` (..): the tokens that are
    somebody's (a padded token and an idle lane choose nothing)."""
    mm = _hybrid._mm
    shape, u = u.shape, u.reshape(-1, u.shape[-1])
    first, count = cfg.experts_held
    k = cfg.experts_per_token
    idx, w = route(u, lp, cfg)
    alive = jnp.broadcast_to(live.reshape(-1, 1), idx.shape)
    here = (idx >= first) & (idx < first + count) & alive
    # the pairs sorted by held expert, those of no held expert behind them
    group = jnp.where(here, idx - first, count).reshape(-1)
    order = jnp.argsort(group)
    sizes = jnp.zeros(count + 1, jnp.int32).at[group].add(1)[:count]
    layers = experts["we_in"].shape[0]
    # this layer's groups among the run's: the others have no rows
    all_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros(layers * count, jnp.int32), sizes, (j * count,))
    x = u[order // k]
    we_in = experts["we_in"].reshape((-1,) + experts["we_in"].shape[2:])
    we_out = experts["we_out"].reshape((-1,) + experts["we_out"].shape[2:])
    h = _grouped(x, we_in, all_sizes)
    F = cfg.expert_hidden
    y = _grouped(jax.nn.silu(h[:, :F]) * h[:, F:], we_out, all_sizes)
    # back in the tokens' order, each pair by its weight; a row behind the
    # groups is nobody's and whatever the product left there is dropped
    y = y[jnp.argsort(order)].reshape(idx.shape + (-1,))
    out = jnp.where(here[..., None], w[..., None] * y, 0.0).sum(1)
    shared = mm(jax.nn.silu(mm(u, lp["ws_gate"])) * mm(u, lp["ws_up"]),
                lp["ws_down"])
    pairs = sizes.sum()
    counts = jnp.stack([pairs, alive.sum() - pairs, (sizes > 0).sum(),
                        sizes.max(), 1]).astype(jnp.uint32)
    return (out + shared).reshape(shape), counts

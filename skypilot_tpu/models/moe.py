"""The dropless expert layer of the paged layer bodies.

``llama._moe_mlp`` (the training path: the 'ep' mesh and the pipeline
read it) dispatches through one-hot tensors ``[B, T * k, E, C]`` and
drops what overflows an expert's capacity, so a token's result depends
on which tokens share its chunk. Serving cannot have that
(``docs/sampling.md``: a row's output never depends on its batch), and
at 128 experts and 8 a token the one-hots alone outweigh the work.
This layer has neither:

- the router scores every token against ALL ``n_experts`` (float32;
  softmax over the experts, or a sigmoid each: ``config.moe_score``),
  takes the top ``moe_top_k`` (of score plus the leaf ``router_bias``
  with ``config.moe_select_bias``; the weights are the scores
  without it) and normalises their weights to sum 1 over all k, held
  here or not, times ``config.moe_routed_scale``;
- the (token, expert) pairs whose expert this chip HOLDS
  (``config.experts_held`` = (first, count); all experts without it)
  are sorted by expert and go through three grouped products (int8
  expert weights are taken as codes, their per-channel scales applied
  to each row by its expert) whose cost follows the pairs routed
  here, in a 512-token chunk as in a decode step. A pair whose expert
  lives on another chip adds nothing: the result is this share's part
  of the layer, and no code stands in for the absent chips or for
  their exchange;
- which product multiplies is ``_grouped``'s one question
  (``ops/grouped_matmul.tiles_engage``: platform, operand types,
  static widths). On a TPU, int8 codes (a decode step, a drafting
  round, every prefill chunk) go through
  ``pair_tiled_matmul``, a Pallas kernel whose row tile follows the
  pairs a group holds and which visits the layer's hit experts
  once; the CPU and a float expert stack keep
  ``jax.lax.ragged_dot``, whose row tile on the TPU follows the
  whole padded array of pairs (256 or 512 rows: 40 to 80 times the
  work for a group of 6). Either way the product's operand is the
  WHOLE expert stack (``LayerOf``): a kernel call's operand is a
  buffer of its own, so a layer's slice would be a copy of it. The
  kernel indexes (layer, group) itself; ``ragged_dot`` walks all
  L x G groups with every group outside the layer empty;
- the shared experts (``n_shared_experts`` gated MLPs side by side in
  the ``ws_*`` leaves) are one dense gated product whose mean is added.

A token's result is a fixed-order sum over its own k slots, so it is
the same to the bit however the prompt was chunked and whoever shares
the batch. Scopes: ``moe_router``, ``moe_experts``, ``moe_shared``.
"""
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama
from skypilot_tpu.models.quant import matmul as _mm
from skypilot_tpu.ops import grouped_matmul as gm

Params = Dict[str, Any]
EXPERT_LEAVES = ('w_gate', 'w_up', 'w_down')


class LayerOf(NamedTuple):
    """One layer's expert weights as the WHOLE stack ``[L, G, in,
    out]`` (plain or ``{'q', 's'}``) and the layer's index. A grouped
    product is a kernel call, and a kernel's operand is a buffer of
    its own: a layer's slice taken out of the stack first is a copy
    of it (268 MB of codes a product at 16 experts of 4,096 x 4,096,
    in every layer of every step, where the dense products read
    their slice in place). ``ragged_dot`` is therefore over all
    L x G groups, with every group outside the layer empty; the
    pair-tiled kernel takes the stack and the index as they are."""
    stack: Any
    layer: jax.Array


def stacked_experts(layers: Params):
    """(``layers`` without the expert leaves, the expert leaves), for
    a layer scan that hands the experts on whole (``LayerOf``)."""
    rest = {k: v for k, v in layers.items() if k not in EXPERT_LEAVES}
    return rest, {k: layers[k] for k in EXPERT_LEAVES}


def route(config: llama.LlamaConfig, x: jax.Array,
          router: jax.Array, bias: Optional[jax.Array] = None
          ) -> Tuple[jax.Array, jax.Array]:
    """x [N, D] -> (weights [N, k] float32 summing to
    ``config.moe_routed_scale`` (1 unless set) a token, experts
    [N, k] int32 among all ``n_experts``). Float32 throughout: a
    near-tie flips on bf16 logits. With ``bias`` [n_experts]
    (``config.moe_select_bias``) the experts are the top k of score
    + bias, and their weights the scores without it."""
    # A float32 ``x`` (a caller that kept the router's input wide)
    # is multiplied at full precision: the default rounds both
    # operands to bf16 on the TPU, which is what the caller avoided.
    logits = jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision='highest' if x.dtype == jnp.float32 else None)
    scores = (jax.nn.sigmoid(logits) if config.moe_score == 'sigmoid'
              else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        weights, experts = jax.lax.top_k(scores, config.moe_top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                   config.moe_top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / jnp.maximum(
        weights.sum(-1, keepdims=True), 1e-20)
    if config.moe_routed_scale != 1.0:
        weights = weights * config.moe_routed_scale
    return weights, experts.astype(jnp.int32)


def _grouped(xs: jax.Array, w, sizes: jax.Array,
             group: jax.Array) -> jax.Array:
    """Rows ``xs`` [M, in], sorted by expert into groups of
    ``sizes`` [G], times each group's own matrix of ``w`` [G, in,
    out] -> [M, out] in xs's type. Quantised ``w`` is read as int8
    codes inside the product; ``group`` [M] (each row's expert) picks
    the row's per-channel scales afterwards, which is exact for
    per-output-channel scaling. ``w`` may be a ``LayerOf``. Which
    product multiplies the codes is ``gm.tiles_engage``'s to say,
    from the platform, the operands' types and the static shapes."""
    if _tiles(w, xs.dtype):
        # The layer's own groups, from the stack where it lies.
        stack, layer = w if isinstance(w, LayerOf) else (
            jax.tree.map(lambda a: a[None], w), jnp.int32(0))
        out = gm.pair_tiled_matmul(xs, stack['q'], layer, sizes)
        scales = stack['s'].reshape(-1, *stack['s'].shape[2:])
        group = group + layer * sizes.shape[0]
        return (out * scales[:, 0].astype(jnp.float32)[group]
                ).astype(xs.dtype)
    if isinstance(w, LayerOf):
        # All layers' groups end to end, this layer's alone filled.
        per_layer = sizes.shape[0]
        w, at = jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), w.stack), \
            w.layer * per_layer
        n_groups = jax.tree.leaves(w)[0].shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_groups,), sizes.dtype), sizes, (at,))
        group = group + at
    if isinstance(w, dict) and 'q' in w:
        out = jax.lax.ragged_dot(xs, w['q'], sizes,
                                 preferred_element_type=jnp.float32)
        out = out * w['s'][:, 0].astype(jnp.float32)[group]
    else:
        out = jax.lax.ragged_dot(xs, w, sizes,
                                 preferred_element_type=jnp.float32)
    return out.astype(xs.dtype)


def _tiles(w, rows_dtype) -> bool:
    """``gm.tiles_engage`` for rows of ``rows_dtype`` on expert
    weights ``w`` [.., in, out]: plain, ``{'q', 's'}`` or a
    ``LayerOf`` either."""
    w = w.stack if isinstance(w, LayerOf) else w
    codes = isinstance(w, dict) and 'q' in w
    return gm.tiles_engage(*(w['q'] if codes else w).shape[-2:],
                           codes=codes, rows_dtype=rows_dtype)


def pairs_tiled(experts: Params, rows_dtype) -> bool:
    """Whether the three grouped products of an expert layer go
    through the pair-tiled kernel: the question ``_grouped`` asks, put
    to the stacked expert leaves ``experts[name]`` [L, G, in, out] for
    rows of ``rows_dtype``. One answer for every program of an
    engine."""
    return all(_tiles(experts[name], rows_dtype)
               for name in EXPERT_LEAVES)


def moe_layer(config: llama.LlamaConfig, h: jax.Array, lp: Params,
              route_on: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """h [B, T, D] (the normed stream) -> (this share's part of the
    expert layer's result [B, T, D], pairs routed to each held expert
    [count] int32). ``route_on`` [B, T, D]: what the router reads
    where that is not ``h`` itself (the same normed stream kept in
    float32, where ``h`` was rounded to the model's type for the
    products: a near-tie among the scores falls one way or the other
    on that rounding)."""
    b, t, d = h.shape
    n, k = b * t, config.moe_top_k
    first, count = (config.experts_held if config.experts_held
                    is not None else (0, config.n_experts))
    x = h.reshape(n, d)
    with jax.named_scope('moe_router'):
        # (The benchmark's tests wrap ``route`` by its three
        # arguments: the bias is handed over only where there is one.)
        bias = {'bias': lp['router_bias']} if 'router_bias' in lp \
            else {}
        weights, experts = route(
            config, x if route_on is None else route_on.reshape(n, d),
            lp['router'], **bias)
        # Pairs by the expert that serves them; an absent expert's
        # pairs sort behind every group and belong to none.
        local = experts - first
        here = (local >= 0) & (local < count)
        key = jnp.where(here, local, count).reshape(n * k)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(
            1)[:count]
        group = jnp.minimum(key[order], count - 1)
        # Where each (token, slot) pair's row went, to bring the
        # results back by a gather and not a scatter-add.
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
    with jax.named_scope('moe_experts'):
        xs = x[order // k]                                   # [M, D]
        gate = llama.mlp_act(config)(
            _grouped(xs, lp['w_gate'], sizes, group).astype(
                jnp.float32)).astype(xs.dtype)
        up = _grouped(xs, lp['w_up'], sizes, group)
        ys = _grouped(gate * up, lp['w_down'], sizes, group)
        # Rows past the groups hold whatever the product left there.
        held_rows = jnp.arange(n * k) < sizes.sum()
        ys = jnp.where(held_rows[:, None], ys.astype(jnp.float32), 0.0)
        pairs = ys[back].reshape(n, k, d) * weights[..., None]
        out = pairs.sum(axis=1)
    if config.n_shared_experts:
        with jax.named_scope('moe_shared'):
            sg = llama.mlp_act(config)(
                _mm(x, lp['ws_gate']).astype(jnp.float32)
            ).astype(x.dtype)
            shared = _mm(sg * _mm(x, lp['ws_up']), lp['ws_down'])
            out = out + shared.astype(jnp.float32) / \
                config.n_shared_experts
    return out.astype(h.dtype).reshape(b, t, d), sizes

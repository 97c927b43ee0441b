"""Step builders + sharding plumbing shared by train.py and dryrun.py.

``make_train_step`` builds a pjit-able function:

    (params, opt_state, batch) -> (params, opt_state, metrics)

with microbatched gradient accumulation (``lax.scan`` over microbatches —
one psum per accumulation window, the standard compute/comm-overlap layout),
global-norm clipping and AdamW.  For a model with experts, ``metrics``
carry the step's expert counters (``expert_counts``, ``held_share``;
:mod:`repro.models.moe`).  Sharding trees are produced from the model's
logical param axes via :mod:`repro.dist.sharding`.

Cross-pod gradient sync (``overlap_sync=``):

* ``None`` (default) — the SPMD partitioner folds the pod reduction into the
  backward pass (batch sharded over ``("pod", "data")``), no explicit sync.
* ``False`` — explicit *blocking* sync: one synchronous
  :func:`~repro.dist.collectives.make_pod_sync` all-reduce per leaf at step
  end, serializing the slowest link behind the backward pass (the baseline
  the paper's overlap principle argues against).
* ``True`` — explicit *overlapped* sync: gradients are bucketed by layer
  group and each bucket's pod sync is issued as soon as the previous
  bucket's wait retires (``psum_start``/``psum_wait`` pipeline, 1F1B-style
  double buffering).  While bucket *g* is in flight, bucket *g−1*'s
  gradient-norm contribution is computed, so the only fully exposed
  transfer is the last bucket's and the optimizer boundary reuses the
  accumulated norm.

With an explicit sync the batch is *replicated* across pods
(``include_pod=False`` batch shardings): each pod computes full-batch
gradients and the explicit pod-mean is numerically the identity, so tier-1
numerics match the single-pod step exactly (modulo int8 quantization when
``sync_compressed=True``) while the HLO carries the full production
cross-pod collective structure — which is precisely what the PASTA walker
measures.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental.shard_map import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.collectives import make_pod_sync, psum_start, psum_wait
from repro.dist.sharding import logical, set_mesh
from repro.models import (forward, forward_with_aux, cross_entropy,
                          init_params, param_axes, init_cache, cache_axes)
from repro.models.config import ModelConfig
from .optimizer import OptConfig, init_opt_state, opt_state_axes, adamw_update

BATCH_AXES = ("pod", "data")


# ---------------------------------------------------------------- shardings
def tree_shardings(mesh, axes_tree, shapes_tree):
    """NamedSharding tree from logical-axes tree + abstract shapes tree."""
    from repro.dist.sharding import get_rules
    set_mesh(mesh, get_rules())          # keep any custom rules in force

    def one(ax, shape_leaf):
        return NamedSharding(mesh, logical(*ax, dims=shape_leaf.shape))

    is_ax = lambda x: isinstance(x, tuple)          # noqa: E731
    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_ax)


def _dp_axes(mesh, batch_size: int | None = None, include_pod: bool = True):
    names = BATCH_AXES if include_pod else BATCH_AXES[1:]
    axes = tuple(a for a in names if mesh.shape.get(a, 1) > 1)
    if batch_size is not None:
        while axes:
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if batch_size % n == 0:
                break
            axes = axes[:-1]          # drop innermost dp axis until it fits
    return axes


def batch_spec(mesh, batch_size: int | None = None,
               include_pod: bool = True):
    return NamedSharding(mesh, P(_dp_axes(mesh, batch_size, include_pod)))


def batch_shardings(mesh, batch_tree, include_pod: bool = True):
    """``include_pod=False`` replicates the batch across pods — required by
    the explicit ``overlap_sync`` paths, whose pod-mean sync supplies the
    cross-pod reduction instead of the partitioner."""
    def one(leaf):
        return NamedSharding(mesh, P(_dp_axes(mesh, leaf.shape[0],
                                              include_pod),
                                     *([None] * (leaf.ndim - 1))))
    return jax.tree.map(one, batch_tree)


def abstract_state(cfg: ModelConfig, opt_cfg: OptConfig | None = None):
    """eval_shape of params (and optimizer state) — no allocation, works for
    the 1T-param config."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    if opt_cfg is None:
        return params, None
    opt = jax.eval_shape(lambda: init_opt_state(params, opt_cfg))
    return params, opt


# --------------------------------------------------------------- train step
def _gather_once(params, cfg: ModelConfig):
    """ZeRO-2-style hoist: re-constrain params with the FSDP ('data') axis
    dropped so the all-gather happens once per step, before the microbatch
    loop — its transpose (one reduce-scatter of the summed grads) lands
    after the loop.  Trades (params/model-shard) bytes of HBM for
    (microbatches-1)/microbatches of the FSDP collective traffic."""
    from repro.dist.sharding import get_mesh, logical
    mesh = get_mesh()
    if mesh is None:
        return params
    axes = param_axes(cfg)
    is_ax = lambda x: isinstance(x, tuple)          # noqa: E731

    def regather(ax, p):
        ax2 = tuple(None if a == "p_embed" else a for a in ax)
        sh = NamedSharding(mesh, logical(*ax2, dims=p.shape))
        return jax.lax.with_sharding_constraint(p, sh)

    return jax.tree.map(regather, axes, params, is_leaf=is_ax)


# ----------------------------------------------------- overlapped pod sync
def _bucket_pieces(leaves, n_buckets: int, layer_dim: int | None):
    """Partition gradient leaves into ``n_buckets`` layer-group buckets.

    Scan-stacked leaves (leading dim == ``layer_dim``) are sliced along the
    layer axis so bucket *g* carries layer group *g* of every stacked leaf —
    the sync for a layer group covers exactly that group's parameters.
    Unstacked leaves (embeddings, final norm, ...) go whole to the currently
    lightest bucket.  Returns a list over buckets of ``(leaf_idx, lo, hi)``
    pieces (``lo is None`` ⇒ the whole leaf).
    """
    buckets: list = [[] for _ in range(n_buckets)]
    weight = [0] * n_buckets
    for i, leaf in enumerate(leaves):
        if (layer_dim is not None and leaf.ndim >= 1
                and leaf.shape[0] == layer_dim and layer_dim >= n_buckets):
            per = leaf.size // max(leaf.shape[0], 1) * leaf.dtype.itemsize
            for g in range(n_buckets):
                lo = g * layer_dim // n_buckets
                hi = (g + 1) * layer_dim // n_buckets
                buckets[g].append((i, lo, hi))
                weight[g] += per * (hi - lo)
        else:
            g = min(range(n_buckets), key=weight.__getitem__)
            buckets[g].append((i, None, None))
            weight[g] += leaf.size * leaf.dtype.itemsize
    return [b for b in buckets if b]


def make_overlapped_pod_sync(mesh, *, axis: str = "pod",
                             compressed: bool = False, n_buckets: int = 4,
                             layer_dim: int | None = None, specs=None):
    """Bucketed, software-pipelined cross-pod gradient sync.

    Returns ``sync(grads) -> (synced_grads, grad_sqnorm)`` (or ``None`` when
    the mesh has no pod axis).  Float leaves are bucketed by layer group
    (:func:`_bucket_pieces`); inside one ``shard_map`` over the mesh the
    buckets run through a ``psum_start``/``psum_wait`` double-buffered
    pipeline: bucket *g*'s reduce half is issued, THEN bucket *g−1*'s wait
    retires and its squared-norm contribution is computed — compute that
    overlaps the in-flight collective.  Only the last bucket's wait is fully
    exposed, and the accumulated ``grad_sqnorm`` lets the optimizer skip its
    own full-tree norm reduction (``adamw_update(grad_sqnorm=...)``).

    The sync is a pod *mean* (cross-pod data parallelism averages); see the
    module docstring for why that makes the step numerically identical to
    the single-pod step when the batch is pod-replicated.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return None
    inv_n = 1.0 / mesh.shape[axis]

    def sync(grads):
        leaves, treedef = jax.tree.flatten(grads)
        fidx = [i for i, l in enumerate(leaves)
                if jnp.issubdtype(l.dtype, jnp.floating)]
        buckets = _bucket_pieces([leaves[i] for i in fidx],
                                 n_buckets, layer_dim)

        def inner(flt):
            # flt: tuple of float leaves (replicated local views).  One flat
            # payload per bucket -> pipelined start/wait over the pod axis.
            def flat_of(bucket):
                return jnp.concatenate(
                    [(flt[j] if lo is None else flt[j][lo:hi])
                     .astype(jnp.float32).reshape(-1)
                     for j, lo, hi in bucket])

            outs: list = [None] * len(buckets)
            sq = jnp.zeros((), jnp.float32)

            def retire(g, handle):
                done = psum_wait(handle, axis) * inv_n
                outs[g] = done
                return sq + jnp.sum(done * done)

            def pin(wait_h, start_h, sq):
                # Pin the pipeline into the dataflow: bucket g-1's wait
                # (all-gather) may not retire before bucket g's start
                # (reduce-scatter) has issued and the previous bucket's
                # norm compute has run.  XLA's latency-hiding scheduler
                # does this implicitly on TPU; the optimization_barrier
                # makes the 1F1B schedule explicit in the HLO, which is
                # also what the PASTA walker's overlap windows measure.
                tied = jax.lax.optimization_barrier(
                    (wait_h.payload, start_h.payload, sq))
                return (dataclasses.replace(wait_h, payload=tied[0]),
                        dataclasses.replace(start_h, payload=tied[1]))

            prev = None
            for g, bucket in enumerate(buckets):
                handle = psum_start(flat_of(bucket), axis,
                                    compressed=compressed)
                if prev is not None:
                    prev, handle = pin(prev, handle, sq)
                    sq = retire(g - 1, prev)     # overlaps bucket g's wire
                prev = handle
            sq = retire(len(buckets) - 1, prev)  # the only exposed wait
            return tuple(outs), sq

        n_f = len(fidx)
        flat_specs = (tuple([P()] * n_f),)
        out_specs = (tuple([P()] * len(buckets)), P())
        f = shard_map(inner, mesh=mesh, in_specs=flat_specs,
                      out_specs=out_specs, check_rep=False)
        flats, sqnorm = f(tuple(leaves[i] for i in fidx))

        # unflatten: split each bucket payload back into its pieces
        new_leaves = list(leaves)
        parts: dict = {}
        for bucket, flat in zip(buckets, flats):
            off = 0
            for j, lo, hi in bucket:
                leaf = leaves[fidx[j]]
                shape = (leaf.shape if lo is None
                         else (hi - lo,) + tuple(leaf.shape[1:]))
                n = 1
                for d in shape:
                    n *= d
                piece = flat[off:off + n].reshape(shape).astype(leaf.dtype)
                off += n
                parts.setdefault(j, []).append((lo, piece))
        for j, pieces in parts.items():
            if len(pieces) == 1 and pieces[0][0] is None:
                new_leaves[fidx[j]] = pieces[0][1]
            else:
                pieces.sort(key=lambda t: t[0])
                new_leaves[fidx[j]] = jnp.concatenate(
                    [p for _lo, p in pieces], axis=0)
        return treedef.unflatten(new_leaves), sqnorm

    return sync


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig,
                    microbatches: int = 1, overlap_sync: bool | None = None,
                    sync_compressed: bool = False, sync_buckets: int = 4):
    def loss_fn(params, inputs, labels):
        logits, aux = forward_with_aux(params, inputs, cfg)
        loss, _parts = cross_entropy(logits, labels)
        return loss, aux

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pod_sync(grads):
        """(synced grads, optional precomputed sqnorm) per overlap_sync."""
        from repro.dist.sharding import get_mesh
        mesh = get_mesh()
        if overlap_sync is None or mesh is None:
            return grads, None
        if overlap_sync:
            sync = make_overlapped_pod_sync(
                mesh, compressed=sync_compressed, n_buckets=sync_buckets,
                layer_dim=cfg.n_layers)
            return (grads, None) if sync is None else sync(grads)
        return make_pod_sync(mesh, compressed=sync_compressed,
                             mean=True)(grads), None

    def train_step(params, opt_state, batch):
        inputs, labels = batch["inputs"], batch["labels"]
        if cfg.gather_params_once and microbatches > 1:
            params = _gather_once(params, cfg)
        if microbatches == 1:
            (loss, aux), grads = grad_fn(params, inputs, labels)
        else:
            m = microbatches
            b = inputs.shape[0]
            assert b % m == 0, (b, m)
            mb_in = inputs.reshape(m, b // m, *inputs.shape[1:])
            mb_lb = labels.reshape(m, b // m, *labels.shape[1:])

            def micro(carry, mb):
                acc, lsum = carry
                (l, a), g = grad_fn(params, mb["i"], mb["l"])
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, lsum + l), a

            zeros = jax.tree.map(jnp.zeros_like, params)
            (grads, lsum), auxes = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)),
                {"i": mb_in, "l": mb_lb})
            grads = jax.tree.map(lambda g: g / m, grads)
            loss = lsum / m
            aux = {k: v.sum(0) if k == "expert_counts" else v.mean(0)
                   for k, v in auxes.items()}
        grads, grad_sqnorm = pod_sync(grads)
        new_params, new_opt, om = adamw_update(params, grads, opt_state,
                                               opt_cfg,
                                               grad_sqnorm=grad_sqnorm)
        metrics = {"loss": loss, **om, **aux,
                   "tokens": jnp.asarray(inputs.shape[0] * inputs.shape[1],
                                         jnp.float32)}
        return new_params, new_opt, metrics

    return train_step


def train_shardings(mesh, cfg: ModelConfig, opt_cfg: OptConfig):
    """(param_shardings, opt_shardings) matching abstract_state."""
    p_shapes, o_shapes = abstract_state(cfg, opt_cfg)
    p_ax = param_axes(cfg)
    p_sh = tree_shardings(mesh, p_ax, p_shapes)
    o_ax = opt_state_axes(p_ax, opt_cfg)
    o_sh = tree_shardings(mesh, o_ax, o_shapes)
    return p_sh, o_sh, p_shapes, o_shapes


# --------------------------------------------------------------- serve step
def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, inputs):
        logits, cache = forward(params, inputs, cfg, return_cache=True,
                                logits_mode="last")
        return logits, cache
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens):
        logits, new_cache = forward(params, tokens, cfg, cache=cache,
                                    logits_mode="last")
        return logits, new_cache
    return decode_step


def serve_shardings(mesh, cfg: ModelConfig, batch: int, max_seq: int):
    params_shapes, _ = abstract_state(cfg)
    p_sh = tree_shardings(mesh, param_axes(cfg), params_shapes)
    cache_shapes = jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_seq))
    c_sh = tree_shardings(mesh, cache_axes(cfg), cache_shapes)
    return p_sh, c_sh, params_shapes, cache_shapes

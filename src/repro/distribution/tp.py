"""Tensor-parallel serving: head-sharded paged HDP attention.

HDP prunes per head — the scout's block keep mask and the early head
gate (`theta_head > tau_h`, an absolute threshold with no cross-head
reduction, see ``core.hdp.decode_scout``) are computed independently
per KV head. That makes the head axis the natural shard dimension for
serving: under a ``(data, model)`` mesh each "model" shard holds 1/TP
of the paged pool (int8 codes + scales + scout views) and runs the
scout, the keep mask, and stage 3 purely on its local heads. The
pruned-pages-never-DMA contract holds per shard: a shard's fetched set
is the OR of *its* heads' keep masks, a subset of the global fetched
set, and masked softmax zeroes non-kept pages exactly — so per-head
outputs are bitwise identical at any TP degree.

The only cross-shard traffic is one all-gather of the per-head
attention output before the output projection (an exact concatenation,
no float reduction — byte identity is preserved; the ISSUE's
psum-the-projection variant would introduce a TP-dependent summation
order). Sparsity stats are shard-local DMA accounting and are pmean'd
over the model axis; ``theta_head`` is all-gathered back to full width.

The mesh is threaded as ambient context (thread-local, like
``distribution.sharding``): the engine wraps its jit'd steps in
:func:`serving_mesh`, and the model layer consults
:func:`active_serving_mesh` at trace time to route paged-decode calls
through :func:`tp_paged_attention`.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from functools import partial
from typing import Optional

import jax

_ctx = threading.local()

#: head (sharded) axis index of each pool leaf in the FULL pool
#: [L, P, N, ps, hd] / scales [L, P, N]; per-layer views drop the
#: leading L. Scout views mirror the page layout.
POOL_HEAD_AXIS = {
    "k_pages": 2, "v_pages": 2, "k_scout": 2, "f_scout": 2,
    "k_scale": 2, "v_scale": 2,
}


@contextmanager
def serving_mesh(mesh: Optional[jax.sharding.Mesh]):
    """Make ``mesh`` the ambient serving mesh for the calling thread."""
    prev = getattr(_ctx, "mesh", None)
    _ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _ctx.mesh = prev


def active_serving_mesh() -> Optional[jax.sharding.Mesh]:
    return getattr(_ctx, "mesh", None)


def active_tp() -> int:
    """TP degree of the ambient serving mesh (1 when unsharded)."""
    mesh = active_serving_mesh()
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get("model", 1))


def _pspec(*axes):
    return jax.sharding.PartitionSpec(*axes)


def pool_pspec(name: str, *, per_layer: bool = False):
    """PartitionSpec sharding pool leaf ``name`` on the model axis."""
    ax = POOL_HEAD_AXIS.get(name)
    if ax is None:
        return _pspec()
    if per_layer:
        ax -= 1
    return _pspec(*([None] * ax + ["model"]))


def pool_shardings(mesh: jax.sharding.Mesh, pool: dict, *,
                   per_layer: bool = False) -> dict:
    """NamedSharding per pool leaf: heads on "model", rest replicated."""
    return {name: jax.sharding.NamedSharding(
        mesh, pool_pspec(name, per_layer=per_layer)) for name in pool}


def constrain_pool(pool: dict, mesh: Optional[jax.sharding.Mesh], *,
                   per_layer: bool = False) -> dict:
    """Re-assert pool shardings inside a jit body (no-op without mesh)."""
    if mesh is None:
        return pool
    sh = pool_shardings(mesh, pool, per_layer=per_layer)
    return {name: jax.lax.with_sharding_constraint(leaf, sh[name])
            for name, leaf in pool.items()}


def replicated(x, mesh: Optional[jax.sharding.Mesh]):
    """Constrain ``x`` (pytree) to fully-replicated on ``mesh``."""
    if mesh is None:
        return x
    sh = jax.sharding.NamedSharding(mesh, _pspec())
    return jax.tree.map(
        lambda leaf: jax.lax.with_sharding_constraint(leaf, sh), x)


def tp_paged_attention(q, call, spec, *, q_pos, k_pos, cache, page_table,
                       mesh: jax.sharding.Mesh):
    """Head-sharded paged-decode attention under ``mesh``.

    ``q`` [B,N,G,Sq,hd] with N the KV-head axis; ``cache`` is the
    per-layer pool view (pages [P,N,ps,hd], scales [P,N]) or the
    layer-stacked pool with its ``"layer"`` index (replicated). Each model
    shard runs the registry dispatch on its local head slice — the
    scout, keep mask, page gather, and stage-3 kernel all see
    N/tp heads and a per-shard fetched set. Returns the full-width
    ``(out, stats)`` with ``out`` constrained replicated (exact
    all-gather concat over heads, no float reduction).
    """
    from repro.attention.registry import attention
    from repro.attention.stats import AttnStats

    tp = int(dict(mesh.shape).get("model", 1))
    n_kv = q.shape[1]
    if tp == 1 or n_kv % tp != 0:
        return attention(q, None, None, call, spec=spec, q_pos=q_pos,
                         k_pos=k_pos, cache=cache, page_table=page_table)

    q_spec = _pspec(None, "model")
    stacked = "layer" in cache    # layer-stacked pool + its layer index
    cache_specs = {name: pool_pspec(name, per_layer=not stacked)
                   for name in cache}

    def body(q_l, cache_l, table, qp, kp):
        out, stats = attention(q_l, None, None, call, spec=spec, q_pos=qp,
                               k_pos=kp, cache=cache_l, page_table=table)
        if stats is not None:
            gather = jax.lax.all_gather
            stats = AttnStats(
                block_sparsity=jax.lax.pmean(stats.block_sparsity, "model"),
                head_sparsity=jax.lax.pmean(stats.head_sparsity, "model"),
                theta_head=(None if stats.theta_head is None else
                            gather(stats.theta_head, "model", axis=1,
                                   tiled=True)),
                page_sparsity=(None if stats.page_sparsity is None else
                               jax.lax.pmean(stats.page_sparsity, "model")),
                # each shard's kernel walks its own kept pages
                kernel_pages=(None if stats.kernel_pages is None else
                              jax.lax.psum(stats.kernel_pages, "model")),
                kernel_block_pages=(
                    None if stats.kernel_block_pages is None else
                    jax.lax.psum(stats.kernel_block_pages, "model")),
                # every shard's scout walks the same columns, each for
                # its own kv heads
                scout_pages=(None if stats.scout_pages is None else
                             jax.lax.pmean(stats.scout_pages, "model")))
        return out, stats

    # stats presence/fields are call-static — derive the output pytree
    # structure from an unsharded abstract trace (the body itself uses
    # collectives, which only trace inside shard_map) so out_specs
    # matches exactly (None fields stay None)
    out_shape = jax.eval_shape(
        lambda q_, c_, t_, qp_, kp_: attention(
            q_, None, None, call, spec=spec, q_pos=qp_, k_pos=kp_,
            cache=c_, page_table=t_),
        q, cache, page_table, q_pos, k_pos)
    out_specs = (q_spec, jax.tree.map(lambda _: _pspec(), out_shape[1]))

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_specs, _pspec(), _pspec(), _pspec()),
        out_specs=out_specs, check_vma=False)
    out, stats = sharded(q, cache, page_table, q_pos, k_pos)
    # exact all-gather of the head-sharded output before the o-projection:
    # every shard then computes the (replicated) wo einsum on full width
    out = replicated(out, mesh)
    return out, stats

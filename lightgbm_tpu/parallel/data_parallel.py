"""Data-parallel tree growing over a device mesh.

TPU-native re-design of the reference's DataParallelTreeLearner
(src/treelearner/data_parallel_tree_learner.cpp): rows are sharded over the mesh's
``data`` axis; per-leaf histograms are reduced with ``psum`` inside ``shard_map``
(replacing the reference's ReduceScatter of serialized histogram buffers,
data_parallel_tree_learner.cpp:149-164 + network.cpp:232); best-split selection runs
replicated on every shard, which also replaces the reference's
``SyncUpGlobalBestSplit`` argmax-allreduce (parallel_tree_learner.h:190-213) — every
shard sees identical reduced histograms so no second collective is needed.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.grow import GrowParams, TreeArrays, grow_tree
from .mesh import DATA_AXIS


def grow_tree_dp(bins, g, h, c, num_bins, na_bin, feature_mask,
                 gp: GrowParams, mesh: Mesh,
                 grow_fn=grow_tree, bundle=None, qseed=None
                 ) -> Tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree with rows sharded over ``mesh``'s data axis.

    ``grow_fn`` is either ops.grow.grow_tree (leaf-wise) or
    ops.grow_depthwise.grow_tree_depthwise (level-wise) — both psum their
    histograms when gp.axis_name is set. bins and the g/h/c channel arrays must
    already be sharded along rows; the returned TreeArrays are replicated,
    leaf_id stays row-sharded.
    """
    import dataclasses
    axis = mesh.axis_names[0]
    gp_dp = gp if gp.axis_name == axis else \
        dataclasses.replace(gp, axis_name=axis)

    if gp_dp.quant or gp_dp.ff_bynode < 1.0 or gp_dp.split.extra_trees:
        # thread the stochastic-rounding / per-node-sampling seed as an
        # explicit replicated operand (a closed-over tracer is illegal under
        # shard_map) so the dither and feature subsets vary per tree on the
        # dp path too
        def _fn(b_, g_, h_, c_, nb_, na_, fm_, qs_):
            return grow_fn(b_, g_, h_, c_, nb_, na_, fm_, gp=gp_dp,
                           bundle=bundle, qseed=qs_)
        fn = jax.shard_map(
            _fn, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(), P(),
                      P(), P()),
            out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))),
                       P(axis)),
            check_vma=False,
        )
        seed = jnp.int32(0) if qseed is None else qseed
        return fn(bins, g, h, c, num_bins, na_bin, feature_mask, seed)
    fn = jax.shard_map(
        partial(grow_fn, gp=gp_dp, bundle=bundle),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))), P(axis)),
        check_vma=False,
    )
    return fn(bins, g, h, c, num_bins, na_bin, feature_mask)

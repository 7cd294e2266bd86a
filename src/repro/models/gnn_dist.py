"""Distributed GNN training step (§Perf hillclimb for the GNN family).

Hypothesis H3: the baseline pjit auto-sharding of edge-index message
passing scatters across *data* shards every layer (all-to-all-heavy: the
compiler reshuffles (E, d) message tensors), and leaves the ``model`` axis
idle.  Restructure with an explicit shard_map over ALL mesh axes:

* nodes row-partitioned over (pod, data, model) — N/512 rows per device;
* edges arrive **partitioned by destination shard** (loader contract: the
  sampler already emits dst-sorted edges), with dst indices local and src
  indices global;
* per layer: one tiled ``all_gather`` of the (N, d) feature matrix →
  local gather + local segment_sum → local MLP;
* gradients ``psum`` once per step.

Collective volume per layer = the feature matrix (N·d·4 B), independent of
E — vs the baseline's per-edge traffic (E ≫ N for products: 61.8M edges vs
2.4M nodes).  Graph-partition locality (METIS-style halo exchange instead
of full gather) is the next rung and is noted in EXPERIMENTS.md.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..train import optimizer as opt_mod
from . import gnn as gnn_mod


def _all_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)


def sharded_train_step(cfg: gnn_mod.GNNConfig, mesh: Mesh,
                       ocfg: opt_mod.AdamWConfig):
    """Returns (step_fn, batch_specs) for gin/sage; batch leaves carry
    *local-shape* semantics inside shard_map (global = local × n_shards)."""
    axes = _all_axes(mesh)

    def local_forward(params, batch, n_total):
        h = gnn_mod.mlp_apply(params["proj"],
                              batch["node_feat"].astype(cfg.dtype))
        for i in range(cfg.n_layers):
            h_all = jax.lax.all_gather(h, axes, axis=0, tiled=True)  # (N, d)
            msg = jnp.take(h_all, batch["edge_src"], axis=0)
            agg = jax.ops.segment_sum(msg, batch["edge_dst"],
                                      num_segments=h.shape[0])
            if cfg.arch == "gin":
                eps = params[f"eps{i}"][0]
                h = gnn_mod.mlp_apply(params[f"mlp{i}"],
                                      (1.0 + eps) * h + agg, final_act=True)
            else:
                if cfg.aggregator == "mean":
                    deg = jax.ops.segment_sum(
                        jnp.ones_like(batch["edge_dst"], h.dtype),
                        batch["edge_dst"], num_segments=h.shape[0])
                    agg = agg / jnp.maximum(deg, 1.0)[:, None]
                h = jax.nn.relu(gnn_mod.mlp_apply(params[f"self{i}"], h)
                                + gnn_mod.mlp_apply(params[f"neigh{i}"], agg))
                h = h / jnp.maximum(
                    jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
        return gnn_mod.mlp_apply(params["head"], h)

    def local_loss(params, batch):
        out = local_forward(params, batch, None)
        logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, batch["labels"][:, None], -1)[:, 0]
        mask = batch["node_mask"].astype(jnp.float32)
        if "train_mask" in batch:
            mask = mask * batch["train_mask"].astype(jnp.float32)
        num = jax.lax.psum((nll * mask).sum(), axes)
        den = jax.lax.psum(mask.sum(), axes)
        return num / jnp.maximum(den, 1.0)

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(local_loss)(params, batch)
        grads = jax.lax.pmean(grads, axes)      # data-parallel reduce
        params, opt_state, _ = opt_mod.apply_updates(params, grads,
                                                     opt_state, ocfg)
        return params, opt_state, loss

    batch_spec = {
        "node_feat": P(axes, None), "edge_src": P(axes),
        "edge_dst": P(axes), "labels": P(axes),
        "node_mask": P(axes), "train_mask": P(axes),
    }
    pspec = jax.tree.map(lambda _: P(), gnn_mod.param_shapes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    opt_spec = {"step": P(), "m": pspec, "v": pspec}

    step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(pspec, opt_spec, batch_spec),
        out_specs=(pspec, opt_spec, P()),
        check_vma=False)
    return step, batch_spec

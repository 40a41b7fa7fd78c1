"""Train-step builders: plain pjit step and the wavelet-synced multi-pod step.

Both variants support microbatch gradient accumulation (scan over
microbatches, single deferred reduction) and return (params, opt_state,
metrics).  The wavelet variant wraps the step in ``jax.shard_map`` manual
over the ``pod`` axis only (data/model stay auto-sharded), so the
inter-pod gradient all-reduce goes through the integer-DWT low band
channel of ``grad_compress.py`` instead of a full-size psum.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.train import optim
from repro.train.grad_compress import WaveletSyncConfig, pod_sync_tree

PyTree = Any


def _split_microbatches(batch: PyTree, n_micro: int) -> PyTree:
    """(B, ...) -> (n_micro, B/n_micro, ...) for scan."""

    def split(x):
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        return x.reshape((n_micro, b // n_micro) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def _grads_of(cfg: ArchConfig, ce_chunk: int):
    def compute(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch, ce_chunk=ce_chunk), has_aux=True
        )(params)
        return loss, metrics, grads

    return compute


def make_train_step(
    cfg: ArchConfig,
    opt_cfg: optim.AdamWConfig = optim.AdamWConfig(),
    *,
    n_microbatches: int = 1,
    ce_chunk: int = 0,
) -> Callable:
    """Plain (paper-faithful baseline) train step: full-fidelity psum."""
    compute = _grads_of(cfg, ce_chunk)

    def train_step(params, opt_state, batch):
        if n_microbatches > 1:
            micro = _split_microbatches(batch, n_microbatches)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

            def body(acc, mb):
                loss_a, g_acc = acc
                loss, metrics, grads = compute(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                )
                return (loss_a + loss, g_acc), None

            (loss_sum, g_sum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro
            )
            loss = loss_sum / n_microbatches
            grads = jax.tree_util.tree_map(lambda g: g / n_microbatches, g_sum)
            metrics = {}
        else:
            loss, metrics, grads = compute(params, batch)
        new_params, new_opt, opt_metrics = optim.adamw_update(
            grads, opt_state, params, opt_cfg
        )
        out_metrics = {"loss": loss, **metrics, **opt_metrics}
        return new_params, new_opt, out_metrics

    return train_step


def make_wavelet_train_step(
    cfg: ArchConfig,
    mesh,
    opt_cfg: optim.AdamWConfig = optim.AdamWConfig(),
    sync_cfg: WaveletSyncConfig = WaveletSyncConfig(),
    *,
    ce_chunk: int = 0,
) -> Callable:
    """Multi-pod step with integer-DWT-codec gradient sync over 'pod'.

    Signature: (params, opt_state, err_fb, batch) -> (params, opt, err, metrics).

    State representation: each pod *owns a replica* — params, optimizer
    moments and the pod-local error-feedback tree all carry an explicit
    leading pod axis sharded P("pod") (physically the same bytes/device as
    replication; replicas stay numerically identical because the synced
    gradients are identical by construction).  This matches what multi-pod
    data parallelism physically does and lets the inter-pod exchange be an
    explicit quantized ring instead of a full-width psum.  Scalar metrics
    are pmean'd (pod-invariant) for logging.
    """
    from jax.sharding import PartitionSpec as P

    compute = _grads_of(cfg, ce_chunk)

    def pod_local_step(params_p, opt_p, err_p, batch):
        # strip the leading pod-replica axis
        unpod = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
        repod = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)  # noqa: E731
        params = unpod(params_p)
        err_fb = unpod(err_p)
        opt_state = optim.AdamWState(
            step=opt_p.step, m=unpod(opt_p.m), v=unpod(opt_p.v)
        )
        loss, metrics, grads = compute(params, batch)
        loss = jax.lax.pmean(loss, "pod")
        grads, err_fb = pod_sync_tree(grads, err_fb, sync_cfg, "pod")
        new_params, new_opt, opt_metrics = optim.adamw_update(
            grads, opt_state, params, opt_cfg
        )
        out_metrics = {
            k: jax.lax.pmean(v, "pod") for k, v in {**metrics, **opt_metrics}.items()
        }
        out_metrics["loss"] = loss
        new_opt_p = optim.AdamWState(
            step=new_opt.step, m=repod(new_opt.m), v=repod(new_opt.v)
        )
        return repod(new_params), new_opt_p, repod(err_fb), out_metrics

    opt_spec = optim.AdamWState(step=P(), m=P("pod"), v=P("pod"))
    step = jax.shard_map(
        pod_local_step,
        mesh=mesh,
        in_specs=(P("pod"), opt_spec, P("pod"), P("pod")),
        out_specs=(P("pod"), opt_spec, P("pod"), P()),
        axis_names={"pod"},
    )
    return jax.jit(step)  # shard_map requires jit (no eager closed_call)


def podded(tree: PyTree, n_pods: int) -> PyTree:
    """Add a leading pod-replica axis (see make_wavelet_train_step)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n_pods,) + p.shape), tree
    )


def podded_opt(opt: optim.AdamWState, n_pods: int) -> optim.AdamWState:
    return optim.AdamWState(
        step=opt.step, m=podded(opt.m, n_pods), v=podded(opt.v, n_pods)
    )


def unpodded(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda p: p[0], tree)


def init_podded_error_feedback(params: PyTree, n_pods: int) -> PyTree:
    """Pod-local error-feedback state with explicit leading pod axis."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params
    )

"""SPMD (mesh-level) realization of ShadowSync for the LLM-scale architectures.

ShadowSync mode ("shadow"): dense params carry a leading replica dim R sharded
over the replica axis (``pod``). Each replica group trains independently —
``train_step``'s lowered HLO contains NO collective over the replica axis (a
property tests assert). ``sync_step`` is a SEPARATE compiled program owning all
cross-replica traffic, dispatched by the host shadow thread at its own cadence.

Baseline mode ("syncdp"): classic fully-synchronous data parallelism — gradients
all-reduce over (pod, data) inside every step. This is the foreground strategy
the paper compares against (its cost shows up as per-step collective bytes in the
roofline; cf. FR-EASGD's saturation in Fig 5).
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import sync as S
from repro.models import transformer, whisper
from repro.optim import Optimizer

Pytree = Any


def _loss_fn(cfg: ArchConfig, remat_policy: str = "full") -> Callable:
    if cfg.family == "audio":
        return lambda p, b: whisper.loss_fn(p, cfg, b)
    return lambda p, b: transformer.loss_fn(p, cfg, b, remat=True, remat_policy=remat_policy)


def init_params(cfg: ArchConfig, key) -> Pytree:
    if cfg.family == "audio":
        return whisper.init_params(cfg, key)
    return transformer.init_params(cfg, key)


def stack_replicas(params: Pytree, n: int) -> Pytree:
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)


def _accum_grads(
    loss_fn: Callable, params: Pytree, batch: Pytree, n_microbatches: int, grad_dtype=jnp.float32
) -> Tuple[Pytree, jnp.ndarray]:
    """Gradient accumulation: scan over microbatches (batch dim split K-ways) so
    live activations scale with the microbatch, not the global batch. Grads
    accumulate in ``grad_dtype`` (fp32 default; bf16 is a hillclimb option that
    halves grad all-reduce bytes). With K=1 this is a plain value_and_grad."""
    if n_microbatches <= 1:
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return grads, loss
    mb = jax.tree.map(
        lambda x: x.reshape(n_microbatches, x.shape[0] // n_microbatches, *x.shape[1:]),
        batch,
    )
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, grad_dtype), params)

    def body(carry, b):
        acc_g, acc_l = carry
        loss, grads = jax.value_and_grad(loss_fn)(params, b)
        acc_g = jax.tree.map(lambda a, g: a + g.astype(grad_dtype), acc_g, grads)
        return (acc_g, acc_l + loss), None

    from repro.models.layers import uscan

    (acc_g, acc_l), _ = uscan(body, (g0, jnp.zeros((), jnp.float32)), mb)
    k = float(n_microbatches)
    return jax.tree.map(lambda g: g / k, acc_g), acc_l / k


def _explicit_mesh(tree: Pytree):
    """The mesh of ``tree``'s leaves when it types them with Explicit axes
    (``jax.make_mesh``'s default since JAX 0.7), else None."""
    for leaf in jax.tree.leaves(tree):
        mesh = jax.typeof(leaf).sharding.mesh
        if not mesh.empty and mesh.are_all_axes_explicit:
            return mesh
    return None


def _on_auto_axes(step: Callable, out_specs: Callable) -> Callable:
    """Run ``step`` — model code written for sharding propagation and
    ``with_sharding_constraint`` hints — with the mesh's axes switched to
    Auto. On an explicit-sharding mesh the outputs are typed back with
    ``out_specs(*args)``; on any other input ``step`` runs as is."""
    def run(*args):
        mesh = _explicit_mesh(args)
        if mesh is None:
            return step(*args)
        with jax.sharding.use_abstract_mesh(mesh):
            return jax.sharding.auto_axes(step, out_sharding=out_specs(*args))(*args)

    return run


def _specs(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda x: jax.typeof(x).sharding.spec, tree)


def make_train_step(
    cfg: ArchConfig,
    opt: Optimizer,
    mode: str,
    n_microbatches: int = 1,
    grad_dtype: str = "float32",
    remat_policy: str = "full",
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, loss).

    mode="shadow": leaves carry a leading replica dim; grads stay replica-local.
    mode="syncdp": plain synchronous DP (grads all-reduce over every batch axis)."""
    loss_fn = _loss_fn(cfg, remat_policy)
    gdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[grad_dtype]

    if mode == "shadow":
        def train_step(params, opt_state, batch):
            def one(p, st, b):
                grads, loss = _accum_grads(loss_fn, p, b, n_microbatches, gdt)
                p2, st2 = opt.update(p, st, grads)
                return p2, st2, loss

            # NOTE: per-replica losses are returned UN-reduced — averaging them
            # on-device would insert a (scalar) cross-pod all-reduce into the
            # training step, breaking the zero-cross-pod-traffic property.
            # Each trainer reports its own loss, exactly as in the paper.
            p2, st2, loss = jax.vmap(one, spmd_axis_name="pod")(params, opt_state, batch)
            return p2, st2, loss

        return _on_auto_axes(train_step, lambda p, st, b: (_specs(p), _specs(st), P("pod")))

    def train_step(params, opt_state, batch):
        grads, loss = _accum_grads(loss_fn, params, batch, n_microbatches, gdt)
        p2, st2 = opt.update(params, opt_state, grads)
        return p2, st2, loss

    return _on_auto_axes(train_step, lambda p, st, b: (_specs(p), _specs(st), P()))


def make_sync_step(cfg: ArchConfig, sync_cfg: S.SyncConfig) -> Callable:
    """The background program. Owns ALL cross-replica communication.

    Uniform signature across every registered algorithm:
    ``sync_step(params_stack, algo_state) -> (params_stack, algo_state)``,
    where ``algo_state`` is the opaque state from
    ``algorithms.get(name).init_state(params, sync_cfg)`` (None for the
    stateless ones — jit treats None as an empty pytree, so one compiled
    program shape serves them all)."""
    from repro.core import algorithms

    step = algorithms.get(sync_cfg.algo).make_sync_step(sync_cfg)
    return _on_auto_axes(step, lambda params, algo_state=None: (_specs(params), _specs(algo_state)))


def make_prefill_step(cfg: ArchConfig, s_max: int) -> Callable:
    if cfg.family == "audio":
        def prefill(params, batch):
            enc_out = whisper.encode(params, cfg, batch["frames"])
            hidden = whisper.decode_full(params, cfg, batch["tokens"], enc_out, return_hidden=True)
            logits = hidden[:, -1, :] @ params["embed"]["table"].T
            cache = whisper.init_cache(cfg, batch["tokens"].shape[0], s_max)
            cross = whisper.build_cross_cache(params, cfg, enc_out)
            return logits, {"self": cache["self"], "cross": cross}

        return prefill

    def prefill(params, batch):
        return transformer.prefill(
            params,
            cfg,
            batch["tokens"],
            s_max,
            prefix_embeds=batch.get("prefix_embeds"),
        )

    return prefill


def make_decode_step(cfg: ArchConfig) -> Callable:
    if cfg.family == "audio":
        def decode(params, cache, token, pos):
            return whisper.decode_step(params, cfg, cache, token, pos)

        return decode

    def decode(params, cache, token, pos):
        return transformer.decode_step(params, cfg, cache, token, pos)

    return decode


def init_cache(cfg: ArchConfig, batch: int, s_max: int) -> Pytree:
    if cfg.family == "audio":
        return whisper.init_cache(cfg, batch, s_max)
    return transformer.init_cache(cfg, batch, s_max)

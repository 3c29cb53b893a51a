"""Train-step construction: loss, grad, update — HyperShard/HyperOffload aware.

``make_train_step`` assembles the full pjit'd step for a (config, mesh,
plan) triple.  All sharding decisions come from HyperShard; all memory-
tier decisions from HyperOffload; the model code is strategy-free.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hypershard, offload as off
from repro.core.meshctx import use_mesh
from repro.models import model as M
from repro.optim import adamw as opt_mod


def cross_entropy_parts(logits, targets, mask, vocab_size: int):
    """(masked NLL sum, mask sum) — the unreduced halves of the mean CE.

    Factored out so the pipeline trainer can normalise each micro-batch's
    NLL sum by the GLOBAL batch's mask count (known upfront): summing
    ``nll_sum_m / N_total`` over micro-batches reproduces the plain
    trainer's whole-batch mean exactly, which per-micro means would not.
    """
    V_pad = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if V_pad > vocab_size:
        # mask padded vocab without materialising a gather
        valid = jnp.arange(V_pad) < vocab_size
        lf = jnp.where(valid, lf, -1e30)
    lse = jax.nn.logsumexp(lf, axis=-1)
    # one-hot contraction instead of take_along_axis: stays sharded over the
    # vocab axis (a gather here would all-gather the full logits)
    oh = jax.nn.one_hot(targets, V_pad, dtype=lf.dtype)
    picked = jnp.einsum("bsv,bsv->bs", lf, oh)
    nll = (lse - picked) * mask
    return nll.sum(), mask.sum()


def cross_entropy(logits, targets, mask, vocab_size: int):
    """Mean CE over masked tokens; logits may be vocab-padded."""
    nll_sum, mask_sum = cross_entropy_parts(logits, targets, mask, vocab_size)
    return nll_sum / jnp.maximum(mask_sum, 1.0)


def loss_fn(params, batch, cfg, *, moe_dispatch="gshard", remat=True,
            prefix_embeds=None, unroll=False):
    logits, _, metrics = M.forward(params, batch["inputs"], cfg,
                                   prefix_embeds=prefix_embeds, mode="train",
                                   moe_dispatch=moe_dispatch, remat=remat,
                                   unroll=unroll)
    ce = cross_entropy(logits, batch["targets"], batch["mask"], cfg.vocab_size)
    aux = jnp.float32(0)
    if cfg.moe is not None:
        aux = (cfg.moe.router_aux_coef * metrics["moe_aux_loss"]
               + cfg.moe.router_z_coef * metrics["moe_z_loss"])
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux, **metrics}


def make_train_step(cfg, mesh: Optional[Mesh], plan: hypershard.ShardingPlan,
                    adamw_cfg: opt_mod.AdamWConfig, *,
                    offload_cfg: off.OffloadConfig = off.OffloadConfig(),
                    moe_dispatch: str = "gshard", donate: bool = True,
                    multimodal: bool = False, unroll: bool = False):
    """Returns (step_fn, shardings dict). step(params, opt, batch)->(p,o,metrics)."""

    # the function's name names the XLA module: jit_train_step
    def train_step(params, opt_state, batch):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            pe = batch.get("prefix_embeds") if multimodal else None
            lf = functools.partial(loss_fn, cfg=cfg, moe_dispatch=moe_dispatch,
                                   prefix_embeds=pe, unroll=unroll)
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, {k: v for k, v in batch.items()
                                           if k != "prefix_embeds"})
            new_params, new_opt, om = opt_mod.adamw_update(
                grads, opt_state, params, adamw_cfg)
            metrics = {"loss": loss, **metrics, **om}
        return new_params, new_opt, metrics

    if mesh is None:
        return jax.jit(train_step,
                       donate_argnums=(0, 1) if donate else ()), {}

    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    scalar_sh = NamedSharding(mesh, P())
    opt_in = opt_mod.AdamWState(mu=param_sh, nu=param_sh, count=scalar_sh)

    from repro.data.pipeline import batch_spec
    bspec = batch_spec(mesh)
    batch_sh = {k: NamedSharding(mesh, bspec)
                for k in ("inputs", "targets", "mask")}
    if multimodal:
        batch_sh["prefix_embeds"] = NamedSharding(
            mesh, P(bspec[0], None, None))
    metrics_sh = None   # let jit infer (all scalars, replicated)

    shardings = {"params": param_sh, "opt_in": opt_in, "batch": batch_sh}
    # NOTE on HyperOffload: XLA SPMD in this jax version rejects memory-
    # kind placement annotations inside partitioned computations whenever
    # the annotated op's sharding isn't attached ("side-effect HLO must
    # have sharding" / "cannot be replicated").  The step is therefore a
    # pure-device jit; the host<->HBM legs of the HyperOffload cycle are
    # ASYNC device_puts between steps (fetch_state / offload_state below),
    # which XLA executes as DMA overlapping dispatch.  In-graph per-layer
    # streaming remains available via offload.streamed_apply (per-layer
    # host arguments, unrolled), used by the offload benchmarks.
    step_jit = jax.jit(
        train_step,
        in_shardings=(param_sh, opt_in, batch_sh),
        out_shardings=(param_sh, opt_in, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return step_jit, shardings


def fetch_state(params, opt_state, shardings, offload_cfg):
    """Host->device leg of the HyperOffload cycle (outside jit, async)."""
    if offload_cfg.params_on_host:
        params = jax.device_put(params, shardings["params"])
    if offload_cfg.opt_state_on_host:
        opt_state = opt_mod.AdamWState(
            mu=jax.device_put(opt_state.mu, shardings["params"]),
            nu=jax.device_put(opt_state.nu, shardings["params"]),
            count=opt_state.count)
    return params, opt_state


def offload_state(params, opt_state, shardings, offload_cfg):
    """Device->host leg of the HyperOffload cycle (outside jit, async)."""
    if offload_cfg.params_on_host:
        params = jax.device_put(params, off.host_shardings(shardings["params"]))
    if offload_cfg.opt_state_on_host:
        opt_state = opt_mod.AdamWState(
            mu=jax.device_put(opt_state.mu,
                              off.host_shardings(shardings["params"])),
            nu=jax.device_put(opt_state.nu,
                              off.host_shardings(shardings["params"])),
            count=opt_state.count)
    return params, opt_state


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def init_state(cfg, mesh: Optional[Mesh], plan, *, seed: int = 0,
               offload_cfg: off.OffloadConfig = off.OffloadConfig()):
    """Initialise (params, opt_state) with HyperShard layouts applied."""
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        params = M.init_model(cfg, key)
        return params, opt_mod.init_adamw(params)
    pshapes = jax.eval_shape(lambda: M.init_model(cfg, key))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    init_jit = jax.jit(lambda k: M.init_model(cfg, k), out_shardings=param_sh)
    params = init_jit(key)
    opt = jax.jit(opt_mod.init_adamw,
                  out_shardings=opt_mod.AdamWState(
                      mu=param_sh, nu=param_sh,
                      count=NamedSharding(mesh, P())))(params)
    if offload_cfg.params_on_host:
        params = jax.device_put(params, off.host_shardings(param_sh))
    if offload_cfg.opt_state_on_host:
        opt = opt_mod.AdamWState(
            mu=jax.device_put(opt.mu, off.host_shardings(param_sh)),
            nu=jax.device_put(opt.nu, off.host_shardings(param_sh)),
            count=opt.count)
    return params, opt

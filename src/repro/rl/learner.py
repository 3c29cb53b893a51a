"""GRPO learner: masked clipped policy-gradient update, HyperShard-aware.

Mirrors :mod:`repro.train.steps` — same param/batch sharding derivation,
same AdamW update, same pure-device jit discipline — with the RL
objective in place of cross-entropy.  Per-token policy logprobs use the
same one-hot contraction as ``steps.cross_entropy`` so the logits stay
sharded over the vocab/model axis (a gather would all-gather them), and
the logits are temperature-scaled to the SAME distribution the actor
sampled from, so the PPO-style importance ratio

    ratio = exp(logp_learner - logp_behaviour)

starts at ~1 on on-policy data.  Loss per masked response token:

    -min(ratio * A, clip(ratio, 1-eps, 1+eps) * A)

with A the group-relative advantage broadcast over the sample's response.
MoE configs keep their router aux/z losses (same coefficients as
pre-training) so expert balance does not collapse during post-training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import RLConfig
from repro.core import hypershard
from repro.core.meshctx import use_mesh
from repro.models import model as M
from repro.optim import adamw as opt_mod
from repro.train import steps as steps_mod


def token_logprobs(logits, targets, vocab_size: int, *,
                   temperature: float = 1.0):
    """Per-token logprob of ``targets`` under temperature-scaled logits.

    Stays sharded over the vocab axis (one-hot contraction, no gather);
    padded vocab entries are masked to -inf before the logsumexp.
    """
    V_pad = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if V_pad > vocab_size:
        valid = jnp.arange(V_pad) < vocab_size
        lf = jnp.where(valid, lf, -1e30)
    lf = lf / jnp.maximum(temperature, 1e-6)
    lse = jax.nn.logsumexp(lf, axis=-1)
    oh = jax.nn.one_hot(targets, V_pad, dtype=lf.dtype)
    picked = jnp.einsum("bsv,bsv->bs", lf, oh)
    return picked - lse


def grpo_loss(params, batch, cfg, *, rl_cfg: RLConfig,
              moe_dispatch: str = "gshard", remat: bool = True):
    logits, _, metrics = M.forward(params, batch["inputs"], cfg,
                                   mode="train", moe_dispatch=moe_dispatch,
                                   remat=remat)
    logp = token_logprobs(logits, batch["targets"], cfg.vocab_size,
                          temperature=rl_cfg.temperature)
    mask = batch["mask"]
    n_tok = jnp.maximum(mask.sum(), 1.0)
    ratio = jnp.exp(logp - batch["behaviour_logp"]) * mask
    adv = batch["advantages"][:, None]
    clipped = jnp.clip(ratio, 1.0 - rl_cfg.clip_eps, 1.0 + rl_cfg.clip_eps)
    pg = -jnp.minimum(ratio * adv, clipped * adv)
    pg_loss = (pg * mask).sum() / n_tok
    aux = jnp.float32(0)
    if cfg.moe is not None:
        aux = (cfg.moe.router_aux_coef * metrics["moe_aux_loss"]
               + cfg.moe.router_z_coef * metrics["moe_z_loss"])
    loss = pg_loss + aux
    clip_frac = ((jnp.abs(ratio - clipped) > 0) * mask).sum() / n_tok
    return loss, {"pg_loss": pg_loss, "aux": aux,
                  "ratio_mean": (ratio * mask).sum() / n_tok,
                  "clip_fraction": clip_frac,
                  "logp_mean": (logp * mask).sum() / n_tok, **metrics}


def make_rl_step(cfg, mesh: Optional[Mesh], plan: hypershard.ShardingPlan,
                 adamw_cfg: opt_mod.AdamWConfig, *, rl_cfg: RLConfig,
                 moe_dispatch: str = "gshard", donate: bool = True):
    """Returns (step_fn, shardings): step(params, opt, batch)->(p,o,metrics).

    The twin of :func:`repro.train.steps.make_train_step`, with the GRPO
    batch contract: inputs/targets (B,S) int32, mask/behaviour_logp (B,S)
    float32, advantages (B,) float32.
    """

    def grpo_step(params, opt_state, batch):
        ctx = use_mesh(mesh) if mesh is not None else _null()
        with ctx:
            lf = functools.partial(grpo_loss, cfg=cfg, rl_cfg=rl_cfg,
                                   moe_dispatch=moe_dispatch)
            (loss, metrics), grads = jax.value_and_grad(
                lf, has_aux=True)(params, batch)
            new_params, new_opt, om = opt_mod.adamw_update(
                grads, opt_state, params, adamw_cfg)
            metrics = {"loss": loss, **metrics, **om}
        return new_params, new_opt, metrics

    if mesh is None:
        return jax.jit(grpo_step,
                       donate_argnums=(0, 1) if donate else ()), {}

    pshapes = jax.eval_shape(lambda: M.init_model(cfg, jax.random.PRNGKey(0)))
    param_sh = hypershard.make_param_shardings(mesh, pshapes, plan)
    scalar_sh = NamedSharding(mesh, P())
    opt_in = opt_mod.AdamWState(mu=param_sh, nu=param_sh, count=scalar_sh)

    from repro.data.pipeline import batch_spec
    bspec = batch_spec(mesh)
    row_sh = NamedSharding(mesh, bspec)
    batch_sh = {k: row_sh for k in ("inputs", "targets", "mask",
                                    "behaviour_logp")}
    batch_sh["advantages"] = NamedSharding(mesh, P(bspec[0]))
    shardings = {"params": param_sh, "opt_in": opt_in, "batch": batch_sh}
    step_jit = jax.jit(grpo_step,
                       in_shardings=(param_sh, opt_in, batch_sh),
                       out_shardings=(param_sh, opt_in, None),
                       donate_argnums=(0, 1) if donate else ())
    return step_jit, shardings


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


class GRPOLearner:
    """Owns the policy being trained: params + AdamW state + jit'd step.

    ``params=None`` initialises fresh under the plan's layouts (the usual
    path — RL fine-tunes whatever ``session.train`` produced, so tests
    and examples hand the trained tree straight in).
    """

    def __init__(self, cfg, mesh: Optional[Mesh],
                 plan: hypershard.ShardingPlan, *,
                 rl_cfg: Optional[RLConfig] = None, params=None,
                 adamw: Optional[opt_mod.AdamWConfig] = None, seed: int = 0,
                 moe_dispatch: str = "gshard", obs=None):
        from repro.obs import Observability
        self.cfg = cfg
        self.mesh = mesh
        self.plan = plan
        self.obs = obs if obs is not None else Observability()
        self.rl_cfg = rl_cfg or RLConfig()
        adamw = adamw or opt_mod.AdamWConfig(lr=self.rl_cfg.lr,
                                             warmup_steps=0)
        self.step_fn, self.shardings = make_rl_step(
            cfg, mesh, plan, adamw, rl_cfg=self.rl_cfg,
            moe_dispatch=moe_dispatch, donate=False)
        if params is None:
            self.params, self.opt = steps_mod.init_state(cfg, mesh, plan,
                                                         seed=seed)
        else:
            if mesh is not None:
                params = jax.tree.map(jax.device_put, params,
                                      self.shardings["params"])
                self.opt = jax.jit(opt_mod.init_adamw, out_shardings=
                                   self.shardings["opt_in"])(params)
            else:
                self.opt = opt_mod.init_adamw(params)
            self.params = params
        self.updates = 0

    def update(self, batch) -> dict:
        """One GRPO step over a :meth:`RolloutBuffer.batch` dict."""
        # the batch shape is pad_len_to-bucketed upstream; a NEW shape key
        # here is a genuine XLA retrace of the GRPO step
        self.obs.record_compile(
            "rl_step", tuple(tuple(v.shape) for _, v in sorted(batch.items())))
        with self.obs.trace.span("rl.update", track="learner",
                                 rows=len(batch["advantages"])):
            if self.mesh is not None:
                batch = {k: jax.device_put(v, self.shardings["batch"][k])
                         for k, v in batch.items()}
            else:
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt, metrics = self.step_fn(
                self.params, self.opt, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
        self.updates += 1
        self.obs.metrics.counter("rl.updates").inc()
        self.obs.metrics.gauge("rl.loss").set(metrics.get("loss", 0.0))
        return metrics

    def dp_size(self) -> int:
        """Row-divisibility the learner batch must satisfy (dp axes)."""
        if self.mesh is None:
            return 1
        n = 1
        for a in ("pod", "data"):
            if a in self.mesh.axis_names:
                n *= self.mesh.shape[a]
        return n

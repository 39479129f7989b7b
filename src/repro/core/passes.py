"""Compression passes as standard building blocks (the paper's Fig. 1).

Each pass declares static metadata (kind: static/dynamic, granularity:
architecture/neuron/sub-neuron — the two axes the paper's sequence law is
stated in), a *typed* hyperparameter dataclass, and a transform
``fn(state, hp, trainer) -> state``; all of it is packaged as a
:class:`repro.core.registry.CompressionPass` and registered in the global
registry.  Fine-tuning after every pass uses 1/10 of the initial LR,
matching the paper's protocol.

Migration note (old API → registry): ``PASSES`` used to be a closed module
dict of exactly D/P/Q/E.  It is now a live read-only *view* of
``core.registry`` — existing ``PASSES['Q'].apply(state, {...}, trainer)``
call sites keep working (dict hps are coerced to the typed dataclass), and
newly registered passes (e.g. low-rank 'L' from core/lowrank.py, or any
third-party pass) appear in it automatically.  New code should use
``registry.get_pass`` / ``chain.Pipeline`` directly.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass, field, replace
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import registry


# ------------------------------------------------------------------ trainer


def mask_like(params, select: Callable[[str], bool]):
    """0/1 mask pytree: 1 where the top-level key satisfies `select`."""
    return {k: jax.tree.map(lambda x: jnp.ones((), x.dtype) * float(select(k)),
                            v) for k, v in params.items()}


@dataclass
class Trainer:
    batch: int = 64
    steps: int = 300
    lr: float = 1e-3
    eval_n: int = 4
    eval_batch: int = 256
    weight_decay: float = 1e-4
    seed: int = 0

    def fit(self, family, cfg, params, *, loss_fn=None, lr=None, steps=None,
            train_keys=None, seed=None, losses=None):
        """SGD loop; train_keys restricts training to those top-level keys.
        ``losses`` (a list) receives every step's loss, in order."""
        from repro.optim import adamw, apply_updates, clip_by_global_norm
        loss_fn = loss_fn or family.loss
        lr = self.lr if lr is None else lr
        steps = self.steps if steps is None else steps
        opt = adamw(lr, weight_decay=self.weight_decay)
        opt_state = opt.init(params)
        mask = None
        if train_keys is not None:
            mask = mask_like(params, lambda k: k in train_keys)

        @jax.jit
        def step(params, opt_state, batch):
            (l, _), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch), has_aux=True)(params)
            grads, _ = clip_by_global_norm(grads, 1.0)
            if mask is not None:
                grads = jax.tree.map(lambda g, m: g * m, grads, mask)
            updates, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, l

        key = jax.random.key(self.seed if seed is None else seed)
        last = None
        for i in range(steps):
            batch = family.train_batch(jax.random.fold_in(key, i), self.batch)
            params, opt_state, last = step(params, opt_state, batch)
            if losses is not None:
                losses.append(float(last))
        return params, float(last) if last is not None else None

    def evaluate(self, family, cfg, params):
        return family.accuracy(params, cfg,
                               family.eval_batches(self.eval_n,
                                                   self.eval_batch))


# -------------------------------------------------------------- chain state


@dataclass
class ChainState:
    family: Any
    cfg: Any
    params: Any
    key: Any
    base_bitops: float = 0.0
    base_bits: float = 0.0
    prune_scale: float = 1.0       # stage-MAC multiplier from pruning
    lowrank_scale: float = 1.0     # stage-MAC multiplier from factorization
    exit_probs: dict | None = None
    exit_threshold: float | None = None   # E's operating point, reused by Q
    dyn_accuracy: float | None = None
    history: list = field(default_factory=list)

    @property
    def mac_scale(self) -> float:
        """Combined stage-MAC multiplier for the BitOps cost model."""
        return self.prune_scale * self.lowrank_scale

    def metrics(self, trainer, label):
        acc = (self.dyn_accuracy if self.dyn_accuracy is not None
               else trainer.evaluate(self.family, self.cfg, self.params))
        bops = self.family.bitops(self.cfg, self.exit_probs, self.mac_scale)
        bits = self.family.storage_bits(self.params, self.cfg)
        rec = {'pass': label, 'acc': acc,
               'BitOpsCR': self.base_bitops / max(bops, 1),
               'CR': self.base_bits / max(bits, 1)}
        self.history.append(rec)
        return rec


def init_chain_state(family, cfg, key, trainer, *, pretrain_steps=None):
    """Train the original model — the paper's baseline."""
    params = family.init(key, cfg)
    params, _ = trainer.fit(family, cfg, params, steps=pretrain_steps)
    st = ChainState(family=family, cfg=cfg, params=params,
                    key=jax.random.fold_in(key, 777))
    st.base_bitops = family.bitops(cfg)
    st.base_bits = family.storage_bits(params, cfg)
    st.metrics(trainer, 'baseline')
    return st


# --------------------------------------------------- typed hyperparameters


@dataclass(frozen=True)
class DistillHP:
    factor: float = 0.5      # student size factor (depth or width)
    temp: float = 2.0        # KD temperature
    alpha: float = 0.5       # KL weight vs. CE


@dataclass(frozen=True)
class PruneHP:
    ratio: float = 0.3       # fraction of channels removed


@dataclass(frozen=True)
class QuantHP:
    w_bits: int = 8
    a_bits: int = 8


@dataclass(frozen=True)
class EarlyExitHP:
    stages: tuple | None = None    # None = family.default_exit_points
    threshold: float = 0.9         # softmax-confidence exit threshold


# ------------------------------------------------------------------- passes


def _distill(state: ChainState, hp: DistillHP, trainer: Trainer) -> ChainState:
    # T=2, alpha=0.5 defaults: at T=4 the T^2-scaled KL dominates the
    # clipped gradient and stalls student training (measured; see
    # EXPERIMENTS.md §Paper-results tuning note)
    temp, alpha = hp.temp, hp.alpha
    fam, t_cfg, t_params = state.family, state.cfg, state.params
    s_cfg = fam.shrink(t_cfg, hp.factor)
    s_params = fam.init(jax.random.fold_in(state.key, 1), s_cfg)

    def kd_loss(p, cfg, batch):
        ce, s_logits = fam.loss(p, cfg, batch)
        t_logits = jax.lax.stop_gradient(fam.logits_of(t_params, t_cfg, batch))
        kl = jnp.mean(jnp.sum(
            jax.nn.softmax(t_logits / temp)
            * (jax.nn.log_softmax(t_logits / temp)
               - jax.nn.log_softmax(s_logits / temp)), axis=-1)) * temp ** 2
        return alpha * kl + (1 - alpha) * ce, s_logits

    # a student is trained from scratch: give it the full (pretrain-scale)
    # budget, like the paper's 200-epoch student training
    s_params, _ = trainer.fit(fam, s_cfg, s_params, loss_fn=kd_loss,
                              steps=trainer.steps * 3,
                              seed=int(jax.random.randint(
                                  state.key, (), 0, 2**31 - 1)))
    new = replace(state, cfg=s_cfg, params=s_params,
                  key=jax.random.fold_in(state.key, 2),
                  exit_probs=None, dyn_accuracy=None, prune_scale=1.0,
                  lowrank_scale=1.0)
    return new


def _prune(state: ChainState, hp: PruneHP, trainer: Trainer) -> ChainState:
    fam = state.family
    params, cfg = fam.prune(state.params, state.cfg, hp.ratio)
    params, _ = trainer.fit(fam, cfg, params, lr=trainer.lr / 10)
    scale = state.prune_scale
    if hasattr(fam, 'pruned_bitops_scale'):
        scale *= fam.pruned_bitops_scale(hp.ratio, cfg)
    return replace(state, cfg=cfg, params=params, prune_scale=scale,
                   key=jax.random.fold_in(state.key, 3),
                   exit_probs=None, dyn_accuracy=None)


def _quantize(state: ChainState, hp: QuantHP, trainer: Trainer) -> ChainState:
    cfg = state.cfg.replace(w_bits=hp.w_bits, a_bits=hp.a_bits)
    params, _ = trainer.fit(state.family, cfg, state.params,
                            lr=trainer.lr / 10)
    new = replace(state, cfg=cfg, params=params,
                  key=jax.random.fold_in(state.key, 4))
    if new.exit_probs is not None:
        # re-measure dynamic stats under quantized compute, at the SAME
        # operating point E established (state.exit_threshold) — Q has no
        # threshold hp of its own, so it cannot silently move it
        thr = (state.exit_threshold if state.exit_threshold is not None
               else 0.9)
        acc, probs = state.family.exit_stats(
            params, cfg, state.family.eval_batches(trainer.eval_n,
                                                   trainer.eval_batch), thr)
        new = replace(new, exit_probs=probs, dyn_accuracy=acc)
    return new


def _early_exit(state: ChainState, hp: EarlyExitHP,
                trainer: Trainer) -> ChainState:
    fam = state.family
    stages = hp.stages
    if stages is None:
        stages = fam.default_exit_points(state.cfg)
    params, cfg = fam.add_exits(jax.random.fold_in(state.key, 5),
                                state.params, state.cfg, stages)
    # paper insight (Sec 3.1.3/3.1.6): exit heads learn from the *student's
    # own body*; train heads only, body frozen, full LR.
    exit_key = 'exits' if 'exits' in params else 'exit_heads'
    loss_fn = getattr(fam, 'exit_loss', None)
    params, _ = trainer.fit(fam, cfg, params, loss_fn=loss_fn,
                            train_keys={exit_key})
    acc, probs = fam.exit_stats(
        params, cfg, fam.eval_batches(trainer.eval_n, trainer.eval_batch),
        hp.threshold)
    return replace(state, cfg=cfg, params=params, exit_probs=probs,
                   exit_threshold=hp.threshold,
                   dyn_accuracy=acc, key=jax.random.fold_in(state.key, 6))


# -------------------------------------------------------------- registration


registry.register(registry.CompressionPass(
    'D', 'distillation', 'static', 'architecture', DistillHP, _distill))
registry.register(registry.CompressionPass(
    'P', 'pruning', 'static', 'neuron', PruneHP, _prune))
registry.register(registry.CompressionPass(
    'Q', 'quantization', 'static', 'sub-neuron', QuantHP, _quantize))
registry.register(registry.CompressionPass(
    'E', 'early-exit', 'dynamic', 'architecture', EarlyExitHP, _early_exit))


class _RegistryView(Mapping):
    """Read-only mapping view of the live registry (old ``PASSES`` API)."""

    def __getitem__(self, key):
        return registry.get_pass(key)

    def __iter__(self):
        return iter(registry.registered_keys())

    def __len__(self):
        return len(registry.registered_keys())


#: Deprecated alias — a live view of ``core.registry`` (see module docstring).
PASSES = _RegistryView()

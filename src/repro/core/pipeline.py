"""The paper's end-to-end pipeline, reusable by examples/ and tests/:

  train CNN -> DDPG pruning search -> fine-tune -> greedy split ->
  compact -> deploy.

Runs at reduced scale on CPU (tiny AlexNet-family CNN + synthetic
PlantVillage-38); every stage is the real algorithm from core/, just on a
smaller model — see DESIGN.md §7.

The deployment stage materializes the pruning masks via ``compact_params``
(physically smaller edge/cloud submodels: real FLOP reduction, not zeroed
channels), re-prices the per-layer costs at the *compacted* shapes with the
chosen feature codec's wire discount, re-picks the split point on those
costs, and packages the whole deployment contract as a
``repro.serving.DeploymentPlan`` (``result.plan``) — save it once with
``plan.save(dir)`` and serve it anywhere via
``serving.connect(plan, backend="local"|"socket"|"streaming")``.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CNNConfig
from repro.core.collab.protocol import CODEC_TX_SCALE
from repro.core.partition.latency_model import (cnn_input_bytes,
                                                cnn_layer_costs,
                                                compacted_cnn_layer_costs)
from repro.core.partition.profiles import PAPER_PROFILE, TwoTierProfile
from repro.core.partition.splitter import SplitDecision, greedy_split
from repro.core.pruning.amc_env import PruningEnv, cnn_layer_descs
from repro.core.pruning.masks import cnn_masks_from_ratios
from repro.core.pruning.policy import SearchResult, search_pruning_policy
from repro.data.synthetic import PlantVillageSynthetic
from repro.models.cnn import (cnn_apply, compact_params, init_cnn_params,
                              prunable_layers)
from repro.optim import make_optimizer, step_lr
from repro.serving.plan import DeploymentPlan


def _xent(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def make_train_step(cfg: CNNConfig, optimizer, masks=None):
    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = cnn_apply(p, cfg, batch["image"], masks=masks)
            return _xent(logits, batch["label"])
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, loss
    return step


def train_cnn(params, cfg: CNNConfig, data: PlantVillageSynthetic,
              epochs: int = 3, batch_size: int = 32, lr: float = 0.01,
              masks=None, log: Optional[Callable] = None,
              optimizer_name: str = "sgd"):
    """Default: SGD momentum 0.9 + StepLR(0.1/20) — the paper's §4.1 recipe.
    ``optimizer_name="adamw"`` is the reduced-scale CPU alternative used by
    smoke tests/examples (plain SGD needs many more epochs at tiny width;
    DESIGN.md §7)."""
    steps_per_epoch = max(len(data.train_ids) // batch_size, 1)
    if optimizer_name == "adamw":
        optimizer = make_optimizer("adamw", step_lr(lr, 0.1, 20,
                                                    steps_per_epoch))
    else:
        optimizer = make_optimizer(
            "sgd", step_lr(lr, 0.1, 20, steps_per_epoch), momentum=0.9)
    opt_state = optimizer.init(params)
    step_fn = make_train_step(cfg, optimizer, masks)
    history = []
    for ep in range(epochs):
        losses = []
        for batch in data.iter_train(batch_size, epochs=1, seed=100 + ep):
            params, opt_state, loss = step_fn(params, opt_state, batch)
            losses.append(float(loss))
        history.append(float(np.mean(losses)))
        if log:
            log(f"epoch {ep}: loss {history[-1]:.4f}")
    return params, history


def evaluate_topk(params, cfg: CNNConfig, data: PlantVillageSynthetic,
                  ks: Tuple[int, ...] = (1, 3, 5), masks=None,
                  batch_size: int = 64) -> Dict[str, float]:
    fn = jax.jit(lambda x: cnn_apply(params, cfg, x, masks=masks))
    hits = {k: 0 for k in ks}
    n = 0
    for batch in data.test_batches(batch_size):
        logits = np.asarray(fn(batch["image"]))
        order = np.argsort(-logits, axis=-1)
        for k in ks:
            hits[k] += (order[:, :k] == batch["label"][:, None]).any(1).sum()
        n += len(batch["label"])
    return {f"top{k}": hits[k] / n for k in ks}


@dataclass
class PaperPipelineResult:
    cfg: CNNConfig
    params: Dict
    masks: Dict
    acc_original: Dict[str, float]
    acc_pruned: Dict[str, float]
    acc_finetuned: Dict[str, float]
    ratios: Dict[int, float]
    search: SearchResult
    split: SplitDecision
    profile: TwoTierProfile
    # deployment artifacts (compacted fast path)
    compact_params: Optional[Dict] = None
    compact_cfg: Optional[CNNConfig] = None
    deploy_split: Optional[SplitDecision] = None
    deploy_codec: str = "fp32"
    # the unified deployment contract (repro.serving): save with
    # plan.save(dir), serve with serving.connect(plan, backend=...)
    plan: Optional[DeploymentPlan] = None


def run_paper_pipeline(cfg: CNNConfig, data: PlantVillageSynthetic,
                       train_epochs: int = 4, finetune_epochs: int = 2,
                       episodes: int = 40, warmup: int = 10,
                       flops_budget: float = 0.5,
                       profile: TwoTierProfile = PAPER_PROFILE,
                       seed: int = 0,
                       log: Optional[Callable] = None,
                       optimizer_name: str = "sgd", lr: float = 0.01,
                       deploy_codec: str = "fp32"
                       ) -> PaperPipelineResult:
    log = log or (lambda s: None)
    key = jax.random.PRNGKey(seed)
    params = init_cnn_params(key, cfg)

    log("[1/6] train original model")
    params, _ = train_cnn(params, cfg, data, epochs=train_epochs, log=log,
                          lr=lr, optimizer_name=optimizer_name)
    acc0 = evaluate_topk(params, cfg, data)
    log(f"    original acc: {acc0}")

    log("[2/6] DDPG pruning search (AMC, Eq. 1-4)")
    players = prunable_layers(cfg)
    descs = cnn_layer_descs(cfg)

    # fast reward evaluation on a fixed subset of the test split
    eval_ids = data.test_ids[::max(len(data.test_ids) // 256, 1)]
    eval_batch = data._batch(eval_ids)

    @functools.lru_cache(maxsize=512)
    def _acc_for(ratio_key) -> float:
        ratios = dict(zip(players, ratio_key))
        masks = cnn_masks_from_ratios(params, cfg, ratios)
        logits = np.asarray(cnn_apply(params, cfg,
                                      jnp.asarray(eval_batch["image"]),
                                      masks=masks))
        return float((logits.argmax(-1) == eval_batch["label"]).mean())

    def evaluate(actions: List[float]) -> float:
        return _acc_for(tuple(round(a, 3) for a in actions))

    env = PruningEnv(descs, evaluate, flops_budget=flops_budget)
    search = search_pruning_policy(env, episodes=episodes, warmup=warmup,
                                   seed=seed, log=log)
    ratios = dict(zip(players, search.best_ratios))
    log(f"    best ratios: { {k: round(v, 3) for k, v in ratios.items()} } "
        f"flops_kept={search.best_flops_kept:.3f}")

    log("[3/6] evaluate pruned model")
    masks = cnn_masks_from_ratios(params, cfg, ratios)
    acc_pruned = evaluate_topk(params, cfg, data, masks=masks)
    log(f"    pruned acc: {acc_pruned}")

    log("[4/6] fine-tune pruned model (SGD m=0.9, StepLR)")
    ft_params, _ = train_cnn(params, cfg, data, epochs=finetune_epochs,
                             masks=masks, log=log, lr=lr * 0.3,
                             optimizer_name=optimizer_name)
    acc_ft = evaluate_topk(ft_params, cfg, data, masks=masks)
    log(f"    fine-tuned acc: {acc_ft}")

    log("[5/6] greedy split search (Algorithm 1 lines 20-27)")
    costs = cnn_layer_costs(cfg, masks)
    split = greedy_split(costs, profile, cnn_input_bytes(cfg))
    log(f"    optimal split c={split.split_point} "
        f"T={split.latency['T'] * 1e3:.2f} ms "
        f"(T_D={split.latency['T_D'] * 1e3:.2f} "
        f"T_TX={split.latency['T_TX'] * 1e3:.2f} "
        f"T_S={split.latency['T_S'] * 1e3:.2f})")

    log("[6/6] compact deployment + re-priced split on compacted shapes")
    cparams, ccfg = compact_params(ft_params, cfg, masks)
    dcosts = compacted_cnn_layer_costs(cfg, masks)
    deploy = greedy_split(dcosts, profile, cnn_input_bytes(cfg),
                          tx_scale=CODEC_TX_SCALE[deploy_codec])
    log(f"    deploy split c={deploy.split_point} codec={deploy_codec} "
        f"T={deploy.latency['T'] * 1e3:.2f} ms "
        f"tx={deploy.latency['tx_bytes'] / 1024:.1f} KB")
    plan = DeploymentPlan.from_args(ft_params, cfg, deploy.split_point,
                                    masks=masks, compact=bool(masks),
                                    codec=deploy_codec, profile=profile)
    log(f"    {plan.describe()}")
    return PaperPipelineResult(cfg, ft_params, masks, acc0, acc_pruned,
                               acc_ft, ratios, search, split, profile,
                               compact_params=cparams, compact_cfg=ccfg,
                               deploy_split=deploy,
                               deploy_codec=deploy_codec, plan=plan)

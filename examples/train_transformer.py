"""Train a reduced assigned-architecture transformer end-to-end on CPU:
sharded jit (host mesh), AdamW, cosine schedule, checkpointing, loss curve.

    PYTHONPATH=src python examples/train_transformer.py \
        --arch qwen2-7b --steps 100

(The paper's kind is inference/serving, so the flagship end-to-end driver is
examples/collaborative_serve.py; this driver exercises the training substrate
on a reduced config — the full configs train only in the multi-pod dry-run.)
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.checkpoint import store
from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tr
from repro.optim import adamw, cosine_warmup
from repro.sharding.specs import batch_specs, param_specs, to_shardings


def synth_batch(cfg, key, B, S):
    """Markov-chain synthetic tokens (learnable bigram structure)."""
    k1, k2 = jax.random.split(key)
    start = jax.random.randint(k1, (B, 1), 0, cfg.vocab_size)
    steps = jax.random.randint(k2, (B, S - 1), 1, 17)
    tok = jnp.concatenate(
        [start, (start + jnp.cumsum(steps, 1)) % cfg.vocab_size], 1)
    batch = {"tokens": tok,
             "labels": jnp.concatenate(
                 [tok[:, 1:], -jnp.ones((B, 1), jnp.int32)], 1)}
    if cfg.vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            k2, (B, cfg.vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.embeds_input:
        batch = {"embeds": jax.random.normal(k1, (B, S, cfg.d_model),
                                             jnp.dtype(cfg.dtype)),
                 "labels": tok}
    return batch


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt/train_transformer")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    n = tr.param_count(params)
    print(f"{args.arch} (reduced): {n / 1e6:.2f}M params, "
          f"{cfg.num_layers}L d{cfg.d_model}")
    opt = adamw(cosine_warmup(args.lr, warmup=min(10, args.steps // 5),
                          total=args.steps))
    opt_state = opt.init(params)
    mesh = make_host_mesh()

    def step_fn(p, s, b):
        (loss, metrics), grads = jax.value_and_grad(
            tr.loss_fn, has_aux=True)(p, cfg, b)
        p, s = opt.update(grads, s, p)
        return p, s, metrics

    with mesh:
        pshard = to_shardings(param_specs(params, cfg, mesh), mesh)
        b0 = synth_batch(cfg, jax.random.PRNGKey(1), args.batch, args.seq)
        bshard = to_shardings(batch_specs(b0, cfg, mesh), mesh)
        jitted = jax.jit(step_fn, in_shardings=(pshard, None, bshard),
                         donate_argnums=(0, 1))
        losses = []
        t0 = time.time()
        for i in range(args.steps):
            batch = synth_batch(cfg, jax.random.PRNGKey(100 + i),
                                args.batch, args.seq)
            params, opt_state, m = jitted(params, opt_state, batch)
            losses.append(float(m["loss"]))
            if i % 10 == 0 or i == args.steps - 1:
                dt = (time.time() - t0) / (i + 1)
                print(f"step {i:4d}  loss {losses[-1]:.4f}  "
                      f"xent {float(m['xent']):.4f}  {dt * 1e3:.0f} ms/step")
    head = float(np.mean(losses[:5]))
    tail = float(np.mean(losses[-5:]))
    assert tail < head, f"training must reduce the loss ({head} -> {tail})"
    os.makedirs(os.path.dirname(args.ckpt), exist_ok=True)
    store.save(args.ckpt, params, metadata={"arch": args.arch,
                                            "steps": args.steps,
                                            "final_loss": losses[-1]})
    print(f"checkpoint -> {args.ckpt}(.npz/.json)  "
          f"final loss {losses[-1]:.4f} (from {losses[0]:.4f})")


if __name__ == "__main__":
    main()

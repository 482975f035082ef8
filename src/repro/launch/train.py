"""Federated LM fine-tuning driver — the framework's end-to-end train
entrypoint, combining:

  * an assigned architecture (``--arch``, reduced or full),
  * synthetic per-client token streams with Dirichlet topic skew,
  * per-round client selection (HiCS-FL or any baseline),
  * pjit'd local training on the mesh (CPU: 1x1 host mesh; TPU: the
    16x16 / 2x16x16 production mesh),
  * npz checkpointing.

Federation pattern: each round the server broadcasts θ^t, the selected
clients run R local epochs on their own token stream, the server
averages the returned models and feeds the LM-head updates (Δb or the
bias-free ΔW-row-mean surrogate) to the selector.  Exactly Algorithm 1,
with the classifier replaced by a language model — the regime where
HiCS-FL's O(C) selection actually matters (C = vocab).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --rounds 20 --clients 8 --select 2 --selector hics
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs import get_config
from repro.core import (head_bias_updates_stacked, head_num_classes,
                        make_selector)
from repro.data import make_lm_streams
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.optim import adam, apply_updates, clip_by_global_norm, sgd


def local_lm_update(api, params, tokens, lr, epochs, opt_name="sgd"):
    """R epochs of LM training on one client's (num_seqs, S) stream."""
    opt = (adam(lr) if opt_name == "adam" else sgd(lr))

    @jax.jit
    def run(params, tokens):
        opt_state = opt.init(params)

        def seq_step(carry, seq):
            params, opt_state = carry
            batch = {"tokens": seq[None, :-1],
                     "targets": seq[None, 1:],
                     "loss_mask": jnp.ones((1, seq.shape[0] - 1),
                                           jnp.float32)}

            def lf(p):
                loss, m = api.loss(p, batch, dtype=jnp.float32)
                return loss

            loss, grads = jax.value_and_grad(lf)(params)
            grads, _ = clip_by_global_norm(grads, 1.0)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return (params, opt_state), loss

        def epoch(carry, _):
            carry, losses = jax.lax.scan(seq_step, carry, tokens)
            return carry, losses.mean()

        (params, _), losses = jax.lax.scan(
            epoch, (params, opt_state), jnp.arange(epochs))
        return params, losses.mean()

    return run(params, tokens)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--select", type=int, default=2)
    ap.add_argument("--selector", default="hics")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--seqs-per-client", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--temperature", type=float, default=0.01)
    ap.add_argument("--alphas", type=float, nargs="+",
                    default=[0.05, 0.05, 0.05, 5.0])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--telemetry", default="",
                    help="write per-round telemetry to this JSONL path "
                         "(training/selection/fairness fields; see "
                         "docs/observability.md)")
    ap.add_argument("--out", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    rng = np.random.default_rng(args.seed)
    toks, mixes = make_lm_streams(
        rng, cfg.vocab_size, args.seq_len + 1, args.clients,
        args.seqs_per_client, args.alphas)
    toks = jnp.asarray(toks)

    key = jax.random.PRNGKey(args.seed)
    params = api.init(key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M vocab={cfg.vocab_size}")

    # uniform kwarg surface: selectors that don't use a kwarg ignore it,
    # so there is no per-selector construction branch
    sel = make_selector(args.selector, num_clients=args.clients,
                        num_select=args.select, total_rounds=args.rounds,
                        temperature=args.temperature,
                        num_classes=head_num_classes(params) or 1,
                        seed=args.seed)

    mesh = make_host_mesh()
    history = {"round": [], "loss": [], "selected": [],
               "bias_entropy": [], "wall_s": []}
    with mesh:
        for t in range(args.rounds):
            t0 = time.time()
            ids = sel.select(t)
            new_params, losses = [], []
            for k in ids:
                pk, loss = local_lm_update(api, params, toks[k], args.lr,
                                           args.epochs)
                new_params.append(pk)
                losses.append(float(loss))
            # Δb for the whole cohort in one stacked-leaf subtraction
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_params)
            dbs = head_bias_updates_stacked(params, stacked)
            params = jax.tree_util.tree_map(
                lambda s: jnp.mean(s, axis=0), stacked)
            sel.update(t, ids, bias_updates=dbs)
            ent = sel.estimated_entropies()
            history["round"].append(t)
            history["loss"].append(float(np.mean(losses)))
            history["selected"].append(list(map(int, ids)))
            history["bias_entropy"].append(
                None if ent is None else ent.tolist())
            history["wall_s"].append(time.time() - t0)
            print(f"round {t:3d} loss={np.mean(losses):.4f} "
                  f"sel={list(ids)} "
                  f"({history['wall_s'][-1]:.1f}s)", flush=True)
            if args.ckpt_dir and (t + 1) % 10 == 0:
                save_pytree(Path(args.ckpt_dir) / f"step_{t+1}.npz",
                            params, step=t + 1)
    history["select_seconds"] = sel.select_seconds
    history["update_seconds"] = sel.update_seconds
    if args.telemetry:
        from repro.telemetry import write_run
        # same field names as the in-scan metric groups
        # (repro.telemetry.metrics) so downstream tooling reads both
        counts = np.zeros(args.clients)
        part, eff = [], []
        for ids in history["selected"]:
            counts[ids] += 1
            p = counts / counts.sum()
            h = -(p * np.log(np.where(p > 0, p, 1.0))).sum()
            part.append((counts > 0).mean())
            eff.append(np.exp(h) / args.clients)
        tel = {"training/loss": np.asarray(history["loss"], np.float32),
               "fairness/participation": np.asarray(part, np.float32),
               "fairness/eff_participation": np.asarray(eff, np.float32)}
        ents = history["bias_entropy"]
        if any(e is not None for e in ents):
            tel["selection/ent_mean"] = np.asarray(
                [np.nan if e is None else float(np.mean(e)) for e in ents],
                np.float32)
        write_run(args.telemetry, tel,
                  meta={"driver": "launch.train", "arch": cfg.name,
                        "selector": args.selector, "rounds": args.rounds,
                        "clients": args.clients})
        print(f"wrote telemetry {args.telemetry}", flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps(history, indent=1))
    print("done. final loss:", history["loss"][-1])


if __name__ == "__main__":
    main()

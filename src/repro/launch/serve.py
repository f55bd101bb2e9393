"""Batched serving driver: prefill a prompt batch, then decode tokens.

Exercises the real prefill/decode path (KV/state caches, greedy sampling)
on live devices — reduced configs on this CPU rig, full configs on TPU.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, get_arch
from repro.launch.compile_cache import setup_compile_cache
from repro.models import model as M


def build_prompt_batch(cfg, B, S, key):
    if cfg.is_encoder_decoder:
        return {
            "embeds": jax.random.normal(key, (B, cfg.encoder_seq_len, cfg.d_model),
                                        jnp.float32) * 0.02,
            "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        }
    if cfg.embedding_inputs:
        return {"embeds": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.float32) * 0.02}
    return {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}


def splice_cache(full, prefill):
    """Copy prefill KV into the (longer) serving cache, preserving states."""
    def one(dst, src):
        if dst.shape == src.shape:
            return src.astype(dst.dtype)
        sl = tuple(slice(0, s) for s in src.shape)
        return dst.at[sl].set(src.astype(dst.dtype))
    return jax.tree_util.tree_map(one, full, prefill)


def main(argv=None):
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "mlp":
        raise SystemExit("dwfl-paper is a classifier; nothing to decode")

    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg)
    B, S, G = args.batch, args.prompt_len, args.gen
    batch = build_prompt_batch(cfg, B, S, key)

    prefill = jax.jit(lambda p, b: M.prefill(p, b, cfg))
    decode = jax.jit(lambda p, b, c, i: M.decode_step(p, b, c, i, cfg))

    t0 = time.time()
    logits, pf_cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    print(f"[serve] prefill {B}x{S}: {t_prefill*1e3:.1f} ms "
          f"({B*S/t_prefill:.0f} tok/s)")

    cache = M.init_cache(cfg, B, S + G)
    cache = splice_cache(cache, pf_cache)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]

    t0 = time.time()
    for i in range(G - 1):
        logits, cache = decode(params, {"tokens": tok}, cache, S + i)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    tok.block_until_ready()
    t_dec = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"[serve] decode {G-1} steps: {t_dec*1e3:.1f} ms "
          f"({B*(G-1)/max(t_dec,1e-9):.0f} tok/s)")
    print(f"[serve] sample output ids[0]: {np.asarray(toks[0])[:16]}")
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    print("[serve] OK")


if __name__ == "__main__":
    main()

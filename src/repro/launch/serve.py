"""Serving driver: batched generation through the Engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --batch 4 --prompt-len 16 --new 32
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCHS, get_arch, reduced
from repro.models import model as M
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.engine import Engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    spec = get_arch(args.arch)
    if args.reduced:
        spec = reduced(spec)
    if spec.frontend != "tokens":
        raise SystemExit(f"{args.arch} uses an embeddings frontend; "
                         "drive it via repro.models.model.prefill/decode_step "
                         "(see tests/test_perf_features.py)")
    params = M.init_params(jax.random.PRNGKey(args.seed), spec)
    eng = Engine(spec, params, max_len=args.prompt_len + args.new)
    prompts = np.random.default_rng(args.seed).integers(
        0, spec.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    out, stats = eng.generate(prompts, max_new=args.new,
                              temperature=args.temperature, seed=args.seed)
    print(f"[serve] prefill {stats.prefill_s*1e3:.0f} ms | "
          f"decode {stats.decode_tok_per_s:.1f} tok/s | {stats.tokens_out} tokens")
    for i, row in enumerate(out[:4]):
        print(f"  request {i}: {row.tolist()[:16]}{'...' if args.new > 16 else ''}")


if __name__ == "__main__":
    main()

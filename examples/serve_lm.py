"""Serve a small LM with continuously-batched requests (reduced llama
config on CPU; the same engine drives the full configs on a pod).

  PYTHONPATH=src python examples/serve_lm.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro import models as zoo
from repro.configs import get_smoke_config
from repro.serve import Request, ServeEngine


def main():
    print("== batched LM serving (continuous batching) ==")
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"),
                              dtype=jnp.float32)
    params = zoo.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, slots=4, max_len=96)

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for uid in range(12):
        prompt = rng.integers(1, cfg.vocab, size=int(rng.integers(4, 20)))
        eng.submit(Request(uid=uid, prompt=prompt.astype(np.int32),
                           max_new_tokens=12, eos_id=-1))
    stats = eng.run()
    dt = time.perf_counter() - t0
    print(f"completed {stats.completed} requests in {stats.ticks} decode "
          f"ticks ({stats.prefills} prefills), "
          f"{stats.generated_tokens} tokens in {dt:.2f}s "
          f"({stats.generated_tokens / dt:.1f} tok/s on CPU)")


if __name__ == "__main__":
    compile_cache.enable()
    main()

"""Serving demo: continuous batching with the paper's batch algorithms.

The paper's trade-off (dispatch overhead Θ vs wasteful work from over-
large batches) maps 1:1 onto LLM serving (compile/dispatch per batch vs
padding waste).  This example schedules a bursty request log with
PERIODIC and GREEDYSETSPLIT-MIN, compares padded-token waste, and runs
the winning schedule through a reduced model.

Run:  PYTHONPATH=src python examples/serve_llm.py
"""
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS
from repro.models import transformer as T
from repro.serve import batcher
from repro.serve.engine import ServeEngine

enable_compile_cache()

rng = np.random.default_rng(0)
requests = [batcher.Request(i, list(rng.integers(1, 60,
                                                 rng.integers(3, 48))),
                            max_new_tokens=8) for i in range(64)]
print(f"{len(requests)} requests, prompt lengths "
      f"{min(r.prompt_len for r in requests)}–"
      f"{max(r.prompt_len for r in requests)}")

for alg, kw in [("periodic", {"s": 8}), ("periodic", {"s": 32}),
                ("greedysetsplit-min", {"bound": 4}),
                ("setsplit-max", {"max_size": 16})]:
    batches = batcher.plan_batches(requests, alg, **kw)
    waste = batcher.padded_tokens(requests, batches)
    print(f"  {alg:20s} {kw}: {len(batches):3d} batches, "
          f"{waste:6d} padded tokens")

s_star, table = batcher.pick_batch_size(requests, theta_seconds=0.05,
                                        tokens_per_second=20_000)
print(f"§8-style model picks s = {s_star} "
      f"(predicted {table[s_star]:.2f}s)")

print("executing the chosen schedule on a reduced starcoder2-3b ...")
cfg = ARCHS["starcoder2-3b"].reduced()
engine = ServeEngine(cfg, T.init_params(cfg, jax.random.PRNGKey(0)),
                     max_len=256)
batches = batcher.plan_batches(requests, "periodic", s=s_star)
t0 = time.perf_counter()
done = 0
for batch in batches:
    prompts = [requests[i].prompt for i in batch]
    outs = engine.generate(prompts, max_new_tokens=8)
    done += len(outs)
print(f"served {done} requests in {time.perf_counter() - t0:.1f}s "
      f"({len(batches)} batches)")

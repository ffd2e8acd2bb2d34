"""End-to-end online GLM serving demo (``make serve-demo``).

Walks the whole inference plane at laptop shapes (docs/serving.md):

  1. train a logistic model on a sparse synthetic with the streaming
     solver and publish it to a model registry;
  2. serve a stream of scoring requests through the micro-batching
     scheduler (one compiled ELL matvec per tick);
  3. new samples arrive -> append them to the shard store and refit
     **warm-started** at the served weights;
  4. the scheduler hot-swaps the new version between ticks and keeps
     serving — traffic never pauses.

Run with  PYTHONPATH=src python examples/glm_serve_demo.py
"""
import os
import tempfile

import numpy as np

from repro.core import DiscoConfig, DiscoSolver
from repro.data.sparse import CSRMatrix, make_sparse_glm_data
from repro.data.store import ShardStore
from repro.glm_serve import (MicroBatchScheduler, ModelRegistry,
                             RefitLoop, ScoreRequest, ScoringEngine)
from repro.kernels.ops import ref_kernels_off_tpu

ref_kernels_off_tpu()   # the fast jnp path off the chip

D, N, CHUNK, BATCH = 64, 512, 64, 16

cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-3,
                  tau=32, max_outer=20, grad_tol=1e-6, pcg_rel_tol=0.01,
                  ell_block_d=8, ell_block_n=8, partition_block=CHUNK,
                  stream_chunk_size=CHUNK)

X, y, _ = make_sparse_glm_data(d=D, n=N, density=0.08, seed=0)
Xd = X.todense()
n0 = N - N // 8                                     # hold out 1/8 as "new"
X0, y0 = CSRMatrix.from_dense(Xd[:, :n0]), y[:n0]
X1, y1 = CSRMatrix.from_dense(Xd[:, n0:]), y[n0:]

with tempfile.TemporaryDirectory() as td:
    # 1. fit (streaming) and publish
    store = ShardStore.from_csr(X0, y0, os.path.join(td, "store"),
                                axis="samples", chunk_size=CHUNK)
    result = DiscoSolver.from_store(store, cfg).fit()
    registry = ModelRegistry(os.path.join(td, "registry"))
    v1 = registry.publish(result, cfg)
    print(f"fit: {len(result.history)} Newton iters, "
          f"converged={result.converged} -> published v{v1}")

    # 2. serve a request stream through the micro-batching scheduler
    engine = ScoringEngine(registry, batch=BATCH)
    sched = MicroBatchScheduler(engine)
    rng = np.random.default_rng(1)
    cols = rng.choice(N, size=48, replace=False)
    rids = [sched.submit(ScoreRequest.from_dense(Xd[:, j]))
            for j in cols]
    sched.run_until_done()
    s = sched.stats
    print(f"served {s.completed} requests in {s.ticks} ticks "
          f"(p50 {s.p50_s * 1e3:.2f} ms, p99 {s.p99_s * 1e3:.2f} ms)")
    probs = engine.predict_proba(
        [ScoreRequest.from_dense(Xd[:, j]) for j in cols[:4]])
    print("sample P(y=+1):", np.round(probs, 3))

    # 3. new data arrives -> warm refit
    loop = RefitLoop(registry, store, cfg)
    loop.ingest(X1, y1)
    v2, warm = loop.refit(warm=True)
    print(f"ingested {X1.shape[1]} samples; warm refit took "
          f"{len(warm.history)} Newton iters -> published v{v2}")

    # 4. the scheduler hot-swaps between ticks, traffic continues
    for j in cols[:8]:
        sched.submit(ScoreRequest.from_dense(Xd[:, j]))
    sched.run_until_done()
    print(f"hot-swapped to v{engine.version} mid-stream; served "
          f"{sched.stats.completed} total requests, "
          f"{engine.reloads} reload(s), 0 pauses")

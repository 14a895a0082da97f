"""Serving CLI: drive the :mod:`repro.serve` SpMV engine (or the LM smoke).

Default mode is a thin CLI over :class:`repro.serve.ServeEngine` — the real
serving path the ROADMAP asked for: it registers a small matrix fleet
(regular grid Laplacians → CSR-k route, a power-law graph → SELL-C-σ route),
replays a seeded random request stream through the engine's continuous
batching + operator cache, drains, verifies a sample against direct
``prepare(A)(x)`` calls, and prints the engine's stats snapshot plus every
``serve.*`` registry record.

SpMV serving example:
  PYTHONPATH=src python -m repro.launch.serve --requests 32 --max-batch 8

The pre-engine single-shot LM generation smoke (one prefill + greedy decode
steps through the KV-cache path, timed through the registry) is kept behind
``--arch``:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import get_registry


def _powerlaw(m: int, scale: float = 6.0, seed: int = 3):
    """Power-law nnz/row CSR matrix — the canonical irregular workload
    (same construction as benchmarks/format_select.py, inlined so the CLI
    never imports the benchmark tree)."""
    from repro.sparse import COOMatrix, csr_from_coo

    rng = np.random.default_rng(seed)
    lengths = np.minimum((rng.pareto(1.0, m) * scale + 1).astype(int), m)
    rows = np.repeat(np.arange(m), lengths)
    cols = np.concatenate([rng.choice(m, size=L, replace=False) for L in lengths])
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    return csr_from_coo(COOMatrix(
        jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
        jnp.asarray(vals), (m, m),
    ))


def run_spmv_serve(args) -> None:
    """Replay a seeded request stream through the serving engine."""
    from repro.configs.spmv_suite import grid_laplacian_2d
    from repro.core.spmv import prepare
    from repro.serve import ServeEngine

    side = max(int(args.scale ** 0.5), 8)
    matrices = {
        "grid_a": grid_laplacian_2d(side, side),
        "grid_b": grid_laplacian_2d(side + 2, side + 2),
        "powerlaw": _powerlaw(max(args.scale, 256)),
    }
    eng = ServeEngine(
        max_batch=args.max_batch,
        max_wait=args.max_wait_ms / 1e3,
        cache_bytes=args.cache_mb * (1 << 20) if args.cache_mb else None,
        device="tpu_v5e",
        format="auto",
    )
    for mid, A in matrices.items():
        fp = eng.add_matrix(mid, A)
        print(f"registered {mid}: {A.shape[0]}x{A.shape[1]} "
              f"nnz={A.nnz} fingerprint={fp[:12]}…")

    rng = np.random.default_rng(args.seed)
    mids = list(matrices)
    futs = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        mid = mids[rng.integers(len(mids))]
        n = matrices[mid].n
        width = int(rng.integers(1, 4))
        shape = (n,) if width == 1 else (n, width)
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        futs.append((mid, x, eng.submit(mid, x)))
        if rng.random() < 0.5:
            eng.step()
    eng.drain()
    wall = time.perf_counter() - t0

    # spot-check the bit-for-bit contract against direct prepares (same
    # fixed launch width as the engine's operators — see docs/serving.md)
    for mid, x, fut in futs[:: max(len(futs) // 4, 1)]:
        direct = prepare(matrices[mid], device="tpu_v5e", format="auto",
                         spmm_width=args.max_batch)
        assert np.array_equal(np.asarray(fut.result()),
                              np.asarray(direct(x))), mid
    print(f"\nserved {len(futs)} requests in {wall:.2f}s "
          f"({len(futs) / max(wall, 1e-9):.1f} req/s), "
          f"sample verified bit-identical to direct prepare(A)(x)")
    for k, v in sorted(eng.stats.snapshot().items()):
        print(f"  {k} = {v:.3f}")
    print(f"  cache: hits={eng.cache.hits} misses={eng.cache.misses} "
          f"prepares={eng.cache.prepares} evictions={eng.cache.evictions} "
          f"bytes={eng.cache.bytes_in_use}")
    for r in get_registry().records():
        if r["section"] == "serve" and not r["name"].startswith(
            ("queue_depth.", "latency_ms.", "batch_cols.")
        ):
            print(f"# obs {r['section']}.{r['name']} = "
                  f"{r['value']:.3f} {r['unit']}")


def run_lm_smoke(args) -> None:
    """Single-shot generation smoke: one prefill + greedy decode steps."""
    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch import steps as STEPS
    from repro.models import transformer as TF

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encdec or cfg.frontend is not None:
        raise SystemExit(f"{args.arch}: use examples for frontend archs")
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(0)
    B, P, G = args.batch, args.prompt_len, args.gen
    max_len = P + G

    with mesh:
        params = TF.init_params(key, cfg)
        prompts = jax.random.randint(key, (B, P), 0, cfg.vocab)
        cache = TF.init_cache(cfg, B, max_len)
        decode_step = jax.jit(STEPS.make_decode_step(cfg, mesh), donate_argnums=(1,))

        reg = get_registry()
        # prefill through the cache path (writes K/V for the prompt)
        t0 = time.time()
        with reg.timer("serve", "prefill"):
            logits, cache, _ = TF.forward(
                params, prompts, cfg, cache=cache, cache_index=jnp.zeros((), jnp.int32)
            )
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            jax.block_until_ready(tok)
        t_prefill = time.time() - t0

        out = [tok]
        t0 = time.time()
        for i in range(G - 1):
            t_step = time.perf_counter()
            logits, cache = decode_step(
                params, cache, tok, jnp.asarray(P + i, jnp.int32)
            )
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            if reg.enabled:
                # per-step timing needs a sync point; only pay it when
                # telemetry is on (disabled runs keep async dispatch)
                jax.block_until_ready(tok)
                reg.observe("serve", "decode_step_ms",
                            (time.perf_counter() - t_step) * 1e3, unit="ms")
            out.append(tok)
        t_decode = time.time() - t0
        reg.gauge("serve", "tokens_per_s",
                  (G - 1) * B / max(t_decode, 1e-9), unit="scalar")

    gen = jnp.concatenate(out, axis=1)
    print(f"prefill {B}x{P}: {t_prefill*1e3:.1f} ms")
    print(f"decode {G-1} steps: {t_decode*1e3:.1f} ms "
          f"({(G-1)*B/max(t_decode,1e-9):.1f} tok/s)")
    print("sample tokens:", gen[0, :16].tolist())
    for r in get_registry().records():
        if r["section"] == "serve":
            print(f"# obs {r['section']}.{r['name']} = {r['value']:.3f} {r['unit']}")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="SpMV serving engine CLI (default) or LM generation "
                    "smoke (--arch). See docs/serving.md.",
    )
    # SpMV serving mode
    ap.add_argument("--requests", type=int, default=32,
                    help="number of requests to replay through the engine")
    ap.add_argument("--scale", type=int, default=576,
                    help="approximate matrix rows (sizes the fleet)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="column budget per coalesced dispatch")
    ap.add_argument("--max-wait-ms", type=float, default=0.0,
                    help="partial-batch wait before dispatching anyway")
    ap.add_argument("--cache-mb", type=float, default=0.0,
                    help="operator-cache byte budget in MiB (0 = unbounded)")
    ap.add_argument("--seed", type=int, default=0)
    # LM smoke mode (pre-engine harness, kept working)
    ap.add_argument("--arch", default=None,
                    help="run the single-shot LM generation smoke instead")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    from repro.util.platform import configure_compile_cache

    configure_compile_cache()
    if args.arch is not None:
        run_lm_smoke(args)
    else:
        run_spmv_serve(args)


if __name__ == "__main__":
    main()

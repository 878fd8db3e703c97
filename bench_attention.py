"""Attention micro-bench: what one attention module costs the model,
forward + backward, on the chip.

The transformer's projections emit ``[B, S, H*D]`` and its output
projection reads ``[B, S, H*D]``, so every path is timed FROM and TO that
layout: the ``[B, H, S, D]`` kernels and the composed XLA path pay their
head transposes inside the timed region, as they do in the model.

Paths (``ops/attention_ops.py``):
  * ``packed``   the packed single-pass kernels (``ops/attention_packed``);
  * ``bhsd``     transposes + the streaming ``[B, H, S, D]`` Pallas
                 kernels (at every length since PR 59);
  * ``composed`` transposes + plain XLA (``_reference_attention``).

Shapes: Transformer-base heads (H 8, D 64), bf16, 32k tokens at S 512 /
1024 and 64k at S 128 / 256 (the long training cell's B32 x S1024 and the
short cells' B256 x S256 among them; B512 x S128 is the row that set
``attention_packed.MIN_S``), each causal and not; S 2048 / 4096 for the
streaming kernels.  DEVICE time per iteration, read from an xplane trace
of one jitted ``lax.scan`` of ITERS grad steps under ``jax.named_scope``
(``profiler.measure_device_seconds``), median of the trials.

``--blocks R:L[,R:L...]`` adds rows of the packed path at other blockings
(query rows : feature lanes a program), which is how
``attention_packed.ROWS`` / ``CAUSAL_ROWS`` / ``LANE_BLOCK`` /
``CAUSAL_WIDE_MAX_S`` were chosen.

Writes ``BENCH_ATTENTION.md`` and prints one JSON line per row.  Needs
the chip: ``chiprun -- python bench_attention.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ITERS = 10
HEADS, DIM = 8, 64
SHAPES = ((32, 1024), (64, 512), (256, 256), (512, 128))       # (B, S)
LONG_SHAPES = ((16, 2048), (8, 4096))              # streaming kernels
PATHS = ("packed", "bhsd", "composed")


def time_path(path, B, S, causal, blocks=None):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    from paddle_tpu.ops import attention_packed as P
    from paddle_tpu.profiler import measure_device_seconds

    shape = (B, S, HEADS * DIM)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape, jnp.bfloat16)
               for i in range(3))
    k_mask = jnp.ones((B, S), jnp.bfloat16)
    scale = DIM ** -0.5
    scope = "attn_bench_iter"
    was = (P.ROWS, P.CAUSAL_ROWS, P.LANE_BLOCK, P.CAUSAL_WIDE_MAX_S)
    if blocks is not None:
        P.ROWS = P.CAUSAL_ROWS = blocks[0]
        P.LANE_BLOCK, P.CAUSAL_WIDE_MAX_S = blocks[1], P.MAX_S

    def loss(q, k, v):
        if path == "packed":
            out = A.fused_attention(q, k, v, k_mask, causal, scale, True,
                                    HEADS)
        else:
            out = A._pack_heads(A.fused_attention(
                *(A._unpack_heads(x, HEADS) for x in (q, k, v)), k_mask,
                causal, scale, path == "bhsd"))
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
            return qq + 0.0 * (g[0] + g[1] + g[2]), g[0][0, 0, 0]
        _, ys = jax.lax.scan(body, q, jnp.arange(ITERS, dtype=jnp.int32))
        return ys[-1]

    try:
        np.asarray(many(q, k, v))  # compile + settle
        trials = []
        for _ in range(int(os.environ.get("PADDLE_TPU_BENCH_TRIALS", "3"))):
            dev_s = measure_device_seconds(
                lambda: np.asarray(many(q, k, v)), scope=scope)
            trials.append(dev_s / ITERS * 1e3)
    finally:
        P.ROWS, P.CAUSAL_ROWS, P.LANE_BLOCK, P.CAUSAL_WIDE_MAX_S = was
    return float(np.median(trials)), trials


def timed(path, B, S, causal, blocks=None):
    try:
        return time_path(path, B, S, causal, blocks)
    except Exception as e:  # composed OOMs once [B,H,S,S] f32 is too big
        if "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower():
            return None, []
        raise


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="",
                    help="extra packed blockings, rows:lanes[,rows:lanes]")
    ap.add_argument("--no-long", action="store_true",
                    help="skip the S 2048 / 4096 rows")
    args = ap.parse_args(argv)
    extra = [tuple(int(x) for x in b.split(":"))
             for b in args.blocks.split(",") if b]

    rows = []

    def add(path, B, S, causal, blocks=None):
        ms, trials = timed(path, B, S, causal, blocks)
        row = {"path": path, "B": B, "S": S, "causal": causal,
               "blocks": list(blocks) if blocks else None,
               "ms": round(ms, 3) if ms is not None else None}
        rows.append(row)
        print(json.dumps(row), flush=True)
        print(f"#   trials {['%.3f' % t for t in trials]}", file=sys.stderr)

    for B, S in SHAPES:
        for causal in (False, True):
            for path in PATHS:
                add(path, B, S, causal)
            for blocks in extra:
                if S % blocks[0] == 0:
                    add("packed", B, S, causal, blocks)
    if not args.no_long:
        for B, S in LONG_SHAPES:
            for path in ("bhsd", "composed"):
                add(path, B, S, True)

    write_markdown(rows)


def write_markdown(rows):
    def cell(path, B, S, causal):
        ms = next((r["ms"] for r in rows if r["blocks"] is None and
                   (r["path"], r["B"], r["S"], r["causal"])
                   == (path, B, S, causal)), "-")
        return "OOM" if ms is None else ms

    lines = [
        "# One attention module, forward + backward (measured)",
        "",
        f"Chip: {_device_kind()}; bf16, H={HEADS}, D={DIM}; operands and "
        "result in the projections' `[B, S, H*D]` layout, so `bhsd` and "
        "`composed` include their head transposes; per-iteration DEVICE "
        "time in ms (xplane, named scope, median of trials; "
        "`bench_attention.py`).",
        "",
        "| B | S | causal | packed | bhsd | composed | bhsd / packed |",
        "|---|---|---|---|---|---|---|",
    ]
    for B, S in SHAPES + LONG_SHAPES:
        for causal in (False, True):
            got = [cell(p, B, S, causal) for p in PATHS]
            if all(x == "-" for x in got):
                continue
            ratio = f"{got[1] / got[0]:.2f}x" if all(
                isinstance(x, float) for x in got[:2]) else "-"
            lines.append(f"| {B} | {S} | {causal} | {got[0]} | {got[1]} | "
                         f"{got[2]} | {ratio} |")
    tuned = [r for r in rows if r["blocks"]]
    if tuned:
        lines += ["", "Packed path at other blockings (query rows : "
                  "feature lanes a program):", "",
                  "| B | S | causal | rows:lanes | ms |", "|---|---|---|---|---|"]
        lines += [f"| {r['B']} | {r['S']} | {r['causal']} | "
                  f"{r['blocks'][0]}:{r['blocks'][1]} | {r['ms']} |"
                  for r in tuned]
    lines += [
        "",
        "`packed` exists from S 128 (`attention_packed.MIN_S`: the "
        "shortest row above, where it beats `composed`) up to S 1024 "
        "(`attention_packed.MAX_S`); above it the op unpacks and `bhsd` is "
        "the streaming flash kernels.  The model "
        "(`models.transformer.multi_head_attention`) takes the fused op "
        "wherever `attention_ops.attention_lowering` says the packed "
        "kernels admit the shapes (equal lengths, a multiple of 128 in "
        "that range, head width 32 / 64 / 128) or the keys are 512 or "
        "longer, and the composed path elsewhere; `PERF.md` has the "
        "in-model numbers.",
        "",
        "Since PR 59 `bhsd` is the streaming kernels at EVERY length.  "
        "Until then its rows at S <= 1024 were the single-pass "
        "`[B, H, S, D]` pair removed there: 4.076 / 2.899 / 6.296 / "
        "6.322 ms at S 1024 / 512 / 256 / 128 (my chip run, PR 39).  "
        "The streaming kernels are slower than that pair on every one "
        "of those rows and, at S 256 and 128, slower than `composed` "
        "(my chip run, PR 59): a shape the packed kernels refuse is "
        "better served by the composed ops under 512 keys, which is "
        "what the model builds there.",
        "The one study that had that pair ahead of the streaming kernels "
        "(a chip run no longer in the tree; forward + backward, causal, "
        "bf16, 64k tokens): S 256 15.6 ms against 18.1 XLA and 18.9 "
        "streaming; S 512 16.2 against 19.9 and 18.0, the streaming "
        "kernels still ahead of XLA: the model's rule of 512 keys.",
    ]
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_ATTENTION.md"), "w") as f:
        f.write("\n".join(lines) + "\n")


def _device_kind():
    import jax
    return getattr(jax.devices()[0], "device_kind", "unknown")


if __name__ == "__main__":
    main()

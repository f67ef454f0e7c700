#!/usr/bin/env python3
"""Kernel census: the Pallas kernels ``chip_smoke.py`` does not reach.

    python3 tools/kernel_census.py        # on the chip, one process

``fused_quant_matmul`` (int8/fp8/fp6), ``gmm`` / ``gmm_quant``,
``lora_matmul``, ``quantization`` and ``block_sparse_attention`` were
all written or last changed without a chip. Each is compiled here once,
at one lane-aligned shape of the ``mistral-7b`` width (hidden 4096, FFN
14336, head_dim 128), run, and compared with its own reference. The
verdict per kernel is "compiles and matches", the relative error that
failed, or the compiler's message. It is a record, not a gate: a
refused kernel does not stop the census or fail it; the verdicts go to
``PERF.md`` and the refusals to ``ROADMAP.md``. A lowered program that
holds no Mosaic custom call — the dispatch fell through to a reference —
is reported as such, never as a pass.

Prints one JSON line per kernel and writes ``chiprun_out/kernel_census.json``.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import mosaic_kernels, rel_err, require_tpu  # noqa: E402

HIDDEN, FFN, HEAD_DIM = 4096, 14336, 128


def cases():
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.quantization.quantization import _quantize_grouped
    from deepspeed_tpu.models.llama import einsum_attention
    from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import dequantize_grouped, quant_matmul
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, gmm_quant, pad_groups_to_tiles
    from deepspeed_tpu.ops.pallas.lora_matmul import lora_delta_pallas, lora_delta_ref
    from deepspeed_tpu.ops.pallas.quantization import dequantize_int8, quantize_int8
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import layout_to_mask

    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, np.float32) * scale, jnp.bfloat16)

    # fused dequant-matmul: a 64-token batch through the [4096, 14336] up projection
    x = normal(64, HIDDEN)
    w = normal(HIDDEN, FFN, scale=HIDDEN ** -0.5)
    for scheme in ("int8", "fp8", "fp6"):
        qw = _quantize_grouped(w, scheme, 512)
        yield (f"fused_quant_matmul[{scheme}]",
               lambda x, v, s, scheme=scheme: quant_matmul(x, v, s, scheme, force_pallas=True),
               lambda x, v, s, scheme=scheme: quant_matmul(x, v, s, scheme, force_pallas=False),
               (x, qw.values, qw.scales), 2e-2)

    # grouped matmul: 1024 rows over 4 experts (one of them empty) of the FFN width
    E, tm = 4, 256
    sizes = jnp.asarray([300, 0, 212, 512], jnp.int32)
    rows = normal(1024, HIDDEN)
    we = normal(E, HIDDEN, FFN, scale=HIDDEN ** -0.5)
    dst, tile_experts, Mp = pad_groups_to_tiles(sizes, 1024, tm)

    def padded(rows):
        return jnp.zeros((Mp, rows.shape[1]), rows.dtype).at[dst].set(rows)

    yield ("gmm",
           lambda rows, we: gmm(padded(rows), we, tile_experts, tm)[dst],
           lambda rows, we: jax.lax.ragged_dot(rows, we, sizes),
           (rows, we), 2e-2)
    for scheme in ("int8", "fp8", "fp6"):
        qe = _quantize_grouped(we, scheme, 512)
        yield (f"gmm_quant[{scheme}]",
               lambda rows, v, s, scheme=scheme: gmm_quant(
                   padded(rows), v, s, tile_experts, scheme, jnp.bfloat16, tm)[dst],
               lambda rows, v, s, scheme=scheme: jax.lax.ragged_dot(
                   rows, dequantize_grouped(v, s, scheme, jnp.bfloat16), sizes),
               (rows, qe.values, qe.scales), 2e-2)

    # segmented LoRA delta: 64 tokens over 4 adapter slots (slot 0 = base), rank 16
    G, r = 4, 16
    slots = jnp.asarray(rng.integers(0, G, 64), jnp.int32)
    a, b = normal(G, HIDDEN, r, scale=HIDDEN ** -0.5), normal(G, r, HIDDEN, scale=r ** -0.5)
    scales = jnp.asarray([0.0, 1.0, 2.0, 0.5], jnp.float32)
    yield ("lora_matmul", lora_delta_pallas, lora_delta_ref, (x, slots, a, b, scales), 2e-2)

    # int8 group quantization of one [4096, 4096] projection, and back
    wq = normal(HIDDEN, HIDDEN)

    def quantize_ref(t):
        g = t.reshape(-1, 2048).astype(jnp.float32)
        absmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
        s = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
        return jnp.clip(jnp.round(g / s), -127, 127).astype(jnp.int8), s[:, 0]

    yield ("quantization.quantize_int8", lambda t: quantize_int8(t, 2048)[:2], quantize_ref,
           (wq,), 1e-2)
    values, qscales = quantize_ref(wq)
    yield ("quantization.dequantize_int8",
           lambda v, s: dequantize_int8(v, s, wq.shape),
           lambda v, s: (v.astype(jnp.float32) * s[:, None]).astype(jnp.bfloat16).reshape(wq.shape),
           (values, qscales), 1e-2)

    # block-sparse attention: BigBird layout, 2048 tokens, 128-wide blocks, head_dim 128
    S, H, block = 2048, 4, 128
    layout = BigBirdSparsityConfig(num_heads=H, block=block, num_random_blocks=1,
                                   num_sliding_window_blocks=3,
                                   num_global_blocks=1).make_layout(S)
    q, k, v = (normal(1, S, H, HEAD_DIM) for _ in range(3))
    mask = layout_to_mask(layout, block, S)[None]
    yield ("block_sparse_attention",
           lambda q, k, v: block_sparse_attention(q, k, v, layout, block),
           lambda q, k, v: einsum_attention(q, k, v, causal=False, mask=mask),
           (q, k, v), 3e-2)


def verdict(fn, ref, args, tol):
    import jax

    lowered = jax.jit(fn).lower(*args)
    kernels = mosaic_kernels(lowered)
    if not kernels:
        return {"verdict": "no Mosaic kernel in the lowered program: the dispatch fell "
                           "through to a reference"}
    t0 = time.perf_counter()
    compiled = lowered.compile()
    out = {"mosaic": kernels, "compile_s": round(time.perf_counter() - t0, 1)}
    err = max(rel_err(g, w) for g, w in zip(jax.tree.leaves(compiled(*args)),
                                            jax.tree.leaves(jax.jit(ref)(*args))))
    out["rel_err"] = float(f"{err:.3e}")
    out["verdict"] = ("compiles and matches" if err < tol
                      else f"compiles; relative error {err:.3e} exceeds {tol}")
    return out


def main():
    devices = require_tpu("kernel_census")
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    report = {"device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                         "count": len(devices)}, "kernels": {}}
    for name, fn, ref, args, tol in cases():
        try:
            result = verdict(fn, ref, args, tol)
        except Exception as e:  # the census records a refusal and goes on to the next kernel
            result = {"verdict": "refused", "error": f"{type(e).__name__}: {e}"[:2000]}
        report["kernels"][name] = result
        print(json.dumps({name: result}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "kernel_census.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()

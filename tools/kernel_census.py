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

The grouped matmul is also timed, a call, beside ``lax.ragged_dot`` at the
four shape classes the benchmark's MoE cells serve (Moonlight's 768- and
3072-row programs over 64 experts of 2048 x 1408, Mixtral's 128- and
1024-row ones over 8 of 4096 x 14336), both directions of the FFN, on a
table of every layer's experts with a traced ``first_group``: ms a call,
GB/s of expert weights, share of the chip's 819 GB/s. That table is what
``ops/grouped_gemm._use_pallas_gmm`` rests on (PERF.md, PR 31).
``--gmm-sweep`` adds other row and column tiles; ``--gmm-only`` skips
the verdicts.

``--flash-window`` (PR 58) times the training block's attention calls alone
at ``mellum2-12b-moe8k-x4``'s shape - one sequence of 8192 x 32 heads x 128 -
the windowed kernels (``flash_window_fwd`` / ``_dkv`` / ``_dq``, a window of
1024) beside the causal ones, forward and forward + backward, from a loop
inside one program: ms a call, the block pairs the grid visits (15 of 36) and
the share of the bf16 peak of the operations the band or the triangle needs
(~1.5 min on one chip, ``chiprun_out/flash_window_census.json``).

``--moe-rows`` (PR 59) times the two row kernels of the training exchange
(``ops/pallas/moe_rows.py``: ``gather_rows`` / ``gather_sum_rows``) alone at
that cell's shape - 32768 tokens of 2304 in bf16, one pass's layout of 102400
slots (98304 + 16 experts' padding), 8 picks a token of which ~65536 are held
- each from a loop inside one program, with the part of each that lays the
source out a row at a time (``moe_rows_pack``) left out, at three block sizes, and
beside them the XLA forms they replace: ``jnp.take`` of the tokens' rows and
the scatter into the layout, the gather back and the sorted ``segment_sum``.
ms a call and GB/s of useful rows (held rows x 4608 bytes, read and written
once), and that kernel and ``jnp`` form agree (~2 min,
``chiprun_out/moe_rows_census.json``).

``--paged`` times ``paged_decode_attention`` instead, at the shape classes
the two key-value cells serve (8 KV heads x 128, 16-row bf16 blocks, a
traced layer index): 64 and 128 decode rows at contexts 128-1536, chat's
128-row program with a quarter of its rows live, and one 512-token prompt
chunk at context 0 and at 512 - with 1, 2, 4, 8, 16 and 32 blocks a tile, and,
where ``--paged-parent DIR`` (default ``_checkout/parent``) holds a checkout
of an older commit, that commit's kernel beside them. ms a call, GB/s of
the KV rows the tokens attend to, share of 819. ``paged_attention.tile_blocks``
rests on this table (PERF.md, PR 33). ``--paged64`` is the same at
``lfm2-24b-rag``'s shape: 32 query / 8 key-value heads **of 64** (a pair of
heads a 128-lane slice), 64-row blocks, 64 decode rows at contexts 1024-8192
and a 512-row chunk at three depths (~1 min; PERF.md, PR 41).

``--paged1`` times the call of the stack that runs several times (PR 54,
``ouro-2.6b-mathqa``): **16 query heads over 16 key-value heads of 128, a query
group of one**, 16-row blocks, a pool 192 layers deep, 16 rows of which 12 are
live at contexts 100-448 - all 192 layers' calls from a loop inside one program
(a call is ~20 us: from the chip's host its dispatch would be all that is
timed), with 1-32 blocks a tile, and the same rows at a group of four (4
key-value heads) beside it: ms a call, GB/s of the attended rows
(:func:`paged_bytes`), share of 819 (~1 min, ``chiprun_out/paged1_census.json``).

``--chunk`` times what a **query tile** does to it (PERF.md, PR 42), at both
shapes: a 512-row prompt chunk at contexts 0 / 512 / 2048 / 7680 (0 / 512 at
16-row blocks, whose table holds 1536 positions), 64 decode rows alone, a
mixed step as ``lfm2-24b-rag`` and chat pack one (decode rows, then a chunk)
and a verify program's 4 and 2 rows a sequence - the parent checkout's kernel,
this tree's with every row an item (``tiles=None``), with the tiles
``query_tiles`` lays, and with ``QUERY_TILE`` at other values. ms a call, GB/s
and share of 819 in least bytes: a sequence's context once a call, whatever
rows of it the call holds. Then a **selection's** calls, which take no tiles
and must cost what the parent's cost (``minicpm-sala-longdoc``'s shape: a row
a (token, key-value head) over a pool of one head a layer, 64-row blocks of
128 values, 64 selected blocks a row; 1024 rows as a 512-token program holds
them, 48 as a burst step does), parent beside this tree's (~3 min).

``--window`` times the **windowed** call (PR 52: ``paged_decode_attention(
window=512)`` over ``paged_attention.window_tables``' table of the 9-10 blocks a
row's or a query tile's window touches) at ``laguna-xs2-repochat``'s shape - 64
query heads over 8 key-value heads of 128, 64-row blocks - for 48 decode rows at
contexts 600-17,000, a 512-row chunk at position 6,000 and a mixed step of both,
against its least bytes (a window a sequence, once a call), and beside it today's
call over the same sequences' whole contexts and over contexts cut to the
window's: the windowed call has to cost what the unwindowed one costs at 512
tokens, not at the sequence's (~1 min, ``chiprun_out/window_census.json``).

``--share`` times one expert layer's tail behind an **expert share** (PR 53:
``ops/grouped_gemm.expert_share_ffn``) at the four share cells' shapes - a
512-token mixed step and the burst's rows of ``longcat-flash-topics`` (16 of
768 columns, 12 picks), ``laguna-xs2-repochat`` (32 of 256, 8),
``solar-open2-reason`` (40 of 320, 8) and ``nemotron3-super-agents`` (128 of
512, 22) - with every pick a row of the layout (``pass_rows = T k``: the
program before PR 53) and with the held picks compacted into passes of 1, 2
and 4 times the picks a step expects (2 is ``share_pass_rows``' rule), and the
parts alone: the ways to list the held picks and to sum a pass's rows by
token. Every time is a turn of a loop inside one program, the expert stacks
its arguments (~8 min, ``chiprun_out/share_census.json``; PERF.md, PR 53).

``--mla`` times ``paged_mla_decode_attention`` at the shape classes the two
latent cells serve (256-row bf16 blocks of 512 + 128 values, a traced layer
index): Moonlight's 128 decode rows at contexts 1024-4096 under 16 heads,
``longcat-flash-topics``' 256-row decode program with 69 rows of padding at
128-1536 under 64 heads, and its 512-token mixed step (187 decode rows, a
200-token chunk at context 100-300, padding) - the parent checkout's kernel,
then the parts of the pipeline switched on in turn (the next token's first
tile early, reuse, a tile of blocks), then ``n`` and the unit swept around
:func:`paged_mla_attention.mla_tile`'s. ms a call, GB/s in
least bytes (the roofline's: a sequence's context once) and in the bytes the
call's copies move. ``mla_tile`` rests on this table (PERF.md, PR 35).

``--live 0.25,0.5,1.0`` times both paged kernels at those shares of a
program's rows live (the rest padding on the null block, as the engine packs
them: live rows first), at chat's 128-row and Mixtral's 512-row programs and
``longcat-flash-topics``' 256- and 512-row ones: ms a call, the same live rows
alone in a call as wide as they are, and from the two **us a padding row a
layer beside us a live row** - for the kernel, which is told the live rows, and
for the parent checkout's, which is not (PERF.md, PR 37).

``--ssm`` times ``ssm_state_step`` at ``nemotron3-super-agents``' shape (5
layers of 129 slots of 128 x 64 x 128 float32, the layer traced in a scan, the
pool donated) with 32, 64 and 128 live sequences, and at
``granite4-h-small-sessions``' (9 layers of 137 slots, **one group**: tiles of
16 heads inside it; 16, 32 and 48 live; records ``ssm-state-granite-*``): ms a layer, GB/s of the
bytes a step has to move (a live slot once in and once out), share of 819 -
the MXU form, the plain float32 lane sum, the copies alone, and
``xla_ssm_state_step``'s three passes beside them (PERF.md, PR 39; ~1 min).

``--scan`` times ``selective_scan`` (Mamba-1, a decay an element) at
``jamba2-3b-chatloop``'s shape (26 layers of 257 slots of 16 x 5120 float32,
the layer traced in a scan, the pool donated): 256 one-row sequences in a
256-row program (a decode step), and a 512-row chunk of 1, 3 and 8 runs (a
prompt step): ms a layer, us a row, GB/s of the bytes a call has to move (a
live slot once in and once out, a row's operands in and ``y`` out), share of
819, the largest error against ``xla_selective_scan`` (PERF.md, PR 45; ~1 min).

``--kda`` times ``kda_delta_rule`` (the Kimi delta rule: a transition that
rotates as well as decays) at ``solar-open2-reason``'s shape (3 layers of 193
slots of 64 x 128 x 128 float32, the layer traced in a scan, the pool
donated): 192 one-row sequences in a 192-row program (a decode step), a
512-row chunk of 1, 3 and 8 runs (a prompt step) and a mixed step (191
one-row sequences, then a prompt's 295 rows; with the row form alone beside
it), in the form the kernel chooses; then runs of 2, 8, 16, 32, 64, 128 and
512 rows in the row form and in the block form (PR 49: where the two cross),
the block form's with the error of the control, one bfloat16 pass: ms a
layer, us a row and a run, GB/s of the bytes a call has to move (a live slot
once in and once out, a row's operands in and ``o`` out), share of 819, the
largest error against ``xla_kda_delta_rule`` on one layer (PERF.md, PRs 48
and 49; ~2.5 min).

Prints one JSON line per kernel and writes ``chiprun_out/kernel_census.json``.
"""

import functools
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


# (name, rows, groups, layers, d_model, d_ff): a serving program's expert matmuls
GMM_CLASSES = (("moonlight-decode", 768, 64, 8, 2048, 1408),
               ("moonlight-chunk", 3072, 64, 8, 2048, 1408),
               ("mixtral-decode", 128, 8, 4, 4096, 14336),
               ("mixtral-chunk", 1024, 8, 4, 4096, 14336))
HBM_GB_S = 819.0  # benchmark/peaks.json, "TPU v5 lite"


def _ms_a_call(fn, *args, calls=20):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = [fn(*args) for _ in range(calls)]
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e3 / calls


def grouped_matmul_classes(sweep=False):
    """Yields one record a shape class and direction: the kernel as
    ``moe_grouped_mlp`` calls it and ``ragged_dot`` as it did, on the same
    rows and the same table. The bytes are the call's experts' matrices
    once (every group has rows here; the record says so)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    rng = np.random.default_rng(31)
    for name, rows, groups, layers, d_model, d_ff in GMM_CLASSES:
        G, layer = layers * groups, layers - 2
        sizes = np.bincount(rng.integers(0, groups, rows), minlength=groups)
        table_sizes = np.zeros(G, np.int32)
        table_sizes[layer * groups:(layer + 1) * groups] = sizes
        sizes, table_sizes = jnp.asarray(sizes, jnp.int32), jnp.asarray(table_sizes)
        first = jnp.int32(layer * groups)
        for direction, (K, N) in (("up", (d_model, d_ff)), ("down", (d_ff, d_model))):
            table = jax.jit(lambda key: (jax.random.normal(key, (G, K, N), jnp.bfloat16)
                                         * K ** -0.5))(jax.random.PRNGKey(K))
            x = jnp.asarray(rng.standard_normal((rows, K), np.float32), jnp.bfloat16)
            ragged = jax.jit(lambda x, w, s: jax.lax.ragged_dot(
                x, w, s, preferred_element_type=jnp.float32).astype(x.dtype))
            want = ragged(x, table, table_sizes)
            nbytes = groups * K * N * 2
            record = {"rows": rows, "groups": groups, "table_groups": G, "K": K, "N": N,
                      "empty_groups": int((sizes == 0).sum()), "weight_bytes": nbytes,
                      "ragged_dot_ms": _ms_a_call(ragged, x, table, table_sizes)}
            fitted, wide = gm.row_tile(rows, groups, x.dtype), gm.col_tile(K, N, 2)
            tiles = [(fitted, wide)]
            if sweep:
                sub = 16  # bf16 rows a sublane tile
                tiles += [(min(256, max(sub, -(-fitted * m // (2 * sub)) * sub)), wide)
                          for m in (1, 4)]   # the rows a group itself, and four times them
                tiles += [(fitted, t) for t in range(128, N + 1, 128)
                          if N % t == 0 and t != wide and t >= 256 and K * t * 2 <= 40 << 20]
            for tm, tn in dict.fromkeys(tiles):
                def layout(x, sizes, first, tm=tm):
                    dst, te, Mp = gm.pad_groups_to_tiles(sizes, rows, tm)
                    filled = jnp.sum((sizes + tm - 1) // tm).astype(jnp.int32)
                    xp = jnp.zeros((Mp, K), x.dtype).at[dst].set(x)
                    return xp, te, jnp.stack([first, filled]), dst

                try:
                    xp, te, meta, dst = jax.jit(layout)(x, sizes, first)
                    call = jax.jit(lambda xp, w, te, meta, tm=tm, tn=tn: gm._gmm_raw(
                        xp, w, te, meta, tm, tn=tn))
                    if not mosaic_kernels(call.lower(xp, table, te, meta)):
                        raise RuntimeError("no Mosaic kernel in the lowered program")
                    ms = _ms_a_call(call, xp, table, te, meta)
                    err = rel_err(call(xp, table, te, meta)[dst], want)
                    got = {"tm": tm, "tn": tn, "ms": ms, "gb_s": nbytes / ms / 1e6,
                           "hbm_share": 100 * nbytes / ms / 1e6 / HBM_GB_S,
                           "rel_err": float(f"{err:.3e}")}
                except Exception as e:  # a refusal is a record too
                    got = {"tm": tm, "tn": tn, "refused": f"{type(e).__name__}: {e}"[:600]}
                if (tm, tn) == (fitted, wide):
                    record["kernel"] = got
                else:
                    record.setdefault("sweep", []).append(got)
            record["ragged_dot_hbm_share"] = 100 * nbytes / record["ragged_dot_ms"] / 1e6 / HBM_GB_S
            del table, want
            yield f"{name}.{direction}", record


# (name, rows, live rows, (least, most) context of a live row or None for a chunk, chunk start)
PAGED_CLASSES = (("mixtral-decode-64", 64, 64, (128, 1536), None),
                 ("chat-decode-128", 128, 128, (128, 1536), None),
                 ("chat-decode-128-35live", 128, 35, (100, 600), None),
                 ("chunk-512-ctx0", 512, 512, None, 0),
                 ("chunk-512-ctx512", 512, 512, None, 512))
PAGED_TILES = (1, 2, 4, 8, 16, 32)
# ``--paged64``: lfm2-24b-rag's shape (32 query / 8 key-value heads of 64, 64-row blocks)
PAGED64_CLASSES = (("rag-decode-64-30live", 64, 30, (1024, 8192), None),
                   ("rag-decode-64", 64, 64, (1024, 8192), None),
                   ("rag-chunk-512-ctx0", 512, 512, None, 0),
                   ("rag-chunk-512-ctx2048", 512, 512, None, 2048),
                   ("rag-chunk-512-ctx7680", 512, 512, None, 7680))
PAGED64_TILES = (1, 2, 4, 8)


def paged_bytes(ctx_tokens, kv_heads, head_dim, itemsize=2):
    """Least bytes one paged call fetches for rows that attend to
    ``ctx_tokens`` positions in all: a position's keys and values."""
    return ctx_tokens * 2 * kv_heads * head_dim * itemsize


def paged_group1_classes():
    """Yields one record a key-value head count (16: the cell's group of one;
    4: a group of four on the same rows): the paged call at every pool layer
    from a loop inside one program, at each ``n`` of ``PAGED_TILES``."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    H, Dh, bs, L, NB, MB, T, live = 16, HEAD_DIM, 16, 192, 368, 32, 16, 12
    rng = np.random.default_rng(54)
    pos = np.zeros(T, np.int32)
    pos[:live] = np.exp(rng.uniform(np.log(100), np.log(448), live)).astype(int) - 1
    tabs = np.zeros((T, MB), np.int32)
    free = iter(rng.permutation(np.arange(1, NB)))
    for t in range(live):
        need = pos[t] // bs + 1
        tabs[t, :need] = [next(free) for _ in range(need)]
    tabs_d, pos_d = jnp.asarray(tabs), jnp.asarray(pos)
    q = jnp.asarray(rng.standard_normal((T, H, Dh), np.float32), jnp.bfloat16)
    ctx = int((pos[:live] + 1).sum())
    for Hkv in (16, 4):
        pool = jax.jit(lambda key: jax.random.normal(key, (L, NB, bs, Hkv * Dh), jnp.bfloat16))
        kc, vc = pool(jax.random.PRNGKey(1)), pool(jax.random.PRNGKey(2))
        want = jax.jit(pa.xla_paged_attention)(q, kc, vc, tabs_d, pos_d, jnp.int32(L - 2))
        nbytes = paged_bytes(ctx, Hkv, Dh)
        record = {"rows": T, "live_rows": live, "ctx_tokens": ctx, "layers": L,
                  "query_group": H // Hkv, "attended_kv_bytes_a_call": nbytes,
                  "rule_n": pa.tile_blocks(bs, Hkv * Dh * 2, 2, MB)}
        for n in PAGED_TILES:
            def every_layer(q, kc, vc, tabs, pos, n=n):
                def body(layer, acc):
                    return acc + pa._paged_call(q, kc, vc, tabs, pos, layer, n, False).astype(
                        jnp.float32)
                return jax.lax.fori_loop(0, L, body, jnp.zeros(q.shape, jnp.float32))
            try:
                one = jax.jit(lambda *a, n=n: pa._paged_call(*a, n, False))
                err = rel_err(one(q, kc, vc, tabs_d, pos_d, jnp.int32(L - 2))[:live], want[:live])
                ms = _ms_a_call(jax.jit(every_layer), q, kc, vc, tabs_d, pos_d, calls=10) / L
                record[f"n={n}"] = {"ms": ms, "gb_s": nbytes / ms / 1e6,
                                    "hbm_share": 100 * nbytes / ms / 1e6 / HBM_GB_S,
                                    "rel_err": float(f"{err:.3e}")}
            except Exception as e:  # a refusal is a record too
                record[f"n={n}"] = {"refused": f"{type(e).__name__}: {e}"[:600]}
        del kc, vc
        yield f"mathqa-decode-16-12live-kv{Hkv}", record


def _parent_kernel(parent_dir, module):
    """``ops/pallas/<module>.py`` of the checkout under ``parent_dir`` as a
    module of its own, or None where there is no such checkout."""
    import importlib.util

    path = os.path.join(parent_dir, "deepspeed_tpu", "ops", "pallas", module + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("parent_" + module, path)
    parent = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parent)
    return parent


def paged_attention_classes(parent_dir, narrow=False):
    """Yields one record a shape class: the kernel at each ``n`` of
    ``PAGED_TILES`` (the rule's own marked), the parent commit's kernel
    where there is one, each against ``xla_paged_attention`` on every
    eighth token. The bytes are the K and V rows at positions <= the
    token's, once a token: what the token attends to, not the whole
    blocks fetched. ``narrow``: ``PAGED64_CLASSES`` at a head of 64 (a
    pair of key-value heads a 128-lane slice), which a parent from before
    PR 41 refuses."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    parent = _parent_kernel(parent_dir, "paged_attention")

    H, Hkv, Dh, bs, L, NB, MB = 32, 8, HEAD_DIM, 16, 4, 8192, 96
    classes, tiles = PAGED_CLASSES, PAGED_TILES
    if narrow:
        Dh, bs, L, NB, MB = 64, 64, 2, 8705, 136
        classes, tiles = PAGED64_CLASSES, PAGED64_TILES
    rng = np.random.default_rng(33)
    pool = jax.jit(lambda key: jax.random.normal(key, (L, NB, bs, Hkv * Dh), jnp.bfloat16))
    kc, vc = pool(jax.random.PRNGKey(1)), pool(jax.random.PRNGKey(2))
    layer = jnp.int32(L - 2)
    rule = pa.tile_blocks(bs, Hkv * Dh * 2, 2, MB)
    for name, T, live, ctx, chunk_start in classes:
        tabs, pos = np.zeros((T, MB), np.int32), np.zeros(T, np.int32)
        free = iter(rng.permutation(np.arange(1, NB)))
        if ctx is None:  # one sequence's chunk: every token on the same table
            pos[:] = chunk_start + np.arange(T)
            need = -(-(chunk_start + T) // bs)
            tabs[:, :need] = [next(free) for _ in range(need)]
        else:            # live rows first, as the engine packs them; the rest padding
            pos[:live] = np.exp(rng.uniform(np.log(ctx[0]), np.log(ctx[1]), live)).astype(int) - 1
            for t in range(live):
                need = pos[t] // bs + 1
                tabs[t, :need] = [next(free) for _ in range(need)]
        q = jnp.asarray(rng.standard_normal((T, H, Dh), np.float32), jnp.bfloat16)
        tabs_d, pos_d = jnp.asarray(tabs), jnp.asarray(pos)
        some = jnp.arange(0, T, 8)
        want = jax.jit(pa.xla_paged_attention)(q[some], kc, vc, tabs_d[some], pos_d[some], layer)
        nbytes = int((pos[:live].astype(np.int64) + 1).sum()) * 2 * Hkv * Dh * 2
        record = {"rows": T, "live_rows": live, "ctx_tokens": int((pos[:live] + 1).sum()),
                  "attended_kv_bytes": nbytes, "rule_n": rule}

        def timed(fn):
            try:
                call = jax.jit(fn)
                if not mosaic_kernels(call.lower(q, kc, vc, tabs_d, pos_d, layer)):
                    raise RuntimeError("no Mosaic kernel in the lowered program")
                ms = _ms_a_call(call, q, kc, vc, tabs_d, pos_d, layer, calls=100)
                err = rel_err(call(q, kc, vc, tabs_d, pos_d, layer)[some], want)
                return {"ms": ms, "gb_s": nbytes / ms / 1e6,
                        "hbm_share": 100 * nbytes / ms / 1e6 / HBM_GB_S,
                        "rel_err": float(f"{err:.3e}")}
            except Exception as e:  # a refusal is a record too
                return {"refused": f"{type(e).__name__}: {e}"[:600]}

        if parent is not None:
            record["parent"] = timed(lambda *a: parent.paged_decode_attention(*a, interpret=False))
        def at(n):
            if not narrow:
                return lambda *a: pa._paged_call(*a, n, False)

            def paired(q, *rest):
                wide, pick = pa._paired(q, Hkv)
                return pick(pa._paged_call(wide, *rest, n, False, None, head_dim=Dh))
            return paired

        for n in tiles:
            record[f"n={n}"] = timed(at(n))
        yield name, record


# ``--chunk``: (name, head of 64?, rows, decode rows, their (least, most) context,
# the chunks after them: (rows, context before it) each, one sequence each)
CHUNK_CLASSES = (
    ("rag-decode-64", True, 64, 64, (1024, 8192), ()),
    ("rag-chunk-512-ctx0", True, 512, 0, None, ((512, 0),)),
    ("rag-chunk-512-ctx512", True, 512, 0, None, ((512, 512),)),
    ("rag-chunk-512-ctx2048", True, 512, 0, None, ((512, 2048),)),
    ("rag-chunk-512-ctx7680", True, 512, 0, None, ((512, 7680),)),
    ("rag-mixed-31+481-ctx2048", True, 512, 31, (1024, 8192), ((481, 2048),)),
    ("chat-decode-64", False, 64, 64, (128, 1536), ()),
    ("chat-chunk-512-ctx0", False, 512, 0, None, ((512, 0),)),
    ("chat-chunk-512-ctx512", False, 512, 0, None, ((512, 512),)),
    ("chat-mixed-64+448-ctx512", False, 512, 64, (128, 1536), ((448, 512),)),
    ("chat-verify-32x4", False, 128, 0, None, tuple((4, 97 + 41 * i) for i in range(32))),
    ("chat-verify-64x2", False, 128, 0, None, tuple((2, 97 + 20 * i) for i in range(64))),
)
# QUERY_TILE beside the module's own
CHUNK_RULES = (("tq=16", 16), ("tq=64", 64))
# ``selected=True`` calls: (name, rows, selected blocks a row)
SELECTION_CLASSES = (("sala-selection-1024x64", 1024, 64), ("sala-selection-48x64", 48, 64))


def chunk_classes(parent_dir):
    """Yields one record a class of ``CHUNK_CLASSES``: the parent checkout's
    kernel, this tree's with no tiles, with :func:`paged_attention.query_tiles`'
    and with the tile heights of ``CHUNK_RULES``, each against
    ``xla_paged_attention`` on every eighth row; then one a class of
    ``SELECTION_CLASSES``: the parent's and this tree's ``selected=True`` call."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    parent = _parent_kernel(parent_dir, "paged_attention")
    H, Hkv = 32, 8
    rng = np.random.default_rng(42)
    pools = {}
    for name, narrow, T, decode, ctx, chunks in CHUNK_CLASSES:
        Dh, bs, L, NB, MB = (64, 64, 2, 8705, 136) if narrow else (HEAD_DIM, 16, 4, 8192, 96)
        if narrow not in pools:
            pool = jax.jit(lambda key: jax.random.normal(key, (L, NB, bs, Hkv * Dh), jnp.bfloat16))
            pools = {narrow: (pool(jax.random.PRNGKey(1)), pool(jax.random.PRNGKey(2)))}
        kc, vc = pools[narrow]
        layer = jnp.int32(L - 2)
        n_seqs = decode + len(chunks)
        tables = np.zeros((n_seqs + 1, MB), np.int32)
        seq, pos = np.full(T, n_seqs, np.int32), np.zeros(T, np.int32)
        free = iter(rng.permutation(np.arange(1, NB)))
        ends = []
        if decode:   # decode rows first, as the engine packs them
            pos[:decode] = np.exp(rng.uniform(np.log(ctx[0]), np.log(ctx[1]), decode)).astype(int) - 1
            seq[:decode] = np.arange(decode)
            ends += [int(p) + 1 for p in pos[:decode]]
        at = decode
        for i, (rows, before) in enumerate(chunks):
            seq[at:at + rows], pos[at:at + rows] = decode + i, before + np.arange(rows)
            ends.append(before + rows)
            at += rows
        for i, end in enumerate(ends):
            need = -(-end // bs)
            tables[i, :need] = [next(free) for _ in range(need)]
        live = at
        q = jnp.asarray(rng.standard_normal((T, H, Dh), np.float32), jnp.bfloat16)
        tabs_d, pos_d, seq_d = jnp.asarray(tables[seq]), jnp.asarray(pos), jnp.asarray(seq)
        some = jnp.arange(0, live, 8)
        want = jax.jit(pa.xla_paged_attention)(q[some], kc, vc, tabs_d[some], pos_d[some], layer)
        least = sum(ends) * 2 * Hkv * Dh * 2
        record = {"rows": T, "live_rows": live, "seq_ctx_tokens": sum(ends), "least_bytes": least}

        live_d = jnp.int32(live)  # on the device: a Python int is a transfer a call
        args = (q, kc, vc, tabs_d, pos_d, layer, live_d)

        def timed(fn, *tiles):
            try:
                jax.clear_caches()   # the rule is a module constant, not a key of the trace
                call = jax.jit(fn)
                if not mosaic_kernels(call.lower(*args, *tiles)):
                    raise RuntimeError("no Mosaic kernel in the lowered program")
                ms = _ms_a_call(call, *args, *tiles, calls=100)
                err = rel_err(call(*args, *tiles)[some], want)
                return {"ms": ms, "least_gb_s": least / ms / 1e6,
                        "hbm_share": 100 * least / ms / 1e6 / HBM_GB_S,
                        "rel_err": float(f"{err:.3e}")}
            except Exception as e:  # a refusal is a record too
                return {"refused": f"{type(e).__name__}: {e}"[:600]}

        def tiled():
            """The kernel over the tiles of the rule in force, laid once a step
            and not once a layer, so outside the timed call (under 30 us a step
            inside ``lfm2-24b-rag``'s program: PERF.md, PR 42)."""
            jax.clear_caches()
            tiles = jax.jit(lambda seq, pos, live: pa.query_tiles(seq, pos, n_seqs, live, MB))(
                seq_d, pos_d, live_d)
            return timed(lambda *a: pa.paged_decode_attention(*a[:7], a[7:], interpret=False),
                         *tiles)

        if parent is not None:
            record["parent"] = timed(lambda *a: parent.paged_decode_attention(*a, interpret=False))
        record["rows-alone"] = timed(lambda *a: pa.paged_decode_attention(*a, interpret=False))
        rule = pa.QUERY_TILE
        record["rule"] = {"QUERY_TILE": rule,
                          "chunk_rows_tiles": pa.chunk_counts(seq, pos, n_seqs, live)}
        record["tiles"] = tiled()
        for label, tq in CHUNK_RULES:
            pa.QUERY_TILE = tq
            try:
                record[label] = tiled()
            finally:
                pa.QUERY_TILE = rule
        yield name, record

    # a selection's calls: a table a (token, key-value head), nothing shared, no tiles
    G, Dh, bs, NB = 16, HEAD_DIM, 64, 8800
    pool = jax.jit(lambda key: jax.random.normal(key, (1, NB, bs, Dh), jnp.bfloat16))
    kc, vc = pool(jax.random.PRNGKey(1)), pool(jax.random.PRNGKey(2))
    for name, T, W in SELECTION_CLASSES:
        tab = jnp.asarray(np.sort(rng.integers(1, NB, (T, W)), axis=1).astype(np.int32))
        at = jnp.asarray(((W - 1) * bs + rng.integers(0, bs, T)).astype(np.int32))
        q = jnp.asarray(rng.standard_normal((T, G, Dh), np.float32), jnp.bfloat16)
        args = (q, kc, vc, tab, at, jnp.int32(0), jnp.int32(T))
        some = jnp.arange(0, T, 8)
        want = jax.jit(pa.xla_paged_attention)(q[some], kc, vc, tab[some], at[some], args[5])
        least = T * W * bs * Dh * 2 * 2   # a block once a (token, head), keys and values
        record = {"rows": T, "blocks_a_row": W, "least_bytes": least}
        for side, mod in (("parent", parent), ("rows-alone", pa)):
            if mod is None:
                continue
            jax.clear_caches()
            call = jax.jit(lambda *a, mod=mod: mod.paged_decode_attention(
                *a, interpret=False, selected=True))
            ms = min(_ms_a_call(call, *args, calls=50) for _ in range(3))
            err = rel_err(call(*args)[some], want)
            record[side] = {"ms": ms, "least_gb_s": least / ms / 1e6,
                            "hbm_share": 100 * least / ms / 1e6 / HBM_GB_S,
                            "rel_err": float(f"{err:.3e}")}
        yield name, record


# (name, rows, heads, table columns, decode rows, (least, most) context, chunk (start, tokens))
MLA_CLASSES = (("moonlight-decode-128", 128, 16, 18, 128, (1024, 4096), None),
               ("topics-decode-256-69pad", 256, 64, 6, 187, (128, 1536), None),
               ("topics-mixed-512", 512, 64, 6, 187, (128, 1536), (100, 200)))


def mla_variants(n, unit, bs, max_blocks):
    """(label, n, unit, ahead, reuse): the parts in turn, from the parent's
    one block a turn to the rule's (n, unit), then ``n`` and the unit
    swept with the other at the rule's."""
    def tile(m):
        return min(m, max_blocks)

    def fitted(m, u):    # the unit, or the tile where it is no whole number of them
        return u if tile(m) * bs % u == 0 and tile(m) * bs // u <= 8 else tile(m) * bs

    turn = [("one-block-a-turn", 1, bs, False, False), ("+ahead", 1, bs, True, False),
            ("+reuse", 1, bs, True, True), (f"+tile n={n} (rule)", n, unit, True, True)]
    sweep = [(f"n={m}", tile(m), fitted(m, unit), True, True) for m in (2, 3, 4, 6, 8)]
    sweep += [(f"unit={u}", n, fitted(n, u), True, True) for u in (128, 512, n * bs)]
    seen = {v[1:] for v in turn}
    return turn + [v for v in sweep if v[1:] not in seen and not seen.add(v[1:])]


def paged_mla_classes(parent_dir):
    """Yields one record a shape class: the parent commit's kernel where
    there is one, then each of :func:`mla_variants`, each against
    ``xla_paged_mla_attention`` on every eighth token."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_mla_attention as pm

    parent = _parent_kernel(parent_dir, "paged_mla_attention")

    rank, lanes, bs, L, NB = 512, 128, 256, 4, 2048
    row_bytes = (rank + lanes) * 2
    rng = np.random.default_rng(35)
    pool = jax.jit(lambda key, w: jax.random.normal(key, (L, NB, bs, w), jnp.bfloat16),
                   static_argnums=1)
    c_pool, r_pool = pool(jax.random.PRNGKey(1), rank), pool(jax.random.PRNGKey(2), lanes)
    layer = jnp.int32(L - 2)
    for name, T, H, MB, live, ctx, chunk in MLA_CLASSES:
        tabs, pos = np.zeros((T, MB), np.int32), np.zeros(T, np.int32)
        free = iter(rng.permutation(np.arange(1, NB)))
        pos[:live] = np.exp(rng.uniform(np.log(ctx[0]), np.log(ctx[1]), live)).astype(int) - 1
        for t in range(live):   # decode rows first, as the engine packs them
            need = pos[t] // bs + 1
            tabs[t, :need] = [next(free) for _ in range(need)]
        seq_ctx = [int(p) + 1 for p in pos[:live]]
        if chunk:               # then one sequence's chunk; the rest is padding on the null block
            at, length = chunk
            pos[live:live + length] = at + np.arange(length)
            need = -(-(at + length) // bs)
            tabs[live:live + length, :need] = [next(free) for _ in range(need)]
            seq_ctx.append(at + length)
        q = jnp.asarray(rng.standard_normal((T, H, rank + lanes), np.float32) * 0.1, jnp.bfloat16)
        tabs_d, pos_d = jnp.asarray(tabs), jnp.asarray(pos)
        some = jnp.arange(0, T, 8)
        want = jax.jit(pm.xla_paged_mla_attention)(q[some], c_pool, r_pool, tabs_d[some],
                                                   pos_d[some], layer)
        # the roofline's bytes (benchmark/readers/mla_roofline.kernel_bytes, one layer)
        least = 2 * (sum(seq_ctx) * (rank + lanes) + T * H * (rank + lanes) + T * H * rank)
        blocks = np.minimum(pos // bs + 1, MB)
        record = {"rows": T, "heads": H, "table_columns": MB, "ctx_tokens": sum(seq_ctx),
                  "least_bytes": least, "blocks_named": int(blocks.sum()),
                  "rule": list(pm.mla_tile(bs, row_bytes, 2, MB, H))}

        def timed(fn, fetched_rows):
            try:
                call = jax.jit(fn)
                if not mosaic_kernels(call.lower(q, c_pool, r_pool, tabs_d, pos_d, layer)):
                    raise RuntimeError("no Mosaic kernel in the lowered program")
                ms = _ms_a_call(call, q, c_pool, r_pool, tabs_d, pos_d, layer, calls=100)
                err = rel_err(call(q, c_pool, r_pool, tabs_d, pos_d, layer)[some], want)
                fetched = fetched_rows * row_bytes
                return {"ms": ms, "least_gb_s": least / ms / 1e6,
                        "hbm_share": 100 * least / ms / 1e6 / HBM_GB_S,
                        "fetched_over_least": fetched / least, "fetched_gb_s": fetched / ms / 1e6,
                        "rel_err": float(f"{err:.3e}")}
            except Exception as e:  # a refusal is a record too
                return {"refused": f"{type(e).__name__}: {e}"[:600]}

        if parent is not None:
            record["parent"] = timed(lambda *a: parent.paged_mla_decode_attention(
                *a, interpret=False), int(blocks.sum()) * bs)
        n, unit = record["rule"]
        for label, n, unit, ahead, reuse in mla_variants(n, unit, bs, MB):
            fetched = int(pm.fetch_counts(tabs_d, pos_d, bs, n)[1]) if reuse else int(blocks.sum())
            got = timed(lambda *a, n=n, unit=unit, ahead=ahead, reuse=reuse:
                        pm._mla_call(*a, n, unit, False, ahead=ahead, reuse=reuse), fetched * bs)
            record[label] = {"n": n, "unit": unit, **got,
                             "fetch_share": 100.0 * fetched / int(blocks.sum())}
        yield name, record


# (name, kernel, rows, heads, table columns, (least, most) context of a live row)
LIVE_CLASSES = (("chat-decode-128", "kv", 128, 32, 96, (100, 600)),
                ("batch-mixed-512", "kv", 512, 32, 96, (128, 1536)),
                ("topics-decode-256", "mla", 256, 64, 6, (128, 1536)),
                ("topics-mixed-512", "mla", 512, 64, 6, (128, 1536)))
LIVE_LAYERS = 32


def live_rows_sweep(parent_dir, shares):
    """Yields one record a class of ``LIVE_CLASSES``: at each share of the
    rows live, the call's ms, the ms of the live rows alone (a call of
    their own width) and the difference a padding row; the parent
    checkout's kernel, which runs every row, beside it."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_mla_attention as pm

    rng = np.random.default_rng(37)
    L = 4
    pools = {}

    def pool(NB, bs, width, key):
        if (NB, bs, width, key) not in pools:
            pools[NB, bs, width, key] = jax.jit(lambda k: jax.random.normal(
                k, (L, NB, bs, width), jnp.bfloat16))(jax.random.PRNGKey(key))
        return pools[NB, bs, width, key]

    for name, kernel, T, H, MB, ctx in LIVE_CLASSES:
        if kernel == "kv":
            bs, width, NB = 16, HEAD_DIM, 8192
            mine, theirs = pa, _parent_kernel(parent_dir, "paged_attention")
            a, b = pool(NB, bs, 8 * HEAD_DIM, 1), pool(NB, bs, 8 * HEAD_DIM, 2)
            entry = "paged_decode_attention"
        else:
            bs, width, NB = 256, 512 + 128, 2048
            mine, theirs = pm, _parent_kernel(parent_dir, "paged_mla_attention")
            a, b = pool(NB, bs, 512, 1), pool(NB, bs, 128, 2)
            entry = "paged_mla_decode_attention"
        pos_all = np.exp(rng.uniform(np.log(ctx[0]), np.log(ctx[1]), T)).astype(np.int32) - 1
        tabs_all = np.zeros((T, MB), np.int32)
        for t in range(T):      # a block may serve two rows: the reads are what is timed
            need = pos_all[t] // bs + 1
            tabs_all[t, :need] = rng.integers(1, NB, need)
        q = jnp.asarray(rng.standard_normal((T, H, width), np.float32) * 0.1, jnp.bfloat16)
        record = {"rows": T, "heads": H, "table_columns": MB}

        def ms(fn, q, a, b, tabs, pos, *live):
            """ms a call of LIVE_LAYERS calls in one program, a layer of the
            pool each, as a model's scan makes them: a call of 0.1 ms alone
            in a program is timed by its launch."""
            try:
                def layers(q, a, b, tabs, pos, *live):
                    first = fn(q, a, b, tabs, pos, jnp.int32(0), *live)
                    return jax.lax.fori_loop(1, LIVE_LAYERS, lambda i, _: fn(
                        q, a, b, tabs, pos, i % L, *live), first)
                call = jax.jit(layers)
                if not mosaic_kernels(call.lower(q, a, b, tabs, pos, *live)):
                    raise RuntimeError("no Mosaic kernel in the lowered program")
                return _ms_a_call(call, q, a, b, tabs, pos, *live, calls=10) / LIVE_LAYERS
            except Exception as e:  # a refusal is a record too
                return f"{type(e).__name__}: {e}"[:600]

        def per_row(whole, alone, live):
            if isinstance(whole, str) or isinstance(alone, str):
                return {"ms": whole, "live_alone_ms": alone}
            out = {"ms": whole, "live_alone_ms": alone, "us_a_live_row": 1e3 * alone / live}
            if live < T:
                out["us_a_padding_row"] = 1e3 * (whole - alone) / (T - live)
            return out

        for share in shares:
            live = max(1, int(round(share * T)))
            tabs, pos = tabs_all.copy(), pos_all.copy()
            tabs[live:], pos[live:] = 0, 0
            tabs_d, pos_d, n_live = jnp.asarray(tabs), jnp.asarray(pos), jnp.int32(live)
            got = {"live_rows": live}
            alone = (q[:live], a, b, tabs_d[:live], pos_d[:live])
            if theirs is not None:
                run = lambda *x: getattr(theirs, entry)(*x, interpret=False)
                got["parent"] = per_row(ms(run, q, a, b, tabs_d, pos_d), ms(run, *alone), live)
            run = lambda *x: getattr(mine, entry)(*x, interpret=False)
            got["kernel"] = per_row(ms(run, q, a, b, tabs_d, pos_d, n_live),
                                    ms(run, *alone, n_live), live)
            record[f"live={share}"] = got
        yield name, record


# name -> (M layers, slots, heads, P, N, groups), the live sequences timed: nemotron3-super-agents'
# (a tile is a group of 16 heads) and granite4-h-small-sessions' (one group: eight tiles of 16
# heads inside it, 48 live slots beside the prefix cache's 88)
SSM_SHAPES = {"": ((5, 128, 128, 64, 128, 8), (32, 64, 128)),
              "granite-": ((9, 136, 128, 64, 128, 1), (16, 32, 48))}


def ssm_state_classes():
    """Yields one record a shape and a number of live sequences: the state
    step at ``nemotron3-super-agents``' shape (5 ``M`` layers of 128 + 1 slots
    of 128 x 64 x 128 float32 in 8 groups, 129 sequence rows) and at
    ``granite4-h-small-sessions``' (9 layers of 136 + 1 slots, **one group**:
    records ``ssm-state-granite-*``), **all the layers a
    call** with the layer traced inside a ``lax.scan`` as the step
    programs have it and the pool donated: the kernel with each unit, and
    ``xla_ssm_state_step`` beside it. ms a layer, GB/s of the bytes the
    step has to move (``live x 2 x H x P x N x 4``), share of 819."""
    for name, (shape, lives) in SSM_SHAPES.items():
        yield from _ssm_state_shape(name, shape, lives)


def _ssm_state_shape(name, shape, lives):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import ssm_state as ss

    Lm, slots, H, P, N, G = shape
    S = slots + 1
    rng = np.random.default_rng(39)
    fill = jax.jit(lambda key: jax.random.normal(key, (Lm, S, H, P, N), jnp.float32))

    def layers(step):
        def run(pool, *rows):
            def one(pool, layer):
                return step(pool, layer, *rows)
            return jax.lax.scan(one, pool, jnp.arange(Lm, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    for live in lives:
        slot, here, fresh = np.zeros(S, np.int32), np.zeros(S, bool), np.ones(S, bool)
        slot[:live] = rng.permutation(np.arange(1, S))[:live]
        here[:live], fresh[:live] = True, False
        fresh[:live:32] = True                   # a sequence in 32 starts here
        rows = (jnp.asarray(slot), jnp.asarray(fresh), jnp.asarray(here),
                jnp.asarray(rng.standard_normal((S, G, N)), jnp.float32),
                jnp.asarray(rng.standard_normal((S, G, N)), jnp.float32),
                jnp.asarray(rng.uniform(0.9, 1.0, (S, H)), jnp.float32),
                jnp.asarray(rng.standard_normal((S, H, P)) * 0.1, jnp.float32))
        least = live * 2 * H * P * N * 4
        record = {"live": live, "least_bytes_a_layer": least}
        want_pool, want_seen = layers(ss.xla_ssm_state_step)(fill(jax.random.PRNGKey(live)), *rows)
        named = np.asarray(slot[:live:max(live // 8, 1)])      # the slots compared

        def timed(step, calls=20):
            try:
                call = layers(step)
                pool = fill(jax.random.PRNGKey(live))
                pool, seen = call(pool, *rows)
                err = max(rel_err(seen, want_seen), rel_err(pool[:, named], want_pool[:, named]))
                jax.block_until_ready(pool)
                t0 = time.perf_counter()
                for _ in range(calls):
                    pool, seen = call(pool, *rows)
                jax.block_until_ready((pool, seen))
                ms = (time.perf_counter() - t0) * 1e3 / calls / Lm
                return {"ms_a_layer": ms, "gb_s": least / ms / 1e6,
                        "hbm_share": 100 * least / ms / 1e6 / HBM_GB_S,
                        "rel_err": float(f"{err:.3e}")}
            except Exception as e:  # a refusal is a record too
                return {"refused": f"{type(e).__name__}: {e}"[:600]}

        for unit in ("mxu", "vpu", "none"):
            record[unit] = timed(lambda *a, unit=unit: ss.ssm_state_step(*a, unit=unit,
                                                                         interpret=False))
        if live == lives[-1]:
            record["xla"] = timed(ss.xla_ssm_state_step, calls=5)
        yield f"ssm-state-{name}{live}", record


# ``--scan``: jamba2-3b-chatloop's Mamba layers (layers, slots, state columns, channels)
SCAN_SHAPE = (26, 256, 16, 5120)
# (rows of the program, runs, rows a run): a decode step, then a prompt chunk cut into runs
SCAN_STEPS = ((256, 256, 1), (512, 1, 512), (512, 3, 170), (512, 8, 64))


def scan_bytes(slots, rows, N, C):
    """Least bytes through HBM for one Mamba layer of a step: each live
    slot's float32 state once in and once out, each row's ``x`` and
    ``delta`` in and ``y`` out (float32, ``C`` wide) and its ``B`` and ``C``
    (``N`` wide)."""
    return slots * 2 * N * C * 4 + rows * (3 * C + 2 * N) * 4


def selective_scan_classes():
    """Yields one record a step of ``SCAN_STEPS``: the scan at
    ``jamba2-3b-chatloop``'s shape, **all 26 layers a call** with the layer
    traced inside a ``lax.scan`` as the step programs have it and the pool
    donated; a sequence in 16 fresh. ms a layer, us a row, GB/s of
    :func:`scan_bytes`, share of 819, the error against
    ``xla_selective_scan`` over four of the layers."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import selective_scan as ss

    Lm, slots, N, C = SCAN_SHAPE
    S = slots + 1
    rng = np.random.default_rng(45)
    fill = jax.jit(lambda key, Lm: jax.random.normal(key, (Lm, S, N, C), jnp.float32),
                   static_argnums=1)
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, C))

    def layers(step, Lm):
        def run(pool, *rows):
            def one(pool, layer):
                return step(pool, layer, *rows, a)
            return jax.lax.scan(one, pool, jnp.arange(Lm, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    for T, runs, rows_a_run in SCAN_STEPS:
        seq = np.full(T, S - 1, np.int32)
        slot, first, length = (np.zeros(S, np.int32) for _ in range(3))
        fresh = np.ones(S, bool)
        slot[:runs] = rng.permutation(np.arange(1, S))[:runs]
        for s in range(runs):
            first[s], length[s] = s * rows_a_run, rows_a_run
            seq[first[s]:first[s] + rows_a_run] = s
            fresh[s] = s % 16 == 15
        live = runs * rows_a_run
        rows = (jnp.asarray(seq), jnp.asarray(slot), jnp.asarray(first), jnp.asarray(length),
                jnp.asarray(fresh), jnp.asarray(rng.standard_normal((T, C)), jnp.float32),
                jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (T, C))), jnp.float32),
                jnp.asarray(rng.standard_normal((T, N)), jnp.float32),
                jnp.asarray(rng.standard_normal((T, N)), jnp.float32))
        least = scan_bytes(runs, live, N, C)
        record = {"rows": T, "runs": runs, "live_rows": live, "least_bytes_a_layer": least}
        try:
            few = 4
            want_pool, want_y = layers(ss.xla_selective_scan, few)(
                fill(jax.random.PRNGKey(T + runs), few), *rows)
            pool, y = layers(functools.partial(ss.selective_scan, interpret=False), few)(
                fill(jax.random.PRNGKey(T + runs), few), *rows)
            named = np.asarray(slot[:runs:max(runs // 8, 1)])
            err = max(rel_err(y, want_y), rel_err(pool[:, named], want_pool[:, named]))
            call = layers(functools.partial(ss.selective_scan, interpret=False), Lm)
            pool = fill(jax.random.PRNGKey(1), Lm)
            pool, y = call(pool, *rows)
            jax.block_until_ready(pool)
            t0 = time.perf_counter()
            for _ in range(10):
                pool, y = call(pool, *rows)
            jax.block_until_ready((pool, y))
            ms = (time.perf_counter() - t0) * 1e3 / 10 / Lm
            record.update(ms_a_layer=ms, us_a_row=ms * 1e3 / live, gb_s=least / ms / 1e6,
                          hbm_share=100 * least / ms / 1e6 / HBM_GB_S,
                          rel_err=float(f"{err:.3e}"))
        except Exception as e:  # a refusal is a record too
            record["refused"] = f"{type(e).__name__}: {e}"[:1500]
        yield f"selective-scan-{T}x{runs}", record


# ``--kda``: solar-open2-reason's KDA layers (layers, slots, heads, head size)
KDA_SHAPE = (3, 192, 64, 128)
# (rows of the program, runs, rows a run, rows of one more run behind them, the least run
# that takes the block form - None: the kernel's own): a decode step as a burst holds it (the
# row form alone) and as a ``put`` program does; a prompt chunk cut into runs; a mixed step of
# the cell, 191 decode rows and a prompt's 295, with the row form alone beside it; then runs
# of 2 to 512 rows back to back from row 0 in the row form and in the block form, to see
# where the two cross (PR 49)
ROW_FORM, BLOCK_FORM, BURST = 1 << 20, 1, "a burst's step: one row a sequence by construction"
KDA_STEPS = (((192, 192, 1, 0, BURST), (192, 192, 1, 0, None), (512, 1, 512, 0, None),
              (512, 3, 170, 0, None), (512, 8, 64, 0, None), (512, 191, 1, 295, ROW_FORM),
              (512, 191, 1, 295, None))
             + tuple((512, min(8, 512 // n), n, 0, form) for n in (2, 8, 16, 32, 64, 128, 512)
                     for form in (ROW_FORM, BLOCK_FORM)))
KDA_FORM_NAMES = {None: "chosen", ROW_FORM: "rows", BLOCK_FORM: "blocks", BURST: "burst"}


def kda_bytes(slots, rows, H, d):
    """Least bytes through HBM for one KDA layer of a step: each live
    slot's float32 state ``[H, d, d]`` once in and once out, each row's
    ``q``, ``k``, ``v`` and decays in and ``o`` out (float32, ``H d`` wide)
    and its ``beta`` (``H`` wide)."""
    return slots * 2 * H * d * d * 4 + rows * (5 * H * d + H) * 4


def kda_classes():
    """Yields one record a step of ``KDA_STEPS``: the delta rule at
    ``solar-open2-reason``'s shape, **all 3 layers a call** with the layer
    traced inside a ``lax.scan`` as the step programs have it and the pool
    donated; a sequence in 16 fresh. ms a layer, us a row and a run, GB/s
    of :func:`kda_bytes`, share of 819, the error against
    ``xla_kda_delta_rule`` on one layer of a third of the slots (a forced
    block form's also with its products in one bfloat16 pass: the control)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import kda

    Lk, slots, H, d = KDA_SHAPE
    S = slots + 1
    rng = np.random.default_rng(48)
    fill = jax.jit(lambda key, Lk, NS: 0.1 * jax.random.normal(key, (Lk, NS, H, d, d),
                                                               jnp.float32),
                   static_argnums=(1, 2))

    def layers(step, Lk):
        def run(pool, *rows):
            def one(pool, layer):
                return step(pool, layer, *rows)
            return jax.lax.scan(one, pool, jnp.arange(Lk, dtype=jnp.int32))
        return jax.jit(run, donate_argnums=0)

    def step_rows(T, runs, rows_a_run, NS, then=0):
        """``runs`` runs of ``rows_a_run`` rows back to back from row 0, then one of
        ``then`` rows."""
        seq = np.full(T, S - 1, np.int32)
        slot, first, length = (np.zeros(S, np.int32) for _ in range(3))
        fresh = np.ones(S, bool)
        lengths = [rows_a_run] * runs + [then] * bool(then)
        slot[:len(lengths)] = rng.permutation(np.arange(1, NS))[:len(lengths)]
        for s, n in enumerate(lengths):
            first[s], length[s] = sum(lengths[:s]), n
            seq[first[s]:first[s] + n] = s
            fresh[s] = s % 16 == 15
        k = rng.standard_normal((T, H, d))
        return (jnp.asarray(seq), jnp.asarray(slot), jnp.asarray(first), jnp.asarray(length),
                jnp.asarray(fresh), jnp.asarray(rng.standard_normal((T, H, d)) / d, jnp.float32),
                jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True), jnp.float32),
                jnp.asarray(rng.standard_normal((T, H, d)), jnp.float32),
                jnp.asarray(-np.exp(rng.uniform(np.log(1e-3), np.log(1.6), (T, H, d))),
                            jnp.float32),
                jnp.asarray(rng.uniform(0.0, 2.0, (T, H)), jnp.float32))

    def rule(form, **control):
        how = {"one_row_runs": True} if form == BURST else {"min_run": form}
        return lambda *a: kda._delta_call(*a, interpret=False, **how, **control)

    for T, runs, rows_a_run, then, form in KDA_STEPS:
        live = runs * rows_a_run + then
        least = kda_bytes(runs + bool(then), live, H, d)
        record = {"rows": T, "runs": runs, "rows_a_run": rows_a_run, "then": then,
                  "live_rows": live, "form": KDA_FORM_NAMES[form], "least_bytes_a_layer": least}
        try:
            few = min(runs, 64) + 2         # the reference gathers every sequence row's state
            small = step_rows(T, min(runs, 64), rows_a_run, few, then)
            want_pool, want_o = layers(kda.xla_kda_delta_rule, 1)(
                fill(jax.random.PRNGKey(T + runs), 1, few), *small)
            pool, o = layers(rule(form), 1)(fill(jax.random.PRNGKey(T + runs), 1, few), *small)
            err = max(rel_err(o, want_o), rel_err(pool, want_pool))
            if form == BLOCK_FORM:          # the control: the same products in one bfloat16 pass
                pool, o = layers(rule(form, one_pass=True), 1)(
                    fill(jax.random.PRNGKey(T + runs), 1, few), *small)
                record["rel_err_one_bf16_pass"] = float(
                    f"{max(rel_err(o, want_o), rel_err(pool, want_pool)):.3e}")
            rows = step_rows(T, runs, rows_a_run, S, then)
            call = layers(rule(form), Lk)
            pool = fill(jax.random.PRNGKey(1), Lk, S)
            pool, o = call(pool, *rows)
            jax.block_until_ready(pool)
            t0 = time.perf_counter()
            for _ in range(10):
                pool, o = call(pool, *rows)
            jax.block_until_ready((pool, o))
            ms = (time.perf_counter() - t0) * 1e3 / 10 / Lk
            record.update(ms_a_layer=ms, us_a_row=ms * 1e3 / live,
                          us_a_run=ms * 1e3 / (runs + bool(then)), gb_s=least / ms / 1e6,
                          hbm_share=100 * least / ms / 1e6 / HBM_GB_S,
                          rel_err=float(f"{err:.3e}"))
        except Exception as e:  # a refusal is a record too
            record["refused"] = f"{type(e).__name__}: {e}"[:1500]
        yield f"kda-{T}x{runs}x{rows_a_run}+{then}-{KDA_FORM_NAMES[form]}", record


def window_bytes(seq_tokens, row_bytes=4096):
    """Least bytes of a windowed call: the positions its sequences attend to
    (a window a sequence, once a call), keys and values (``benchmark/readers/
    laguna.attention_bytes`` at one layer)."""
    return seq_tokens * row_bytes


def window_classes():
    """``--window``: the windowed call of ``laguna-xs2-repochat``'s window
    layers (``paged_decode_attention(window=512)``: 64 query heads over 8
    key-value heads of 128, 64-row blocks, a table of the 9-10 blocks
    ``paged_attention.window_tables`` cuts out of a sequence's ring) against
    its least bytes - 48 decode rows at contexts 600-17,000, and a 512-row
    prompt chunk at position 6,000 with the step's query tiles - and, beside
    each, the **unwindowed** kernel over the same sequences' whole context
    and over a 512-token context: the windowed call at any context has to
    cost what the unwindowed one costs at 512, not what it costs at the
    sequence's. → (name, record) a class."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    H, Hkv, Dh, bs, W, L, NB, MB, ring = 64, 8, HEAD_DIM, 64, 512, 2, 4609, 272, 17
    rng = np.random.default_rng(52)
    pool = jax.jit(lambda key: jax.random.normal(key, (L, NB, bs, Hkv * Dh), jnp.bfloat16))
    kc, vc = pool(jax.random.PRNGKey(1)), pool(jax.random.PRNGKey(2))
    layer = jnp.int32(1)
    for name, T, decode, chunk in (("decode48", 48, 48, None), ("chunk512", 512, 0, (512, 6000)),
                                   ("mixed", 512, 48, (464, 6000))):
        n_seqs = decode + (chunk is not None)
        seq, pos = np.full(T, n_seqs, np.int32), np.zeros(T, np.int32)
        pos[:decode] = np.exp(rng.uniform(np.log(600), np.log(17000), decode)).astype(int) - 1
        seq[:decode] = np.arange(decode)
        if chunk is not None:
            rows, before = chunk
            seq[decode:decode + rows], pos[decode:decode + rows] = decode, before + np.arange(rows)
        live = decode + (chunk[0] if chunk else 0)
        free = iter(rng.permutation(np.arange(1, NB)))
        tables = np.zeros((n_seqs + 1, MB), np.int32)
        rings = np.zeros((n_seqs + 1, ring), np.int32)
        ends, lows = [], []
        for i in range(n_seqs):
            rows_i = pos[seq == i]
            end, low = int(rows_i.max()) + 1, max(0, int(rows_i.min()) - W + 1)
            ends.append(end), lows.append(low)
            for b in range(-(-end // bs)):
                tables[i, b] = next(free)
                if b >= low // bs:      # the ring holds what the window still needs
                    rings[i, b % ring] = tables[i, b]
        # the same sequences as today's call would see them were their contexts the window's:
        # a table that starts at the block of the lower bound, positions counted from there
        cut, first = np.zeros_like(tables), np.zeros(n_seqs + 1, np.int32)
        for i, low in enumerate(lows):
            b0 = low // bs
            cut[i, :MB - b0], first[i] = tables[i, b0:], b0 * bs
        q = jnp.asarray(rng.standard_normal((T, H, Dh), np.float32), jnp.bfloat16)
        seq_d, pos_d, live_d = jnp.asarray(seq), jnp.asarray(pos), jnp.int32(live)
        tiles = jax.jit(lambda s, p, n: pa.query_tiles(s, p, n_seqs, n, MB))(seq_d, pos_d, live_d) \
            if T % pa.QUERY_TILE == 0 else None
        tab_w, pos_w = jax.jit(lambda r, p: pa.window_tables(
            r, p, W, bs, 1 if tiles is None else pa.QUERY_TILE))(jnp.asarray(rings[seq]), pos_d)
        win_tokens = sum(e - l for e, l in zip(ends, lows))
        some = jnp.arange(0, live, 8)
        want = jax.jit(lambda *a: pa.xla_paged_attention(*a, window=W))(
            q[some], kc, vc, tab_w[some], pos_w[some], layer)

        def timed(least, tab, at, **kw):
            try:
                call = jax.jit(lambda q, kc, vc, tab, at, layer, live, *tiles: (
                    pa.paged_decode_attention(q, kc, vc, tab, at, layer, live, tiles or None,
                                              interpret=False, **kw)))
                args = (q, kc, vc, tab, at, layer, live_d, *(tiles or ()))
                ms = _ms_a_call(call, *args, calls=100)
                out = {"ms": ms, "least_bytes": least, "least_gb_s": least / ms / 1e6,
                       "hbm_share": 100 * least / ms / 1e6 / HBM_GB_S}
                if kw:
                    out["rel_err"] = float(f"{rel_err(call(*args)[some], want):.3e}")
                return out
            except Exception as e:  # a refusal is a record too
                return {"refused": f"{type(e).__name__}: {e}"[:600]}

        row = 2 * Hkv * Dh * 2
        record = {"rows": T, "live_rows": live, "win_seq_tokens": win_tokens,
                  "ctx_seq_tokens": sum(ends), "table_columns": int(tab_w.shape[1]),
                  "windowed": timed(window_bytes(win_tokens, row), tab_w, pos_w, window=W),
                  # the same rows through today's call over their whole contexts ...
                  "unwindowed_whole_context": timed(sum(ends) * row, jnp.asarray(tables[seq]), pos_d),
                  # ... and over a context cut to the window's length (positions 0 .. W - 1 + rows)
                  "unwindowed_at_512": timed(window_bytes(win_tokens, row),
                                             jnp.asarray(cut[seq]), jnp.asarray(pos - first[seq]))}
        yield name, record


# ``--share``: (name, tokens, picks a token, held, routed, zero-compute columns, width the
# experts see, their inner width, gated): the four cells that serve one rank's share
SHARE_CLASSES = (("longcat-mixed-512", 512, 12, 16, 512, 256, 6144, 2048, True),
                 ("longcat-burst-256", 256, 12, 16, 512, 256, 6144, 2048, True),
                 ("laguna-mixed-512", 512, 8, 32, 256, 0, 2048, 512, True),
                 ("laguna-burst-64", 64, 8, 32, 256, 0, 2048, 512, True),
                 ("solar-mixed-512", 512, 8, 40, 320, 0, 4096, 1280, True),
                 ("solar-burst-192", 192, 8, 40, 320, 0, 4096, 1280, True),
                 ("nemotron-mixed-512", 512, 22, 128, 512, 0, 1024, 2688, False),
                 ("nemotron-burst-128", 128, 22, 128, 512, 0, 1024, 2688, False))


def share_classes():
    """Yields one record a shape class: one expert layer's tail behind a
    share (``ops/grouped_gemm.expert_share_ffn`` over a table of two layers'
    experts, the router's picks drawn evenly over its columns) with every
    pick a row of the layout (``pass_rows = T k``: the program before PR 53)
    and with the held picks compacted into passes of 1, 2 and 4 times the
    picks a step expects (2 is the rule's), ms a layer and the passes run; then
    the parts alone at the rule's ``cap``: the ways to list the held picks in
    order and the ways to sum a pass's rows into ``[T, D]`` by token. Every
    time is of ``SHARE_LOOP`` turns of a loop inside one program, each turn's
    input a function of the last turn's result: the device's time, not the
    host's dispatch (0.2-0.5 ms a call on the chip's host)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import grouped_gemm as gg

    rng = np.random.default_rng(53)
    relu2 = lambda v: jnp.square(jax.nn.relu(v))  # noqa: E731
    for name, T, k, held, routed, zero, D, F, gated in SHARE_CLASSES:
        share = gg.ExpertShare(0, held, routed, zero)
        columns = routed + zero
        picks = np.stack([rng.permutation(columns)[:k] for _ in range(T)]).astype(np.int32)
        idx = jnp.asarray(picks)
        vals = jnp.asarray(rng.uniform(0.05, 0.2, (T, k)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((T, D), np.float32), jnp.bfloat16)
        G = 2 * held
        stacks = tuple(None if shape is None else jax.jit(
            lambda key, shape=shape: jax.random.normal(key, (G,) + shape, jnp.bfloat16)
            * shape[0] ** -0.5)(jax.random.PRNGKey(i))
            for i, shape in enumerate(((D, F), (D, F) if gated else None, (F, D))))
        first = jnp.int32(held)
        cap = gg.share_pass_rows(T, k, share, x.dtype)
        record = {"T": T, "k": k, "held": held, "columns": columns, "D": D, "F": F,
                  "held_picks": int((picks < held).sum()), "cap": cap,
                  "weight_bytes": held * D * F * 2 * (3 if gated else 2)}

        def layer(rows):
            def once(x, idx, vals, first, stacks):
                return gg.expert_share_ffn(x, idx, vals, *stacks, share, first_group=first,
                                           activation=jax.nn.silu if gated else relu2,
                                           pass_rows=rows)

            def looped(x, idx, vals, first, stacks):
                def turn(_, carry):
                    y, passes = once(x + carry[0] * jnp.bfloat16(1e-3), idx, vals, first, stacks)
                    return y, jnp.asarray(passes, jnp.int32)
                return jax.lax.fori_loop(0, SHARE_LOOP, turn, (jnp.zeros_like(x), jnp.int32(0)))

            return jax.jit(once), jax.jit(looped)

        args = (x, idx, vals, first, stacks)
        once, looped = layer(T * k)
        want, _ = once(*args)
        record["every_pick_a_row_ms"] = _ms_a_call(looped, *args, calls=3) / SHARE_LOOP
        for multiple in (1, 2, 4):
            rows = gg.share_pass_rows(T, k, share, x.dtype, multiple=multiple)
            if rows >= T * k:
                continue
            once, looped = layer(rows)
            got, passes = once(*args)
            record[f"passes_of_{multiple}x"] = {
                "rows": rows, "passes": int(passes),
                "ms": _ms_a_call(looped, *args, calls=3) / SHARE_LOOP,
                "rel_err": float(f"{rel_err(got, want):.3e}")}
        if cap < T * k:
            record["parts_us"] = _share_parts(T, k, D, cap, idx < held, idx, vals, x.dtype)
        del stacks, args
        yield name, record


SHARE_LOOP = 20


def _share_parts(T, k, D, cap, held, idx, vals, dtype):
    """us a turn of the candidates for the two parts of a pass that are not
    the grouped matmul: listing the held picks, and the sum by token."""
    import jax
    import jax.numpy as jnp

    n = T * k
    at = jnp.arange(n, dtype=jnp.int32)
    turns = 10 * SHARE_LOOP

    def sort3(live, idx, vals):
        return jax.lax.sort((jnp.where(live, at, n + at), idx, vals), num_keys=1)

    def sort1_gather(live, idx, vals):
        order = jnp.sort(jnp.where(live, at, n + at))[:cap] % n
        return order, idx[order], vals[order]

    def scatter_gather(live, idx, vals):
        slot = jnp.where(live, jnp.cumsum(live, dtype=jnp.int32) - 1, n)
        order = jnp.zeros((n,), jnp.int32).at[slot].set(at, mode="drop", unique_indices=True)[:cap]
        return order, idx[order], vals[order]

    def search_gather(live, idx, vals):
        csum = jnp.cumsum(live, dtype=jnp.int32)
        order = jnp.sum(csum[None, :] <= jnp.arange(cap, dtype=jnp.int32)[:, None], axis=1)
        order = jnp.minimum(order, n - 1)
        return order, idx[order], vals[order]

    def search_alone(live, idx, vals):
        csum = jnp.cumsum(live, dtype=jnp.int32)
        order = jnp.sum(csum[None, :] <= jnp.arange(cap, dtype=jnp.int32)[:, None], axis=1)
        return order, order, vals[:cap]

    def listed(fn):
        def looped(held, idx, vals):
            def turn(_, c):     # c stays 0, which the compiler cannot know
                a, b, w = fn(held.reshape(-1) ^ (c != 0), idx.reshape(-1) + c, vals.reshape(-1))
                return jnp.minimum(a[0] + b[0], 0) * (w[0] > 1e9)
            return jax.lax.fori_loop(0, turns, turn, jnp.int32(0))
        return jax.jit(looped)

    out = {}
    for fn in (sort3, sort1_gather, scatter_gather, search_gather, search_alone):
        out["list_" + fn.__name__] = _ms_a_call(listed(fn), held, idx, vals, calls=3) * 1e3 / turns
    tok = jnp.sort(jnp.where(held.reshape(-1), at, n + at))[:cap] // k
    tok = jnp.where(tok < T, tok, T)
    y = jax.random.normal(jax.random.PRNGKey(5), (cap, D), dtype)
    w = jnp.full((cap,), 0.1, jnp.float32)

    def segment_sum(acc, y, w, tok):
        prod = y.astype(jnp.float32) * w.astype(dtype).astype(jnp.float32)[:, None]
        return acc + jax.ops.segment_sum(prod, tok, num_segments=T, indices_are_sorted=True)

    def one_hot_product(acc, y, w, tok):
        hot = jnp.where(tok[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None],
                        w.astype(dtype)[None, :], 0)
        return acc + jnp.dot(hot, y, preferred_element_type=jnp.float32)

    def one_hot_guarded(acc, y, w, tok):    # the one that serves: a row not finite reaches
        fine = jnp.all(jnp.isfinite(y), axis=1)     # its own token alone
        mine = tok[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]
        acc = acc + jnp.dot(jnp.where(mine, w.astype(dtype)[None, :], 0),
                            jnp.where(fine[:, None], y, 0), preferred_element_type=jnp.float32)
        return jnp.where(jnp.any(mine & ~fine[None, :], axis=1)[:, None], jnp.nan, acc)

    def carried_alone(acc, y, w, tok):      # the float32 carry's read and write, and y's read
        return acc + jnp.sum(y.astype(jnp.float32) * w[:, None], axis=0, keepdims=True)

    def summed(fn):
        def looped(y, w, tok):
            def turn(_, acc):   # y a function of the carry, or the product leaves the loop
                return fn(acc, y + (acc[:1, :1] * 1e-9).astype(dtype), w, tok)
            return jax.lax.fori_loop(0, turns, turn, jnp.zeros((T, D), jnp.float32))
        return jax.jit(looped)

    want = segment_sum(jnp.zeros((T, D), jnp.float32), y, w, tok)
    for fn in (segment_sum, one_hot_product, one_hot_guarded, carried_alone):
        out["sum_" + fn.__name__] = _ms_a_call(summed(fn), y, w, tok, calls=3) * 1e3 / turns
    out["sum_one_hot_rel_err"] = float(f"{rel_err(one_hot_product(want * 0, y, w, tok), want):.3e}")
    return out


FLASH_CLASSES = (("window_8192x32x128", 8192, 1024),     # mellum2-12b-moe8k-x4's three band layers
                 ("causal_8192x32x128", 8192, None),     # ... and its full layer
                 ("causal_4096x32x128", 4096, None))     # mistral7b-zero3-x4's eleven layers


def flash_window_classes(parent_dir):
    """The training cells' attention calls alone (PR 58; PR 62: Mistral's shape,
    the schedule by class, the parent's kernels and the pieces swept): one
    sequence of 8192 or 4096 x 32 heads x 128 (the key-value heads already
    repeated, as the training block hands them over), a window of 1024 beside
    the causal kernels - forward, and forward + backward (``dkv`` and ``dq``) -
    each from a loop inside one program. Yields the time a call, the schedule
    (``flash_schedule`` of the forward and of the backward kernels: pairs
    skipped, whole and crossed, computed over needed, masked over computed -
    the forward's pairs, every one masked, are what all three kernels computed
    before PR 62), and the
    share of the bf16 peak of the operations the band or the triangle NEEDS
    (``benchmark/readers/mellum.py``); the same for the kernels of the checkout
    under ``parent_dir`` and for this tree's at other numbers of pieces a side
    of a block in the forward and in the backward kernels."""
    import jax
    import jax.numpy as jnp
    import importlib
    from benchmark.readers import mellum as work
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")   # the module, not
    H, d, LOOPS = 32, 128, 4                                                    # the function
    peak = 197e12
    parent = _parent_kernel(parent_dir, "flash_attention")

    def looped(fn):
        def run(q, k, v):
            def turn(_, carry):
                return fn(q + carry.astype(q.dtype), k, v)
            return jax.lax.fori_loop(0, LOOPS, turn, jnp.zeros((), jnp.float32))
        return jax.jit(run)

    def forward(module, window):
        return lambda q, k, v: jnp.sum(module.flash_attention(
            q, k, v, causal=True, window=window, force_pallas=True, interpret=False)[0, 0, 0, :2]
            .astype(jnp.float32)) * 0

    def both(module, window):
        def fn(q, k, v):
            grads = jax.grad(lambda *a: jnp.sum(module.flash_attention(
                *a, causal=True, window=window, force_pallas=True, interpret=False)
                .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
            return sum(jnp.sum(g[0, 0, 0, :2].astype(jnp.float32)) for g in grads) * 0
        return fn

    def timed(module, window, S, qkv):
        need, out = work.attention_pairs(S, window), {}
        for what, make, backward in (("fwd", forward, False), ("fwd_bwd", both, True)):
            try:
                ms = _ms_a_call(looped(make(module, window)), *qkv, calls=5) / LOOPS
                flops = work.attention_flops(need, H, d, backward=backward)
                out[what] = {"ms": ms, "needed_tflops": flops / 1e12,
                             "peak_share": 100 * flops / peak / (ms / 1e3)}
            except Exception as e:
                out[what] = {"refused": f"{type(e).__name__}: {e}"[:600]}
        return out

    for name, S, window in FLASH_CLASSES:
        qkv = tuple(jax.random.normal(key, (1, S, H, d), jnp.bfloat16)
                    for key in jax.random.split(jax.random.PRNGKey(0), 3))
        record = {"schedule": {"forward": fa.flash_schedule(S, window),
                               "backward": fa.flash_schedule(S, window, backward=True)}}
        record.update(timed(fa, window, S, qkv))
        if parent is not None:
            record["parent"] = timed(parent, window, S, qkv)
        was = fa._FORWARD_PIECES, fa._BACKWARD_PIECES
        for pieces in ((1, 1), (1, 2), (2, 2), (1, 4), (4, 4)):
            if pieces != was:
                fa._FORWARD_PIECES, fa._BACKWARD_PIECES = pieces
                record["pieces_fwd_%d_bwd_%d" % pieces] = timed(fa, window, S, qkv)
        fa._FORWARD_PIECES, fa._BACKWARD_PIECES = was
        # the same answer as the XLA reference's mask, at 2048
        small = tuple(x[:, :2048, :4] for x in qkv)
        got = fa.flash_attention(*small, causal=True, window=window, force_pallas=True, interpret=False)
        want = fa.flash_attention(*small, causal=True, window=window, force_pallas=False)
        record["rel_err_vs_masked_softmax"] = float(f"{rel_err(got, want):.3e}")
        yield f"flash_{name}", record


def moe_rows_classes(T=32768, D=2304, n_held=65536, interpret=False):
    """``mellum2-12b-moe8k-x4``'s row moves alone (PR 59): one pass of a rank's
    held picks as :func:`ops.grouped_gemm._pass_layout` lays it out (random
    picks of 16 experts, the layout of the Pallas grouped matmul), the two row
    kernels beside the XLA forms PR 58 ran, each from a loop inside one program.
    GB/s counts the held rows alone, once read and once written. (The arguments:
    a rehearsal's, interpreted on the CPU at a small size under
    ``grouped_gemm.FORCE_INTERPRET``, which gives the chip's layout there.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops import grouped_gemm as gg
    from deepspeed_tpu.ops.pallas import moe_rows as mr
    from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile
    k, held, LOOPS = 8, 16, 4
    dtype = jnp.bfloat16
    cap = gg.mesh_share_rows(T, k, gg.ExpertShare(0, 64, 64), 4, dtype)
    rng = np.random.default_rng(0)
    pick = np.zeros((cap,), np.int32)
    pick[:n_held] = np.sort(rng.choice(T * k, n_held, replace=False))
    here = jnp.arange(cap) < n_held
    experts = jnp.asarray(rng.integers(0, held, cap).astype(np.int32))
    pick = jnp.asarray(pick)
    slot_token, slots = jax.jit(lambda e, h, p: gg._pass_layout(
        e, h, p, T, k, held, T * k // 64, D, 896, dtype)[:2])(experts, here, pick)
    S = slot_token.shape[0]
    tm = row_tile(T * k // 64 * held, held, dtype)
    slot_of_pick, _, _ = gg._tile_routing(jnp.where(here, experts, 0), held, tm, here)
    tok_of_pick = jnp.where(here, pick // k, T)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), dtype)
    y = jax.random.normal(jax.random.PRNGKey(2), (S, D), dtype)
    w = jax.random.uniform(jax.random.PRNGKey(3), (T, k), jnp.float32)
    useful = 2 * n_held * D * jnp.dtype(dtype).itemsize

    def looped(fn, out_shape, out_dtype):
        def run(src, index, *rest):
            def turn(_, out):       # the indices a function of the carry (plus 0), or the call
                moved = (out.ravel()[0] != out.ravel()[0]).astype(jnp.int32)   # leaves the loop
                return fn(src, index + moved, *rest)
            return jax.lax.fori_loop(0, LOOPS, turn, jnp.zeros(out_shape, out_dtype))
        return jax.jit(run)

    def timed(name, fn, out_shape, out_dtype, *args):
        try:
            ms = _ms_a_call(looped(fn, out_shape, out_dtype), *args, calls=3) / LOOPS
            record[name] = {"ms": ms, "useful_GBps": useful / ms / 1e6}
        except Exception as e:
            record[name] = {"refused": f"{type(e).__name__}: {e}"[:600]}
        print(json.dumps({name: record[name]}), file=sys.stderr, flush=True)   # as it goes

    # dispatch: the tokens' rows into the layout
    def xla_dispatch(x, tok_of_pick, slot_of_pick):         # PR 58: pick order between them
        rows = jnp.where((tok_of_pick < T)[:, None],
                         jnp.take(x, jnp.minimum(tok_of_pick, T - 1), axis=0), 0)
        return jnp.zeros((S, D), dtype).at[slot_of_pick].set(rows, unique_indices=True)

    x_rows, y_rows = mr._as_rows(x, interpret), mr._as_rows(y, interpret)
    pack = jax.jit(lambda a: mr._as_rows(a, interpret))
    record = {"tokens": T, "slots": S, "held_rows": n_held, "row_bytes": D * 2,
              # not from a loop (its input would not change): twenty calls queued from the host
              "pack_tokens_alone_ms": _ms_a_call(pack, x)}
    timed("jnp_take_into_layout", lambda x, i: mr.gather_rows(x, i), (S, D), dtype, x, slot_token)
    timed("xla_take_then_scatter", xla_dispatch, (S, D), dtype, x, tok_of_pick, slot_of_pick)
    # the kernel alone: the source already a row at a time
    for block in (128, 256, 512):
        timed(f"gather_rows_packed_block{block}", lambda r, i: mr._gather_packed(
            r, i, dtype, block, interpret), (S, D), dtype, x_rows, slot_token)
    timed("gather_rows", lambda x, i: mr.gather_rows(x, i, None, True, interpret), (S, D), dtype,
          x, slot_token)
    got = mr.gather_rows(x, slot_token, None, True, interpret)
    record["gather_rows_equal_jnp"] = bool(jnp.array_equal(got, mr.gather_rows(x, slot_token)))
    yield "moe_rows_dispatch_32768_to_102400", record

    # combine: the layout's rows into their tokens' sums
    def xla_combine(y, slot_of_pick, tok_of_pick, w_of_pick):  # PR 58: gather back, sorted sum
        rows = jnp.take(y, slot_of_pick, axis=0, mode="fill", fill_value=0).astype(jnp.float32)
        return jax.ops.segment_sum(rows * w_of_pick[:, None], tok_of_pick, num_segments=T + 1,
                                   indices_are_sorted=True)[:T]

    w_of_pick = jnp.where(here, jnp.take(w.reshape(-1), pick), 0)
    record = {"tokens": T, "slots": S, "held_rows": n_held, "row_bytes": D * 2,
              "pack_layout_alone_ms": _ms_a_call(pack, y)}
    timed("xla_take_then_segment_sum", xla_combine, (T, D), jnp.float32, y, slot_of_pick,
          tok_of_pick, w_of_pick)
    for block in (8, 16, 32):
        timed(f"gather_sum_rows_packed_block{block}", lambda r, s, w: mr._sum_packed(
            jax.lax.empty((T, D), jnp.float32), True, r, s, w, dtype, block, interpret),
            (T, D), jnp.float32, y_rows, slots, w)
    timed("gather_sum_rows_unweighted", lambda y, s: mr.gather_sum_rows(
        y, s, None, None, True, interpret), (T, D), jnp.float32, y, slots)
    timed("gather_sum_rows", lambda y, s, w: mr.gather_sum_rows(y, s, w, None, True, interpret),
          (T, D), jnp.float32, y, slots, w)
    got = mr.gather_sum_rows(y, slots, w, None, True, interpret)
    record["rel_err_vs_segment_sum"] = float(
        f"{rel_err(got, xla_combine(y, slot_of_pick, tok_of_pick, w_of_pick)):.3e}")
    yield "moe_rows_combine_102400_to_32768", record


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
    paged, mla = "--paged" in sys.argv or "--paged64" in sys.argv, "--mla" in sys.argv
    parent_dir = (sys.argv[sys.argv.index("--paged-parent") + 1]
                  if "--paged-parent" in sys.argv else os.path.join("_checkout", "parent"))
    live, ssm, chunk = "--live" in sys.argv, "--ssm" in sys.argv, "--chunk" in sys.argv
    scan, kda, window = "--scan" in sys.argv, "--kda" in sys.argv, "--window" in sys.argv
    share, paged1 = "--share" in sys.argv, "--paged1" in sys.argv
    flash_window, moe_rows = "--flash-window" in sys.argv, "--moe-rows" in sys.argv
    if moe_rows:
        section, records = "moe_rows", moe_rows_classes()
    elif flash_window:
        section, records = "flash_window", flash_window_classes(parent_dir)
    elif paged1:
        section, records = "paged_group1", paged_group1_classes()
    elif share:
        section, records = "expert_share", share_classes()
    elif window:
        section, records = "window_attention", window_classes()
    elif kda:
        section, records = "kda", kda_classes()
    elif scan:
        section, records = "selective_scan", selective_scan_classes()
    elif chunk:
        section, records = "query_tiles", chunk_classes(parent_dir)
    elif ssm:
        section, records = "ssm_state", ssm_state_classes()
    elif live:
        shares = [float(x) for x in sys.argv[sys.argv.index("--live") + 1].split(",")]
        section, records = "live_rows", live_rows_sweep(parent_dir, shares)
    elif paged:
        section, records = "paged_attention", paged_attention_classes(
            parent_dir, narrow="--paged64" in sys.argv)
    elif mla:
        section, records = "paged_mla_attention", paged_mla_classes(parent_dir)
    else:
        section, records = "grouped_matmul", grouped_matmul_classes(sweep="--gmm-sweep" in sys.argv)
    for name, record in records:
        report.setdefault(section, {})[name] = record
        print(json.dumps({name: record}), flush=True)
    for name, fn, ref, args, tol in (() if ssm or live or paged or mla or chunk or scan or kda
                                     or window or share or paged1 or flash_window or moe_rows
                                     or "--gmm-only" in sys.argv
                                     else cases()):
        try:
            result = verdict(fn, ref, args, tol)
        except Exception as e:  # the census records a refusal and goes on to the next kernel
            result = {"verdict": "refused", "error": f"{type(e).__name__}: {e}"[:2000]}
        report["kernels"][name] = result
        print(json.dumps({name: result}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    out = ("moe_rows_census.json" if moe_rows else "flash_window_census.json" if flash_window else "paged1_census.json" if paged1 else "share_census.json" if share else "window_census.json" if window
           else "kda_census.json" if kda
           else "scan_census.json" if scan
           else "chunk_census.json" if chunk
           else "ssm_census.json" if ssm
           else "live_census.json" if live
           else "paged_census.json" if paged
           else "mla_census.json" if mla else "kernel_census.json")
    with open(os.path.join("chiprun_out", out), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()

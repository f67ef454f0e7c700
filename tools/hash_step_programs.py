#!/usr/bin/env python3
"""sha256 of every serving debug preset's step program, as StableHLO.

    JAX_PLATFORMS=cpu PYTHONPATH=<tree> python3 tools/hash_step_programs.py > a.txt

Lowers ``model_runner.ragged_forward`` (nothing runs) for each debug preset
of the model kinds the ragged engine serves, at 8 and at 32 rows, once as
the backend chooses and once with ``DS_PALLAS=1`` (the kernel paths,
interpreted), and prints a line a program: preset, rows, the first 16 hex
digits of the text's sha256, the attention implementation it got. Run it
with ``PYTHONPATH`` on two checkouts (this file from either) and ``diff`` the
two outputs: a change that is meant to leave a model kind's programs alone
shows no line of that kind (PERF.md, PRs 34, 38, 39).
"""

import hashlib
import os

import jax
import jax.numpy as jnp

PRESETS = ("debug", "mixtral-debug", "gpt2-debug", "opt-debug", "bloom-debug", "neox-debug",
           "gptj-debug", "falcon-debug", "moonlight-debug", "longcat-flash-debug",
           "minicpm-sala-debug", "nemotron-h-debug", "lfm2-debug", "jamba-debug",
           "solar-open2-debug", "laguna-debug", "ouro-debug", "granite-hybrid-debug")
SEQS, TABLE, BLOCKS = 4, 12, 64


def programs(forced):
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    sds = jax.ShapeDtypeStruct
    for preset in PRESETS:
        model = models.build_model(preset)
        cfg = model.config
        kind = model_runner.kind_of(cfg)
        params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                                   jnp.zeros((1, 8), jnp.int32))["params"])
        bs = getattr(cfg, "sparse_block_size", 16)
        pools = [sds((kind.state_layers(cfg), BLOCKS, bs, w), jnp.float32)
                 for w in kind.state_rows(cfg)]
        extra = jax.eval_shape(lambda: kind.extra_state(cfg, BLOCKS, SEQS, jnp.float32))
        seq_rows = kind.seq_rows
        if kind.window(cfg) is not None:    # a second pool, its ring a sequence's state row
            from deepspeed_tpu.inference.v2.ragged.kv_cache import WindowPool
            window, layers = kind.window(cfg)
            pool = WindowPool(window, bs, 32, BLOCKS)
            extra = jax.eval_shape(lambda: pool.arrays(layers, kind.state_rows(cfg)[0],
                                                       jnp.float32))
            seq_rows = pool.ring
        for T in (8, 32):
            batch = {"token_ids": sds((T,), jnp.int32), "token_seq": sds((T,), jnp.int32),
                     "token_pos": sds((T,), jnp.int32),
                     "block_tables": sds((SEQS + 1, TABLE), jnp.int32),
                     "last_index": sds((SEQS,), jnp.int32), "num_tokens": sds((), jnp.int32)}
            if seq_rows:
                batch["seq_state"] = sds((SEQS + 1, seq_rows), jnp.int32)
            choice = AttentionChoice()
            text = jax.jit(lambda p, kc, vc, b, x: model_runner.ragged_forward(
                p, kc, vc, b, cfg, jnp.float32, attn_impl=choice, extra=x)).lower(
                    params, *pools, batch, extra).as_text()
            print(f"{preset:22s} T={T:3d} DS_PALLAS={forced or '-'} "
                  f"{hashlib.sha256(text.encode()).hexdigest()[:16]} "
                  f"{sorted(set(choice.selected.values()))}", flush=True)


if __name__ == "__main__":
    os.environ.pop("DS_PALLAS", None)
    programs("")
    os.environ["DS_PALLAS"] = "1"
    programs("1")

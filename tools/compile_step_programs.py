#!/usr/bin/env python3
"""A serving preset's step programs compiled for a described TPU v5e chip.

    JAX_PLATFORMS=cpu python3 tools/compile_step_programs.py ouro-2.6b \
        [--rows 16,256,512] [--blocks 368] [--block-size 16] [--seqs 16] [--table 32] \
        [--over '{"num_hidden_layers": 22}'] [--shapes '2048,2048|2048,5632|5632,2048'] \
        [--slots 136] [--out DIR]

No chip: libtpu's compile-only client (the ``on-chip-measurement`` guide's
section 2) compiles ``model_runner.ragged_forward`` on ``ShapeDtypeStruct``s
- bfloat16 parameters and pools of the given size, the Pallas paged kernel
pinned and lowered by Mosaic - and prints, a program: the seconds it took,
``memory_analysis()`` (``temp_size_in_bytes`` is what the program needs
beside its arguments), and every op of the optimized HLO whose **result has
the shape of a layer's matrices** (``--shapes``: a regular expression over
the trailing dims; parameters and tuple reads left out): a ``copy`` of a
whole stack, or a slice of one in another layout, is the chip's compiler
re-laying weights (PERF.md, PR 54: 2 x 403 MB a step and 807 MB of
temporaries came and went with where a gate's vector was cut out of its
matrix; PR 55 added a 256-row program and looked here first; the scan's own
slices of a layer - ``fusion ... kLoop``, seven a loop - are listed too: on the chip they fuse into their consumers and a trace shows no op
of their shape). ``--out DIR`` keeps the HLO texts. ~5 s a program at 48 layers.

It proves compilation only - never a time, and never that a result is right.
"""

import argparse
import json
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["DS_PALLAS"] = "1"

BUDGET = 512    # the token budget of every engine the benchmark builds


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("preset")
    parser.add_argument("--rows", default=None,
                        help="default: every size a put can be (engine_v2.put_ladder of --seqs "
                             "and the benchmark's token budget)")
    parser.add_argument("--blocks", type=int, default=368)
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--seqs", type=int, default=16)
    parser.add_argument("--table", type=int, default=32, help="blocks a sequence's table holds")
    parser.add_argument("--over", default="{}", help="JSON of overrides of the preset's config")
    parser.add_argument("--shapes", default="2048,2048|2048,5632|5632,2048")
    parser.add_argument("--slots", type=int, default=None,
                        help="a kind with a slot of state a sequence (kv+slots): the slot pool's "
                             "slots, the prefix cache's among them (default: --seqs)")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import deepspeed_tpu.ops.pallas as pallas
    pallas.default_interpret = lambda: False          # the kernels lower compiled, by Mosaic
    from deepspeed_tpu import models
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.engine_v2 import put_ladder
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    model = models.build_model(args.preset, **json.loads(args.over))
    cfg = model.config
    kind = model_runner.kind_of(cfg)
    if kind.state_kind not in ("kv", "kv+slots"):
        raise SystemExit(f"{args.preset}: a {kind.state_kind!r} state has pools of its own shapes; "
                         f"this tool lays the two key-value pools and a slot pool only")
    extra = None
    if kind.slot_state:     # the kind's own tree beside the pools, a slot a sequence
        extra = jax.tree.map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda: kind.extra_state(cfg, args.blocks, args.slots or args.seqs, jnp.bfloat16)))
    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32))["params"]))
    pools = [sds((kind.state_layers(cfg), args.blocks, args.block_size, width), jnp.bfloat16)
             for width in kind.state_rows(cfg)]
    shaped = re.compile(r"= bf16\[(\d+,)*(" + args.shapes + r")\]")
    ladder = put_ladder(args.seqs, BUDGET)
    for rows in (ladder if args.rows is None else (int(r) for r in args.rows.split(","))):
        batch = {"token_ids": sds((rows,), jnp.int32), "token_seq": sds((rows,), jnp.int32),
                 "token_pos": sds((rows,), jnp.int32),
                 "block_tables": sds((args.seqs + 1, args.table), jnp.int32),
                 "last_index": sds((args.seqs,), jnp.int32), "num_tokens": sds((), jnp.int32)}
        if kind.seq_rows:
            batch["seq_state"] = sds((args.seqs + 1, kind.seq_rows), jnp.int32)
        choice = AttentionChoice("pallas_paged")
        start = time.time()
        step = jax.jit(lambda p, kc, vc, b, x: model_runner.ragged_forward(
            p, kc, vc, b, cfg, jnp.bfloat16, attn_impl=choice, extra=x),
            donate_argnums=(1, 2, 4))
        compiled = step.lower(params, *pools, batch, extra).compile()
        print(f"{args.preset} rows={rows}: compiled in {time.time() - start:.1f} s, "
              f"{dict(choice.selected)} state step {dict(choice.state_step)}")
        print(" ", compiled.memory_analysis())
        text = compiled.as_text()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{args.preset}.rows{rows}.hlo"), "w") as f:
                f.write(text)
        for line in text.splitlines():
            if shaped.search(line) and " parameter(" not in line \
                    and "get-tuple-element" not in line:
                print("  matrix-shaped:", line.strip()[:200])


if __name__ == "__main__":
    main()

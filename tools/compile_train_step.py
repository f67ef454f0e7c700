#!/usr/bin/env python3
"""Compile a training cell's step for a described TPU v5e host, without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=. python3 tools/compile_train_step.py mellum2-12b-moe8k-x4 \
        [--remat-policy moe|full|dots] [--layers N] [--out DIR]

Builds the cell's engine (``benchmark/runners/train_mellum.build`` for a
``train_mellum`` cell) on the four abstract devices of a ``v5e:2x2`` topology,
lays its state out as ``ShapeDtypeStruct``s under the ZeRO policy's shardings
(nothing is placed: a described device holds no array), and lowers and compiles
``engine.train_batch``'s one program with the Pallas kernels lowered by Mosaic
and the grouped matmul's on-chip dispatch. Prints the seconds, XLA's
``memory_analysis()`` (bytes on each chip: what the compiler refuses here costs
no chip time), the collectives by kind with their largest result, and the
custom calls by kernel name. It proves compilation and a fit, never a time and
never a result.
"""

import argparse
import collections
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["DS_PALLAS"] = "1"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def abstract_state(engine, args):
    """The engine's parameters, master copy, optimizer state and scaler as
    shapes under its policy's shardings: what ``_make_state`` makes, unplaced."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.runtime.zero.partitioning import path_tree_map
    mesh, policy = engine.mesh, engine.sharding_policy
    engine._configure_param_offload()
    shapes = jax.eval_shape(lambda rng: engine.module.init(rng, *args)["params"],
                            jax.random.PRNGKey(0))

    def laid(spec_of, dtype):
        return path_tree_map(lambda path, x: jax.ShapeDtypeStruct(
            x.shape, dtype, sharding=NamedSharding(mesh, spec_of(path, x.shape))), shapes)

    engine.params = laid(policy.param_spec, engine.compute_dtype)
    engine._param_specs = policy.tree_param_specs(shapes)
    engine._opt_specs = policy.tree_opt_specs(shapes)
    engine._opt_shardings = policy.tree_opt_shardings(shapes)
    engine._grad_specs = policy.tree_grad_specs(shapes)
    engine._trainable_mask = None
    engine._host_offload = None
    engine.master_params = laid(policy.opt_spec, jnp.float32)
    transform = engine.optimizer.transform()
    engine._opt_init, engine._opt_update = transform.init, transform.update
    state = jax.eval_shape(engine._opt_init, engine.master_params)
    shardings = engine._opt_state_shardings(state)
    engine.opt_state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), state, shardings)
    engine.scaler_state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=NamedSharding(mesh, P())), engine.scaler_state)
    engine._initialized = True


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--remat-policy", default=None)
    parser.add_argument("--layers", type=int, default=None, help="periods x 4 layers to build")
    parser.add_argument("--out", default=None, help="directory for the compiled HLO text")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops import grouped_gemm, pallas
    pallas.default_interpret = lambda: False          # the kernels lower compiled, by Mosaic
    # off the chip the grouped matmul would lower lax.ragged_dot: its on-chip answer instead
    from deepspeed_tpu.ops.pallas.grouped_matmul import col_tile
    grouped_gemm._use_pallas_gmm = lambda rows, experts, d, f, dtype, quantized=False: (
        d % 128 == 0 and f % 128 == 0 and rows >= experts
        and col_tile(d, f, jnp.dtype(dtype).itemsize) is not None
        and col_tile(f, d, jnp.dtype(dtype).itemsize) is not None)
    from deepspeed_tpu.ops.pallas.moe_rows import rows_kernel_supported
    grouped_gemm._use_pallas_rows = rows_kernel_supported     # the exchange's row kernels likewise

    from benchmark.harness import spec
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(args.cell)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    if args.remat_policy:
        config["trainer"]["remat_policy"] = args.remat_policy
    if args.layers:
        period = config["model"]["layer_types"][:4]
        config["model"].update(num_hidden_layers=args.layers, layer_types=period * (args.layers // 4),
                               mlp_layer_types=["sparse"] * args.layers)
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices

    class Ctx:
        rehearse = False
    ctx = Ctx()
    ctx.config, ctx.devices = config, devices[:cell["chips"]]
    ids = np.zeros((traffic["sequences_per_step"], traffic["seq_len"]), np.int32)
    runner = bench.load("runners", cell["runner"], "build")
    engine, _, _ = runner(ctx, ids)
    abstract_state(engine, (jnp.zeros((ids.shape[0], 128), jnp.int32),))
    fn, tied = engine._train_batch_fn()
    batch = jax.ShapeDtypeStruct((1,) + ids.shape, jnp.int32,
                                 sharding=NamedSharding(engine.mesh, P(None, "expert")))
    feed = ((batch, batch), {})
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=NamedSharding(engine.mesh, P()))
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=NamedSharding(engine.mesh, P()))
    start = time.time()
    lowered = fn.lower(engine.params, engine.master_params, engine.opt_state, engine.scaler_state,
                       scalar(jnp.float32), rng, feed)
    print(f"{args.cell}: lowered in {time.time() - start:.1f} s; dispatch {dict(grouped_gemm.GMM_STATS.snapshot())}")
    start = time.time()
    compiled = lowered.compile()
    print(f"compiled in {time.time() - start:.1f} s")
    mem = compiled.memory_analysis()
    print(" ", mem)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
             - mem.alias_size_in_bytes)
    print(f"  a chip: arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, live at once <= {total / 1e9:.2f} GB")
    text = compiled.as_text()
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.cell}.train_step.hlo"), "w") as f:
            f.write(text)
    collectives, kernels = collections.defaultdict(list), collections.Counter()
    shape = re.compile(r"(bf16|f32|s32|u32)\[([\d,]*)\]")
    for line in text.splitlines():
        m = re.search(r"= (\S+) (all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)"
                      r"(-start)?\(", line)
        if m:
            size = 0
            for kind, dims in shape.findall(m.group(1)):
                n = 1
                for d in filter(None, dims.split(",")):
                    n *= int(d)
                size = max(size, n * (2 if kind == "bf16" else 4))
            collectives[m.group(2)].append((size, m.group(1)[:60]))
        k = re.match(r'\s*(?:ROOT )?%([A-Za-z_][\w-]*?)[.\d]* = .* custom-call\(', line)
        if k and 'custom_call_target="tpu_custom_call"' in line:
            kernels[k.group(1)] += 1    # a Pallas call by its instruction's name (its kernel's)
    for kind, found in sorted(collectives.items()):
        big = max(found)
        print(f"  {kind}: {len(found)}, the largest {big[0] / 1e6:.1f} MB {big[1]}")
    print("  kernels:", json.dumps(dict(kernels)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

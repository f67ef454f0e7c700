"""``zero_optimization.overlap_comm`` at stage 3 (``runtime/zero/overlap.py``):
the layer scan whose backward gathers a layer once gives the plain scan's
numbers, engages only where its conditions hold, leaves every other program
the parent's, and keeps no gathered layer."""

import dataclasses
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.models.llama import LLAMA_CONFIGS, init_cache
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import make_mesh_topology
from deepspeed_tpu.runtime.zero import overlap
from deepspeed_tpu.runtime.zero.partitioning import ZeroShardingPolicy
from deepspeed_tpu.utils import tracing

LAYERS, SEQ = 4, 32


def make_engine(stage=3, overlap_comm=None, bf16=False, n_dev=4, gas=1, zero=None, mesh=None, **model):
    groups.destroy_mesh()
    cfg = dataclasses.replace(LLAMA_CONFIGS["debug"], num_hidden_layers=LAYERS, **model)
    zero_cfg = {"stage": stage, "stage3_param_persistence_threshold": 0, **(zero or {})}
    if overlap_comm is not None:
        zero_cfg["overlap_comm"] = overlap_comm
    mesh = mesh or {"data": n_dev}
    engine, *_ = deepspeed_tpu.initialize(
        model=build_llama(cfg), mesh=make_mesh_topology(**mesh, devices=jax.devices()[:n_dev]),
        config={"train_batch_size": 2 * mesh["data"] * gas, "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": gas, "bf16": {"enabled": bf16},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": zero_cfg, "steps_per_print": 10 ** 9})
    return engine


def token_ids(n_dev=4, gas=1):
    return np.random.RandomState(0).randint(0, 256, (gas, 2 * n_dev, SEQ)).astype(np.int32)


def grads_of(engine, ids):
    engine._materialize_state(ids, ids)
    loss, grads = engine._value_and_grad_fn()(engine.params, jnp.float32(1.0), jax.random.PRNGKey(0),
                                               (ids, ids), {})
    return float(loss), jax.tree.map(lambda g: np.asarray(g, np.float32), grads)


def last_train_record():
    return [r for r in tracing.snapshot()["steps"] if r["kind"] == "train"][-1]


def traced(fn, *args):
    """sha256 of a traced program's text, the functions' addresses left out
    (a remat policy prints as ``<function ... at 0x...>``)."""
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


def core_program(engine, n_dev=4):
    ids = jnp.asarray(token_ids(n_dev)[0])
    engine._materialize_state(ids, ids)
    return engine._vag_core(), (engine.params, jnp.float32(1.0), jax.random.PRNGKey(0), (ids, ids), {})


# ------------------------------------------------------------------ the numbers
CASES = {
    "dense": dict(),
    "dense-bf16": dict(bf16=True),
    "dense-tied": dict(tie_word_embeddings=True),
    "dense-dots": dict(remat_policy="dots"),
    "dense-mics2": dict(zero={"mics_shard_size": 2}, mesh={"data": 2, "sequence": 2}),
    "dense-gas2": dict(gas=2),
    "moe": dict(moe_num_experts=4, moe_top_k=2),
    "moe-bf16": dict(moe_num_experts=4, moe_top_k=2, bf16=True),
    "moe-dropless-moe": dict(moe_num_experts=4, moe_top_k=2, moe_drop_tokens=False, remat_policy="moe"),
    "moe-tied-dots": dict(moe_num_experts=4, moe_top_k=2, tie_word_embeddings=True, remat_policy="dots"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_overlapped_step_gives_the_plain_steps_numbers(name):
    """Loss and every gradient leaf of one micro-step, then the parameters
    three optimizer steps reach, against ``overlap_comm: false``. The dense
    reductions are the same sums in the same order on this backend; an
    expert layer's differ in their order."""
    case = dict(CASES[name])
    gas, bf16 = case.get("gas", 1), case.get("bf16", False)
    ids = token_ids(case.get("mesh", {}).get("data", 4), gas)
    rtol = 2e-2 if bf16 else 1e-5
    got = {}
    for on in (True, False):
        engine = make_engine(overlap_comm=on, **case)
        loss, grads = grads_of(engine, jnp.asarray(ids[0]))
        losses = [float(engine.train_batch(batch=(ids, ids))) for _ in range(3)]
        got[on] = (loss, grads, losses, jax.tree.map(lambda p: np.asarray(p, np.float32), engine.params))
        engaged = on and case.get("remat_policy", "full") == "full"
        assert last_train_record()["n_layers_prefetched"] == (gas * LAYERS if engaged else 0)
    (loss, grads, losses, params), (loss0, grads0, losses0, params0) = got[True], got[False]
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    np.testing.assert_allclose(losses, losses0, rtol=1e-3 if bf16 else 1e-5)
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads0)):
        scale = np.abs(g0).max() + 1e-12
        np.testing.assert_allclose(g / scale, g0 / scale, atol=rtol, err_msg=jax.tree_util.keystr(path))
    for (path, p), p0 in zip(jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(params0)):
        np.testing.assert_allclose(p, p0, atol=2e-2 if bf16 else 2e-5, err_msg=jax.tree_util.keystr(path))


# --------------------------------------------------- the programs that do not change
# What the parent commit (f36d662) traces for the gradient core of this file's four-layer
# debug Llama on data=4 / data=1, and for the model alone with streamed layers and in decode:
# sha256 of ``jax.make_jaxpr``'s text. The core's program does not depend on the stage (the
# stages differ in the shardings around it), so four of them share a hash.
PARENT_CORE = "83950eae857de448"
PARENT = {
    "stage0": (dict(stage=0, overlap_comm=True), PARENT_CORE),
    "stage1": (dict(stage=1, overlap_comm=True), PARENT_CORE),
    "stage2": (dict(stage=2, overlap_comm=True), PARENT_CORE),
    "overlap_comm_false": (dict(stage=3, overlap_comm=False), PARENT_CORE),
    "one_device": (dict(stage=3, n_dev=1), "2f82f94b49bf732e"),
    "remat_dots": (dict(stage=3, remat_policy="dots"), "8e0ee2c4b1bf9c72"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_a_step_that_does_not_engage_traces_the_parents_program(name):
    kwargs, parent = PARENT[name]
    engine = make_engine(**kwargs)
    fn, args = core_program(engine, kwargs.get("n_dev", 4))
    assert traced(fn, *args)[:16] == parent
    assert not (engine._layer_overlap and engine._layer_overlap.scans)


def test_an_engaged_step_traces_another_program():
    engine = make_engine(stage=3)  # overlap_comm: None becomes true at stage 3
    fn, args = core_program(engine)
    assert traced(fn, *args)[:16] != PARENT_CORE
    assert engine._layer_overlap.scans == {"model/layers": LAYERS}


@pytest.mark.parametrize("name", ["offload_params", "decode"])
def test_streamed_layers_and_decode_ignore_the_request(name):
    """With the engine's request active around the trace, a model whose layers
    stream from the host, and the decode scan, trace what they trace without."""
    mesh = make_mesh_topology(data=4, devices=jax.devices()[:4])
    groups.set_mesh(mesh)
    cfg = dataclasses.replace(LLAMA_CONFIGS["debug"], num_hidden_layers=LAYERS,
                              offload_params=name == "offload_params")
    model = build_llama(cfg)
    ids = jnp.zeros((8, SEQ), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, ids)["params"])
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    if name == "decode":
        cache = init_cache(cfg, 8, SEQ, jnp.float32)
        run = lambda p: model.apply({"params": p}, ids[:, :1], cache=cache, start_pos=3)[0].sum()
    else:
        run = lambda p: model.apply({"params": p}, ids, ids)[0]
    asked = overlap.LayerOverlap(ZeroShardingPolicy(mesh, stage=3, tp_rule=model.tp_rule))
    plain = traced(jax.grad(run), params)
    with overlap.overlapping(asked):
        assert traced(jax.grad(run), params) == plain
    assert asked.scans == {} and overlap.active() is None


# ------------------------------------------------------------------- the structure
def scans_of(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(scans_of(sub))
    return found


def test_no_gathered_layer_is_saved_for_the_backward():
    """The forward scan's stacked outputs are the backward's residuals: each
    layer's input and no value of the shape of a layer's parameter."""
    engine = make_engine(stage=3)
    fn, args = core_program(engine)
    layer_shapes = {x.shape[1:] for x in jax.tree.leaves(engine.params["model"]["layers"])}
    layer_scans = [e for e in scans_of(jax.make_jaxpr(fn)(*args).jaxpr) if e.params["length"] == LAYERS]
    forward = [e for e in layer_scans if not e.params["reverse"]]
    backward = [e for e in layer_scans if e.params["reverse"]]
    assert len(forward) == 1 and len(backward) == 1
    n_carry = forward[0].params["num_carry"]
    saved = [v.aval.shape[1:] for v in forward[0].outvars[n_carry:]]
    assert saved and not layer_shapes & set(saved)
    # and the backward's stacked outputs are one gradient a parameter leaf, nothing more
    n_carry = backward[0].params["num_carry"]
    assert sorted(v.aval.shape[1:] for v in backward[0].outvars[n_carry:]) == sorted(
        x.shape[1:] for x in jax.tree.leaves(engine.params["model"]["layers"]))


def test_the_overlapped_step_holds_at_most_two_more_layers():
    """``memory_analysis()`` of the compiled gradient core: the temporaries
    exceed the plain program's by no more than two layers' parameters and one
    layer's gradients."""
    temps = {}
    for on in (True, False):
        engine = make_engine(stage=3, overlap_comm=on)
        fn, args = core_program(engine)
        temps[on] = jax.jit(fn).lower(*args).compile().memory_analysis().temp_size_in_bytes
        layer_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(engine.params["model"]["layers"]))
    assert temps[True] - temps[False] <= 3 * layer_bytes // LAYERS


def test_a_small_leaf_is_summed_whole_and_a_large_one_left_to_the_ring(monkeypatch):
    """Below ``RING_REDUCE_MIN_BYTES`` a gradient is first constrained to the
    gathered layout, then cut; at or above it only cut."""
    def constraints(limit):
        monkeypatch.setattr(overlap, "RING_REDUCE_MIN_BYTES", limit)
        fn, args = core_program(make_engine(stage=3))
        backward = [e for e in scans_of(jax.make_jaxpr(fn)(*args).jaxpr)
                    if e.params["length"] == LAYERS and e.params["reverse"]][0]
        return sum(e.primitive.name == "sharding_constraint" for e in backward.params["jaxpr"].jaxpr.eqns)

    engine = make_engine(stage=3)
    core_program(engine)
    assert constraints(2 ** 40) - constraints(0) == len(jax.tree.leaves(engine.params["model"]["layers"]))


def test_the_collective_table_reads_a_recorded_trace():
    """``tools/collective_table.py`` (what ``PERF.md`` section 5's table of the
    training cell is made with) on the benchmark's small recorded trace."""
    import io
    import os

    from benchmark.harness import trace
    from tools.collective_table import table

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(__file__)))))
    out = io.StringIO()
    table(trace.load(os.path.join(root, "benchmark/tests/data/trace_small.json.gz")), min_step_ms=1, out=out)
    assert "while0 100.20 ms" in out.getvalue() and "paged_decode_attention" in out.getvalue()

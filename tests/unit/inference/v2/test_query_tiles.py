"""Query tiles through the engine (``ops/pallas/paged_attention``, "A query
tile"): a ``put`` whose batch holds prompt chunks attends them a tile at a
time - the same streams as the gather's and as a row a grid step - and its
step record says how many rows went through how many tiles, counted on the
host by the function that lays them inside the program. The kernel runs
interpreted (``DS_PALLAS=1``) at a head size it takes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.utils import tracing

# 40 + 9 + 3 prompt rows fill one 64-row put: rows 0-31 and 32-39 of the first prompt are a
# tile each (a tile ends with its block of QUERY_TILE rows), the second prompt's 9 rows are
# one and so are the third's 3
PROMPTS = {u: ((np.arange(n) * (u + 3)) % 250).astype(np.int32)
           for u, n in ((1, 40), (2, 9), (3, 3))}
FIRST_PUT = (32 + 8 + 9 + 3, 4)


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug", hidden_size=256, num_attention_heads=2, num_key_value_heads=1)
    assert model.config.head_dim == 128
    return model, model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]


def serve(model_and_params, max_burst=4):
    """→ ({uid: generated}, the engine's step records, its attention)."""
    model, params = model_and_params
    engine = InferenceEngineV2(
        model=model, params=params, dtype=jnp.float32,
        config=RaggedInferenceEngineConfig(
            kv_block_size=8,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                               max_ragged_sequence_count=8,
                                               max_tracked_sequences=8, max_context=128)))
    sched = DynamicSplitFuseScheduler(engine, token_budget=64, max_burst=max_burst)
    for uid, prompt in PROMPTS.items():
        sched.add_request(uid, prompt, max_new_tokens=6)
    out = sched.run_to_completion()
    records = [r for r in tracing.snapshot()["steps"] if r["engine"] == engine.trace_id]
    impls = set(engine.attention_impls.values())
    engine.destroy()
    return out, records, impls


def test_a_puts_record_counts_the_rows_its_tiles_hold(model_and_params, monkeypatch):
    assert pa.QUERY_TILE == 32, "FIRST_PUT is laid out for this"
    gathered, records, impls = serve(model_and_params)
    assert impls == {"xla_gather"}
    assert all(r["n_chunk_rows"] == r["n_chunk_tiles"] == 0 for r in records)

    monkeypatch.setenv("DS_PALLAS", "1")
    tiled, records, impls = serve(model_and_params)
    assert impls == {"pallas_paged"}
    assert tiled == gathered and all(len(tokens) == 6 for tokens in tiled.values())
    puts = [r for r in records if r["kind"] == "put"]
    bursts = [r for r in records if r["kind"] == "burst"]
    assert puts and bursts
    assert (puts[0]["n_chunk_rows"], puts[0]["n_chunk_tiles"]) == FIRST_PUT
    assert puts[0]["n_tokens"] == 52 and puts[0]["n_rows"] == 64
    # a decode row shares nothing, in a put or in a burst
    for r in puts[1:] + bursts:
        assert r["n_chunk_rows"] == r["n_chunk_tiles"] == 0
    assert {"n_chunk_rows", "n_chunk_tiles"} <= set(tracing.STEP_FIELDS)


def test_a_burst_program_lowers_a_row_a_grid_step(monkeypatch):
    """A burst holds one row a sequence by construction and says so
    (``query_tiles: None``): ``_paged_attend`` gives the kernel what the
    batch holds, so its kernel has no items among its operands where a put's
    of the same width has, and only the put's width is noted as tiled."""
    from deepspeed_tpu.inference.v2.model_runner import _paged_attend
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    monkeypatch.setenv("DS_PALLAS", "1")
    rows, blocks = 64, 4
    batch = {"token_seq": jnp.zeros(rows, jnp.int32), "token_pos": jnp.arange(rows, dtype=jnp.int32),
             "block_tables": jnp.zeros((9, blocks), jnp.int32)}
    q = jnp.zeros((rows, 2, 128), jnp.float32)
    kv = jnp.zeros((rows, 1, 128), jnp.float32)
    pool = jnp.zeros((1, 2 * blocks, 16, 128), jnp.float32)

    def lowered(tiles_of):
        impl = AttentionChoice()

        def step(q, kv, pool, batch):
            batch = dict(batch, query_tiles=tiles_of(batch))
            return _paged_attend(q, kv, kv, pool, pool, jnp.int32(0), batch, 128, impl=impl)[0]
        return jax.jit(step).lower(q, kv, pool, batch).as_text(), impl.tiled

    with_items, tiled = lowered(lambda b: pa.query_tiles(b["token_seq"], b["token_pos"], 8, rows,
                                                         blocks))
    assert tiled == {rows}
    without, tiled = lowered(lambda b: None)
    assert tiled == set()
    assert with_items != without
    assert without.count("xi32>") < with_items.count("xi32>")

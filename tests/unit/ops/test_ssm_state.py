"""The Mamba-2 state step (``ops/pallas/ssm_state``): the kernel,
interpreted on the CPU, against ``xla_ssm_state_step`` - the same
equations by slot over the whole layer - and what an in-place kernel must
leave alone: every slot no live row names and every other layer, bit for
bit.

The kernel's read ``S c`` goes through the MXU as bfloat16 pieces (the
state to 16 bits of mantissa: ~2.5e-6 of the read's norm); its update is
the float32 product to a place or two, so the state is held to 1e-6.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import ssm_state
from deepspeed_tpu.ops.pallas.ssm_state import (kernel_supported, ssm_state_step,
                                                xla_ssm_state_step)

LM, NS, H, P, N, G = 3, 7, 8, 8, 128, 2
S = 6                       # sequence rows a step; the last is padding's


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def step_rows(live, fresh=(), seed=0):
    """``live``: {sequence row: slot}; every other row names padding's slot
    0 and is fresh, as the engine's rows without a sequence are."""
    r = np.random.default_rng(seed)
    slot, here, new = np.zeros(S, np.int32), np.zeros(S, bool), np.ones(S, bool)
    for s, at in live.items():
        slot[s], here[s], new[s] = at, True, s in fresh
    return (jnp.asarray(slot), jnp.asarray(new), jnp.asarray(here),
            jnp.asarray(r.standard_normal((S, G, N)), jnp.float32),
            jnp.asarray(r.standard_normal((S, G, N)), jnp.float32),
            jnp.asarray(r.uniform(0.5, 1.0, (S, H)), jnp.float32),
            jnp.asarray(r.standard_normal((S, H, P)), jnp.float32))


@pytest.fixture(scope="module")
def pool():
    return jax.random.normal(jax.random.PRNGKey(1), (LM, NS, H, P, N), jnp.float32)


CASES = {"decode_only": ({0: 3, 1: 6, 2: 1, 3: 5, 4: 2}, ()),
         "fresh_sequences": ({0: 4, 1: 2, 2: 6}, (0, 2)),
         "absent_rows": ({1: 5, 3: 2}, (3,)),
         "no_live_row": ({}, ())}


@pytest.mark.parametrize("unit", ["mxu", "vpu"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_reference_and_touches_nothing_else(pool, case, unit):
    live, fresh = CASES[case]
    rows = step_rows(live, fresh)
    layer = jnp.int32(1)
    want_pool, want_seen = xla_ssm_state_step(pool, layer, *rows)
    got_pool, got_seen = ssm_state_step(pool, layer, *rows, unit=unit, interpret=True)
    pool, want_pool, got_pool = (np.asarray(x) for x in (pool, want_pool, got_pool))
    named = sorted(live.values())
    if named:
        assert rel_err(got_pool[1, named], want_pool[1, named]) < 1e-6
        assert rel_err(got_seen, want_seen) < (1e-5 if unit == "mxu" else 1e-6)
    absent = [s for s in range(S) if s not in live]
    assert not np.asarray(got_seen)[absent].any() and not np.asarray(want_seen)[absent].any()
    # in place: slots no live row names (padding's slot 0 among them), and every other
    # layer, are bitwise what they were - in the reference too
    others = [s for s in range(NS) if s not in named]
    for new in (got_pool, want_pool):
        assert np.array_equal(new[1, others], pool[1, others])
        assert np.array_equal(new[[0, 2]], pool[[0, 2]])


def test_a_fresh_sequence_never_sees_what_its_slot_held(pool):
    """NaNs in a slot whose next owner starts here: nothing of them in
    what it reads or leaves."""
    rows = step_rows({0: 4, 1: 2}, fresh=(0,))
    stale = pool.at[2, 4].set(jnp.nan)
    new, seen = ssm_state_step(stale, jnp.int32(2), *rows, interpret=True)
    assert np.isfinite(np.asarray(seen)).all() and np.isfinite(np.asarray(new[2, 4])).all()
    assert not np.asarray(seen[0]).any()


def test_the_layer_may_be_traced_inside_a_scan(pool):
    """As the step programs have it: the pool the scan's carry, the layer
    its counter, the call jitted around both."""
    rows = step_rows({0: 3, 2: 1, 4: 6}, fresh=(2,))

    def through(step):
        def run(pool):
            return jax.lax.scan(lambda pool, layer: step(pool, layer, *rows), pool,
                                jnp.arange(LM, dtype=jnp.int32))
        return jax.jit(run)(pool)

    want_pool, want_seen = through(xla_ssm_state_step)
    got_pool, got_seen = through(lambda *a: ssm_state_step(*a, interpret=True))
    assert rel_err(got_pool, want_pool) < 1e-6 and rel_err(got_seen, want_seen) < 1e-5
    others = [0, 2, 4, 5]
    assert np.array_equal(np.asarray(got_pool)[:, others], np.asarray(pool)[:, others])


@pytest.mark.parametrize("shape,groups,rows,ok", [
    ((5, 129, 128, 64, 128), 8, 129, True),         # nemotron3-super-agents
    ((2, 5, 8, 8, 128), 2, 5, True),
    ((2, 5, 8, 8, 16), 2, 5, False),                # N is not whole 128-lane vregs
    ((2, 5, 8, 12, 128), 2, 5, False),              # P is not whole sublane tiles
    ((2, 5, 8, 8, 128), 3, 5, False),               # heads do not divide into groups
    ((9, 137, 128, 64, 128), 1, 137, True),         # granite4-h-small-sessions: tiles in a group
    ((2, 5, 1, 8192, 128), 1, 5, False),            # one head's [P, N] over the fitted tile
    ((5, 2049, 128, 64, 128), 8, 2049, False),      # the rows' decays overflow SMEM
])
def test_kernel_supported_refuses_what_it_cannot_tile(shape, groups, rows, ok):
    assert kernel_supported(shape, groups, rows) is ok
    if not ok:
        args = (jax.ShapeDtypeStruct(shape, jnp.float32), jax.ShapeDtypeStruct((), jnp.int32),
                *(jax.ShapeDtypeStruct(s, d) for s, d in (
                    ((rows,), jnp.int32), ((rows,), jnp.bool_), ((rows,), jnp.bool_),
                    ((rows, groups, shape[4]), jnp.float32), ((rows, groups, shape[4]), jnp.float32),
                    ((rows, shape[2]), jnp.float32), ((rows, shape[2], shape[3]), jnp.float32))))
        with pytest.raises(ValueError, match="state step kernel needs"):
            jax.eval_shape(lambda *a: ssm_state_step(*a, interpret=False), *args)


@pytest.mark.parametrize("shape,groups,heads", [
    ((5, 129, 128, 64, 128), 8, 16),        # nemotron3-super-agents: a tile is a group, as it was
    ((9, 137, 128, 64, 128), 1, 16),        # one group of 128 heads: eight tiles of the same 512 KB
    ((2, 5, 8, 8, 128), 2, 4), ((2, 5, 64, 64, 128), 2, 32), ((2, 5, 96, 64, 128), 1, 16)])
def test_a_tile_is_a_group_where_four_fit_and_the_fitted_size_inside_one(shape, groups, heads):
    assert ssm_state.tile_heads(shape, groups) == heads


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_tiles_inside_a_group_are_the_reference(monkeypatch, case, groups):
    """A budget so small that a group's tile does not fit: ``Ht`` = 2 heads a
    tile, two or four tiles sharing a group's ``b`` and ``c`` row (one group:
    Granite 4.0-H's ``mamba_n_groups`` 1), against ``xla_ssm_state_step``."""
    head = P * N * 4
    monkeypatch.setattr(ssm_state, "TILE_VMEM_BYTES", 4 * (H // groups) * head - 1)
    monkeypatch.setattr(ssm_state, "FITTED_TILE_BYTES", 2 * head)
    slots = NS + 2 + groups           # a pool no other test traces the kernel at
    pool = jax.random.normal(jax.random.PRNGKey(3), (LM, slots, H, P, N), jnp.float32)
    assert ssm_state.tile_heads(pool.shape, groups) == 2
    live, fresh = CASES[case]
    slot, new, here, c, b, decay, left = step_rows(live, fresh, seed=5)
    rows = (slot, new, here, c[:, :groups], b[:, :groups], decay, left)
    want_pool, want_seen = xla_ssm_state_step(pool, jnp.int32(1), *rows)
    got_pool, got_seen = ssm_state_step(pool, jnp.int32(1), *rows, interpret=True)
    named = sorted(live.values())
    if named:
        assert rel_err(np.asarray(got_pool)[1, named], np.asarray(want_pool)[1, named]) < 1e-6
        assert rel_err(got_seen, want_seen) < 1e-5
    others = [s for s in range(slots) if s not in named]
    assert np.array_equal(np.asarray(got_pool)[1, others], np.asarray(pool)[1, others])
    assert np.array_equal(np.asarray(got_pool)[[0, 2]], np.asarray(pool)[[0, 2]])


def test_the_choice_follows_the_backend_and_the_shapes(monkeypatch):
    shape = (5, 129, 128, 64, 128)
    monkeypatch.delenv("DS_PALLAS", raising=False)
    assert ssm_state.state_step_impl(shape, 8, 129) == "xla"            # not a TPU
    monkeypatch.setenv("DS_PALLAS", "1")                               # interpreted: any shape
    assert ssm_state.state_step_impl(shape, 8, 129) == "pallas_ssm_state"
    assert ssm_state.state_step_impl((2, 5, 8, 8, 16), 2, 5) == "pallas_ssm_state"
    # compiled, the shapes decide
    import deepspeed_tpu.ops.pallas as kernels
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    assert ssm_state.state_step_impl(shape, 8, 129) == "pallas_ssm_state"
    assert ssm_state.state_step_impl((2, 5, 8, 8, 16), 2, 5) == "xla"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(fn, args, **jit_options):
    """``fn`` compiled for the described chip, the persistent cache off around
    it: a compile for a chip that is not attached is written there and cannot
    be read back, and the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn, **jit_options).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("shape,rows,groups", [
    ((5, 129, 128, 64, 128), 129, 8),       # nemotron3-super-agents
    ((9, 137, 128, 64, 128), 49, 1)],       # granite4-h-small-sessions: one group, tiles inside it
    ids=["nemotron", "granite"])
def test_mosaic_takes_the_cells_shape_and_the_pool_is_aliased(one_chip, shape, rows, groups):
    """Compiled for a described v5e (nothing runs): the kernel lowers at the
    cells' shapes, the pool comes back as the buffer it came in and the
    program holds no temporary of its size."""

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(shape, jnp.float32), sds((), jnp.int32), sds((rows,), jnp.int32),
            sds((rows,), jnp.bool_), sds((rows,), jnp.bool_),
            sds((rows, groups, 128), jnp.float32), sds((rows, groups, 128), jnp.float32),
            sds((rows, 128), jnp.float32), sds((rows, 128, 64), jnp.float32))
    compiled = _compiled(lambda *a: ssm_state_step(*a, interpret=False), args, donate_argnums=0)
    memory = compiled.memory_analysis()
    pool_bytes = int(np.prod(shape)) * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 100
    assert "ssm_state_step" in compiled.as_text()


@pytest.mark.parametrize("rows", [512, 64])
def test_mosaic_takes_the_paged_kernel_at_a_head_of_64(one_chip, rows):
    """Compiled for a described v5e (nothing runs; here beside the other
    such compile because one process loads the TPU's library): the paged
    attention kernel lowers at ``lfm2-24b-rag``'s shape - 32 query and 8
    key-value heads of 64, a pair of heads a 128-lane slice, 64-row blocks
    of 1024 bytes, a 136-block table - in the 512-row and the 64-row
    program, and the pools are read where they lie."""
    from deepspeed_tpu.ops.pallas.paged_attention import (kernel_supported,
                                                          paged_decode_attention,
                                                          smem_table_fits)
    assert kernel_supported(64, 64, 8) and smem_table_fits(rows, 136)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (2, 8705, 64, 512)
    args = (sds((rows, 32, 64), jnp.bfloat16), sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds((rows, 136), jnp.int32), sds((rows,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32))
    compiled = _compiled(lambda *a: paged_decode_attention(*a, interpret=False), args)
    assert "paged_decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < int(np.prod(pool)) * 2 // 100


# (query heads, key-value heads, head, block rows, pool blocks, table blocks) of the cells
# whose 512-row programs hold prompt chunks: lfm2-24b-rag, mistral7b-chat-r2 and
# jamba2-3b-chatloop (a query group of 20 over one key-value head: the first group that is
# no power of two; a tile lays 32 x 20 = 640 columns, five whole lane tiles)
TILED_SHAPES = {"head64-64row-blocks": (32, 8, 64, 64, 8705, 136),
                "head128-16row-blocks": (32, 8, 128, 16, 2560, 360),
                "group20-one-kv-head": (20, 1, 128, 64, 4097, 16),
                # solar-open2-reason: 64 query heads over 8 key-value heads, an 80-block table
                "group8-eight-kv-heads": (64, 8, 128, 64, 10241, 80)}


@pytest.mark.parametrize("shape", list(TILED_SHAPES))
def test_mosaic_takes_a_query_tile(one_chip, shape):
    """Compiled for a described v5e (nothing runs; in this file for the reason
    above): a 512-row ``put`` program's paged kernel with the step's query
    tiles laid inside the program (``paged_attention.query_tiles``) - the
    body that attends a tile of a chunk's rows through one walk of its
    context beside the one-row body - lowers at the three cells' shapes."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    H, Hkv, Dh, bs, NB, MB = TILED_SHAPES[shape]
    rows, n_seqs = 512, 64
    assert pa.kernel_supported(Dh, bs, Hkv) and pa.smem_table_fits(rows, MB)
    assert pa.query_tile_rows(rows, MB) == pa.QUERY_TILE

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, kc, vc, tab, pos, layer, live, seq):
        tiles = pa.query_tiles(seq, pos, n_seqs, live, MB)
        return pa.paged_decode_attention(q, kc, vc, tab, pos, layer, live, tiles, interpret=False)

    pool = (2, NB, bs, Hkv * Dh)
    args = (sds((rows, H, Dh), jnp.bfloat16), sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds((rows, MB), jnp.int32), sds((rows,), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32), sds((rows,), jnp.int32))
    compiled = _compiled(step, args)
    assert "paged_decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < int(np.prod(pool)) * 2 // 100


@pytest.mark.parametrize("rows", [512, 256])
def test_mosaic_takes_the_selective_scan_at_jambas_shape(one_chip, rows):
    """Compiled for a described v5e (nothing runs; in this file for the reason
    above): ``ops/pallas/selective_scan.selective_scan`` lowers at
    ``jamba2-3b-chatloop``'s shape in the 512-row and the 256-row program,
    the pool comes back as the buffer it came in, and the program holds no
    temporary of a slot-pool layer's size, let alone a ``[T, C, N]``."""
    from deepspeed_tpu.ops.pallas import selective_scan as ss
    shape, S = (26, 257, 16, 5120), 257
    assert ss.kernel_supported(shape, rows, S)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(shape, jnp.float32), sds((), jnp.int32), sds((rows,), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_),
            sds((rows, 5120), jnp.float32), sds((rows, 5120), jnp.float32),
            sds((rows, 16), jnp.float32), sds((rows, 16), jnp.float32),
            sds((16, 5120), jnp.float32))
    compiled = _compiled(lambda *a: ss.selective_scan(*a, interpret=False), args,
                         donate_argnums=0)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= int(np.prod(shape)) * 4
    # b and c laid a column a sublane, 2 x rows x 8 KB, and nothing of the pool's order
    assert memory.temp_size_in_bytes < 2 * rows * 16 * 128 * 4 + (1 << 20)
    assert rows * 5120 * 16 * 4 > 20 * memory.temp_size_in_bytes        # no [T, C, N]
    assert "selective_scan" in compiled.as_text()


@pytest.mark.parametrize("rows", [512, 192])
def test_mosaic_takes_the_delta_rule_at_solars_shape(one_chip, rows):
    """Compiled for a described v5e (nothing runs; in this file for the reason
    above): ``ops/pallas/kda.kda_delta_rule`` lowers at ``solar-open2-reason``'s
    shape in the 512-row and the 192-row program, the pool comes back as the
    buffer it came in, and the program holds no temporary of a slot's order
    beyond the rows' own operands, let alone a ``[T, H, d, d]``."""
    from deepspeed_tpu.ops.pallas import kda
    shape, S = (3, 193, 64, 128, 128), 193
    assert kda.kernel_supported(shape, rows, S)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds(shape, jnp.float32), sds((), jnp.int32), sds((rows,), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.bool_),
            *[sds((rows, 64, 128), jnp.float32)] * 4, sds((rows, 64), jnp.float32))
    compiled = _compiled(lambda *a: kda.kda_delta_rule(*a, interpret=False), args,
                         donate_argnums=0)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= int(np.prod(shape)) * 4
    # the decays and beta laid a row wide, the mask: a few rows' operands, nothing of the pool's
    assert memory.temp_size_in_bytes < 4 * rows * 64 * 128 * 4
    assert rows * 64 * 128 * 128 * 4 > 100 * memory.temp_size_in_bytes      # no [T, H, d, d]
    text = compiled.as_text()
    # both forms (PR 49: the row form, and the block form for runs of MIN_CHUNK_RUN rows or
    # more), each under a name the benchmark's reader finds (^kda_delta_rule)
    assert set(re.findall(r"kda_delta_rule\w*", text)) >= {"kda_delta_rule",
                                                           "kda_delta_rule_blocks"}
    assert f"f32[{rows},64,128,128]" not in text
    # a program of one row a sequence by construction (a burst's step) holds the row form alone
    alone = _compiled(lambda *a: kda.kda_delta_rule(*a, interpret=False, one_row_runs=True), args,
                      donate_argnums=0).as_text()
    assert "kda_delta_rule" in alone and "kda_delta_rule_blocks" not in alone
    # nothing but the two kernels (and what hands a buffer on) gives a result of the pool's shape
    made = [line for line in text.splitlines()
            if re.search(r"= (\([^)]*\) )?f32\[3,193,64,128,128\]", line)]
    assert made and all(re.search(r"parameter\(|custom-call\(|get-tuple-element\(|bitcast\(",
                                  line) for line in made), made


def test_the_tail_pool_rides_the_layer_loop_as_it_arrives(one_chip):
    """Compiled for a described v5e (nothing runs; in this file for the reason
    above): ``model_runner._conv_with_tail`` in a layer scan at
    ``jamba2-3b-chatloop``'s shape, the pool donated. The pool comes back as
    the buffer it came in; **nothing in the program has the pool's shape in
    another layout** - written a slab ``[K - 1, C]`` at a time, the compiler
    copies all 205 MB into a slot-major layout before the loop and back after
    it, 1.5 ms a step (PERF.md, PR 46) - and its temporaries are of the order
    of the table of rows, not of the pool."""
    from deepspeed_tpu.inference.v2 import model_runner
    L, NS, K, C, T = 26, 257, 4, 5120, 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pool, batch, streams, kernels, biases):
        rows = model_runner._SlotStep(None, batch, NS)

        def layer(carry, x):
            pool, at, total = carry
            acc, pool = model_runner._conv_with_tail(*x, pool, at, rows)
            return (pool, at + 1, total + acc), None

        start = (pool, jnp.int32(0), jnp.zeros((T, C), jnp.float32))
        return jax.lax.scan(layer, start, (streams, kernels, biases))[0][::2]

    batch = {"token_seq": sds((T,), jnp.int32), "token_pos": sds((T,), jnp.int32),
             "block_tables": sds((NS, 4), jnp.int32), "seq_state": sds((NS, 1), jnp.int32)}
    args = (sds((L, NS, K - 1, C), jnp.bfloat16), batch, sds((L, T, C), jnp.bfloat16),
            sds((L, K, C), jnp.bfloat16), sds((L, C), jnp.bfloat16))
    compiled = _compiled(step, args, donate_argnums=0)
    memory = compiled.memory_analysis()
    pool_bytes = L * NS * (K - 1) * C * 2
    assert memory.alias_size_in_bytes >= pool_bytes
    table_bytes = ((K - 1) * NS + T + 1) * C * 2
    assert memory.temp_size_in_bytes < 4 * table_bytes < pool_bytes // 3
    layouts = set(re.findall(rf"bf16\[{L},{NS},{K - 1},{C}\](\{{[^}}]*\}})", compiled.as_text()))
    assert len(layouts) == 1, layouts


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_mosaic_takes_the_row_kernels_at_mellums_shape(one_chip, dtype):
    """Compiled for a described v5e (nothing runs): the training exchange's two
    row kernels (``ops/pallas/moe_rows.py``) at ``mellum2-12b-moe8k-x4``'s
    shape - 32768 tokens of 2304, a layout of 102400 slots, 8 picks a token -
    each behind its pass that lays the source out a row at a time, and nothing
    of ``T k`` rows among the temporaries (the largest is the source's copy).
    A DMA of one row of the tiled ``[N, D]`` array itself is what Mosaic
    refuses (``Slice shape along dimension 0 must be aligned to tiling``)."""
    from deepspeed_tpu.ops.pallas import moe_rows
    T, D, S, k = 32768, 2304, 102400, 8
    width = jnp.dtype(dtype).itemsize

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    gather = _compiled(lambda x, i: moe_rows.gather_rows(x, i, None, True, False),
                       (sds((T, D), dtype), sds((S,), jnp.int32)))
    assert gather.memory_analysis().temp_size_in_bytes < 1.1 * T * D * width
    summed = _compiled(lambda y, s, w: moe_rows.gather_sum_rows(y, s, w, None, True, False),
                       (sds((S, D), dtype), sds((T, k), jnp.int32), sds((T, k), jnp.float32)))
    assert summed.memory_analysis().temp_size_in_bytes < 1.1 * S * D * width
    for text, name in ((gather.as_text(), "moe_rows_gather"), (summed.as_text(), "moe_rows_sum")):
        assert name in text and "moe_rows_pack" in text


@pytest.mark.parametrize("K,N", [(2304, 1792), (896, 2304)], ids=["dw1_dw3", "dw2"])
def test_mosaic_takes_the_kernels_that_add_where_they_write_at_mellums_shape(one_chip, K, N):
    """Compiled for a described v5e (nothing runs): ``gmm_dw_onto`` at the two
    shapes the exchange's written backward calls it with (``xp^T (dg | du)``,
    ``(v h)^T G``; 102400 slots, 16 experts) and ``gather_sum_rows_onto`` at the
    rows' - their accumulators **aliased** to their results (donated: no copy
    of ``[16, K, N]`` or ``[32768, 2304]`` float32 among the temporaries), the
    stack's two blocks of output and two of accumulator inside the VMEM the
    kernel asks for (16 MiB, the default, refuses them)."""
    from deepspeed_tpu.ops.pallas import moe_rows
    from deepspeed_tpu.ops.pallas.grouped_matmul import dw_tiles, gmm_dw_onto
    S, E, T, D, k, tm = 102400, 16, 32768, 2304, 8, 256

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dw = _compiled(lambda acc, fresh, x, dy, te, n: gmm_dw_onto(acc, fresh, x, dy, te, n,
                                                                *dw_tiles(K, N))[0],
                   (sds((E, K, N), jnp.float32), sds((E,), bool), sds((S, K), jnp.bfloat16),
                    sds((S, N), jnp.bfloat16), sds((S // tm,), jnp.int32), sds((), jnp.int32)),
                   donate_argnums=0)
    assert "gmm_dw" in dw.as_text()
    assert dw.memory_analysis().temp_size_in_bytes < E * K * N * 4 // 2
    if N == D:
        summed = _compiled(lambda acc, first, y, s: moe_rows.gather_sum_rows_onto(
            acc, first, y, s, None, True, False),
            (sds((T, D), jnp.float32), sds((), bool), sds((S, D), jnp.bfloat16),
             sds((T, k), jnp.int32)), donate_argnums=0)
        assert "moe_rows_sum" in summed.as_text()
        assert summed.memory_analysis().temp_size_in_bytes < 1.1 * S * D * 2


@pytest.mark.parametrize("seq_len,window", [(8192, 1024), (8192, None), (4096, None)],
                         ids=["mellum-band", "mellum-full", "mistral"])
def test_mosaic_takes_the_flash_kernels_at_the_training_cells_shapes(one_chip, seq_len, window):
    """Compiled for a described v5e (nothing runs): forward and backward of
    ``flash_attention`` at ``mellum2-12b-moe8k-x4``'s and ``mistral7b-zero3-x4``'s
    shapes - blocks of 1024, the backward's walked in pieces of 512 with a
    dynamic start, the classes' bodies - under the names the benchmark's readers look for,
    and with no segment operand: three kernels of three, six and six inputs."""
    import importlib
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    assert fa.flash_schedule(seq_len, window, backward=True)["piece"] == [512, 512]
    x = jax.ShapeDtypeStruct((1, seq_len, 32, 128), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal=True, window=window, force_pallas=True, interpret=False)
            .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    text = _compiled(grads, (x, x, x)).as_text()
    family = "flash_window_" if window is not None else "flash_attention_"
    for part, operands in (("fwd", 3), ("dkv", 6), ("dq", 6)):
        calls = re.findall(rf"%\w*{family}{part}[\w.]* = [^\n]*? custom-call\(([^)]*)\)", text)
        assert len(calls) == 1, f"{family}{part}"
        assert calls[0].count("%") == operands

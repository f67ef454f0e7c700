"""What the files of the served model kinds (``test_<kind>.py``) share that is
no test: the ``Case`` a kind states of itself, and the helpers every one of
them used to define for itself. The fixtures built from a case are in
``conftest.py``, the tests every kind passes in ``kind_conformance.py``."""

import dataclasses
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        KVTierConfig, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig, SpecDecodeConfig)
from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig, QuantizationConfig


class Refused(NamedTuple):
    """A value of the config that the model file refuses: the error names
    ``words`` (None: the field); ``also`` is what else has to change with it
    for the value to get as far as its own check."""
    field: str
    value: object
    words: Optional[str] = None
    also: dict = {}


class Plan(NamedTuple):
    """Steps of ``[(uid, row of the tokens, start, stop)]``, the prompts the
    engine is told - ``{uid: (row, length)}`` - and what a step's record must
    count: ``{index of the step: {counter: value or (least, most)}}``."""
    steps: list
    prompts: dict
    counts: dict = {}


class Burst(NamedTuple):
    """A prompt ``tokens[row][start:start + prompt]``, then bursts of
    ``bursts`` steps. ``counts``: what the last burst's record must count.
    A token is held to the reference's argmax where the reference's margin
    is over ``margin``, which it must be at ``clear`` positions at least."""
    row: int
    start: int
    prompt: int
    bursts: tuple
    counts: dict = {}
    margin: float = 0.0
    clear: Optional[int] = None     # None: at every position


class Gateway(NamedTuple):
    """Prompts ``(row, length)`` and the tokens asked for each. The
    reference's margin is over ``margin`` at every position of every stream,
    so that rounding cannot change a token (the smallest of the nine kinds'
    read 1.1e-4 when this was written)."""
    prompts: tuple = ((0, 75), (1, 9), (2, 40))
    new: int = 12
    margin: float = 5e-5


def two_prompts(counts=None):
    """One step holds a decode row, the end of one prompt and the start of
    another; a sequence of one row beside chunks."""
    return Plan([[(1, 0, 0, 32)], [(3, 2, 0, 29)],
                 [(3, 2, 29, 30), (1, 0, 32, 50), (2, 1, 0, 13)],
                 [(1, 0, 50, 51), (2, 1, 13, 40)], [(1, 0, 51, 52), (2, 1, 40, 41)]],
                {1: (0, 50), 2: (1, 40), 3: (2, 29)}, counts or {})


def one_long_prompt():
    """A 130-token prompt in chunks of 40 + 40 + 40 + 10 (its context crosses
    eight 16-token blocks), while two other sequences decode one token in
    each of the same steps."""
    steps = [[(2, 2, 0, 20), (3, 3, 0, 11)]]
    for i, (fed, n) in enumerate(((0, 40), (40, 40), (80, 40), (120, 10))):
        steps.append([(1, 1, fed, fed + n), (2, 2, 20 + i, 21 + i), (3, 3, 11 + i, 12 + i)])
    return Plan(steps, {1: (1, 130), 2: (2, 20), 3: (3, 11)})


def two_sequences(counts):
    """One step of 20 rows of a prompt of 40 and a whole prompt of 9."""
    return Plan([[(60, 2, 0, 20), (61, 3, 0, 9)]], {60: (2, 40), 61: (3, 9)}, counts)


def uniform_tokens(seed, shape):
    return lambda: np.random.default_rng(seed).integers(0, 256, shape, dtype=np.int32)


def two_pool_subsystems(parallel="tensor_parallel_degree"):
    """Every subsystem that reads, moves or shards two pools of keys and
    values, each with the settings that switch it on."""
    return (("prefix cache", {"prefix_cache": PrefixCacheConfig(enabled=True)}),
            ("KV tier", {"kv_tier": KVTierConfig(enabled=True)}),
            ("speculative decoding", {"spec_decode": SpecDecodeConfig(enabled=True)}),
            ("LoRA serving", {"lora": LoRAServingConfig(enabled=True)}),
            ("weight-only quantization",
             {"quantization": QuantizationConfig(quantization_mode="wf6af16")}),
            ("tensor/expert-parallel sharding", {parallel: 2}))


@dataclasses.dataclass(frozen=True)
class Case:
    """A served model kind as its file states it; ``kind_conformance.py``
    says what each field is held to."""
    preset: str
    reference: Callable     # (params, ids [1, S], cfg, prompt length) -> logits [1, S, V]
    refused: tuple          # rows of Refused
    prefill: tuple          # rows of (prompt, steps, chunks), of tokens[0]
    plans: dict             # name -> Plan: sequences side by side in a step
    burst: Burst
    records: Plan           # its last step's record is read; .counts: {counter: value}
    step_counts: tuple      # every counter of a step's record, in the record's order
    scopes: tuple           # named scopes the step program's text must hold
    subsystems: tuple = two_pool_subsystems()
    gateway: Gateway = Gateway()
    cuts: tuple = ()        # rows as ``prefill``'s, of tokens[1]: a prompt cut at every offset
    preset_over: dict = dataclasses.field(default_factory=dict)
    # the engine's settings: kv_block_size, num_kv_blocks, max_ragged_batch_size,
    # max_ragged_sequence_count (and max_tracked_sequences), max_context
    block: int = 16
    blocks: int = 96
    rows: int = 32
    sequences: int = 4
    context: int = 192
    rng: int = 5
    tokens: Callable = uniform_tokens(3, (4, 192))
    tol: float = 2e-5
    # a kind with slots: the entries of ``engine.state_extra``; a slot's bytes by the config;
    # the name its state step's kernel bears in a step record, and the shared tests that run
    # under it too
    state_extra: tuple = ()
    slot_bytes: Optional[int] = None
    state_step: Optional[str] = None
    kernel_tests: tuple = ()


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def engine_config(case, **over):
    """The case's engine settings. ``over``: other values of them, by the
    case's names (and ``tracked`` for ``max_tracked_sequences``), and fields
    of ``RaggedInferenceEngineConfig``."""
    s = {k: over.pop(k, getattr(case, k))
         for k in ("block", "blocks", "rows", "sequences", "context")}
    return RaggedInferenceEngineConfig(
        kv_block_size=s["block"], num_kv_blocks=s["blocks"],
        state_manager=DSStateManagerConfig(
            max_ragged_batch_size=s["rows"], max_ragged_sequence_count=s["sequences"],
            max_tracked_sequences=over.pop("tracked", s["sequences"]),
            max_context=s["context"]), **over)


def second_engine(case, engine, cfg=None, dtype=jnp.float32, **over):
    """Another engine on ``engine``'s weights."""
    return InferenceEngineV2(params=engine.params, model_config=cfg or engine.model_config,
                             config=engine_config(case, **over), dtype=dtype)


def jitted_reference(case, params, cfg, length):
    """``(seq, prompt=len(seq))`` -> the reference's logits [len(seq), V]. One
    compiled program for every length a test asks for: the sequence is padded
    to ``length`` tokens, which a causal model's rows before the padding
    cannot see."""
    program = jax.jit(lambda params, ids, prompt: case.reference(params, ids, cfg, prompt))

    def logits(seq, prompt=None):
        padded = np.zeros((1, length), np.int32)
        padded[0, :len(seq)] = seq
        prompt = jnp.int32(len(seq) if prompt is None else prompt)
        return np.asarray(program(params, jnp.asarray(padded), prompt)[0, :len(seq)])
    return logits


def serve(engine, plan, prompts=None):
    """``plan``: steps of ``[(uid, tokens)]`` -> {uid: [the logits row of
    each of its steps]}; a uid's first appearance tells the engine its
    prompt (``prompts[uid]``; its first chunk where none is given), as the
    scheduler does."""
    rows = {}
    for step in plan:
        for u, t in step:
            if engine.state_manager.query(u) is None:
                engine.prefix_match(u, (prompts or {}).get(u, t))
        out = engine.put([u for u, _ in step], [t for _, t in step])
        for (u, _), row in zip(step, out):
            rows.setdefault(u, []).append(row)
    return rows


def count(shapes):
    """The parameters of a tree of shapes (``param_shapes(cfg)``)."""
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


def slot_batch(rows, n_rows, slots):
    """A step's batch for a mixer called alone. ``rows``: [(sequence row,
    first position, length)] in batch order; ``slots``: the sequence rows'."""
    seq = np.concatenate([np.full(n, s, np.int32) for s, _, n in rows])
    pos = np.concatenate([np.arange(f, f + n, dtype=np.int32) for _, f, n in rows])
    state = np.zeros((n_rows, 1), np.int32)
    state[:len(slots), 0] = slots
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
            "block_tables": jnp.zeros((n_rows, 1), jnp.int32), "seq_state": jnp.asarray(state)}


def counted(counts, want):
    """``counts`` holds every counter of ``want`` at its value, or within
    its ``(least, most)``."""
    for name, value in want.items():
        least, most = value if isinstance(value, tuple) else (value, value)
        assert least <= counts[name] <= most, (name, counts[name], value)

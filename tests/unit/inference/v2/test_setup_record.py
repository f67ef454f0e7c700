"""What an engine says of its own set-up (utils/tracing.py): the ``setup``
record of its constructor, and one row of the build table a program, written
when the program's first step builds it and left alone after."""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.engine_v2 import logger as engine_logger
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.utils import tracing

PHASES = ["ds.setup.params", "ds.setup.pools", "ds.setup.kind", "ds.setup.programs"]
PROMPT = (np.arange(1, 11) % 250).astype(np.int32)


@pytest.fixture(scope="module")
def engine_and_lines():
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    engine_logger.addHandler(handler)
    try:
        engine = InferenceEngineV2(
            model=build_llama("debug"), dtype=jnp.float32,
            config=RaggedInferenceEngineConfig(
                kv_block_size=8,
                state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                                   max_ragged_sequence_count=4,
                                                   max_tracked_sequences=4, max_context=64)))
        yield engine, lines
    finally:
        engine_logger.removeHandler(handler)
    engine.destroy()


def rows_of(engine):
    return [row for row in tracing.snapshot()["builds"] if row["engine"] == engine.trace_id]


def test_the_constructor_is_a_setup_record_in_four_phases(engine_and_lines):
    engine, lines = engine_and_lines
    (record,) = [r for r in tracing.RECORDER.setups if r.engine == engine.trace_id]
    assert (record.kind, record.program, record.caused_by) == ("setup", "engine", 0)
    assert [name for name, _, _ in record.phases] == PHASES
    stamps = [t for _, enter, exit_ in record.phases for t in (enter, exit_)]
    assert stamps == sorted(stamps) and stamps[1:-1:2] == stamps[2::2]
    assert stamps[-1] - stamps[0] >= 0.95 * (record.end_ns - record.start_ns)
    assert record.process_age_ns > 0
    # the weights were made inside it, by a program the constructor compiled itself
    (row,) = [row for row in rows_of(engine) if row["kind"] == "setup"]
    assert row["seq"] == record.seq and row["compiles"] >= 1
    assert "init_cast" in [name for name, *_ in row["functions"]]
    assert 0 < row["trace_ns"] + row["lower_ns"] + row["backend_ns"] == record.build.ns \
        <= record.compile_ns <= record.end_ns - record.start_ns
    (line,) = [line for line in lines if "max_tokens=32" in line]
    assert "setup_s=" in line and "params=" in line and "programs=" in line \
        and "process_age_s=" in line


def test_a_programs_first_step_writes_its_row_and_its_second_leaves_the_table(engine_and_lines):
    engine, lines = engine_and_lines
    engine.put([1], [PROMPT], sample="greedy")
    built = rows_of(engine)
    (row,) = [row for row in built if row["kind"] == "put"]
    assert (row["program"], row["builds"], row["compiles"]) == ("32", 1, 1)
    assert row["seq"] == engine.last_step.seq and engine.last_step.build.compiles == 1
    # (which function's trace took longest is the process's history: a first use is slow)
    assert {name: times for name, times, _, _ in row["functions"]}["step_greedy"] == 1
    (line,) = [line for line in lines if "built in" in line]
    assert line.startswith("InferenceEngineV2: the 32-row program built in ")
    assert "(trace " in line and "cache none)" in line and "; most traced: " in line
    engine.put([2], [PROMPT], sample="greedy")
    assert engine.last_step.build is None and rows_of(engine) == built
    assert len([line for line in lines if "built in" in line]) == 1
    # what nobody's record was open for is the caller's own
    outside = tracing.RECORDER.outside.ns
    jax.jit(lambda x: x * 5 + len(lines))(jnp.ones(3)).block_until_ready()
    assert tracing.RECORDER.outside.ns > outside and rows_of(engine) == built
    summary = tracing.setup_summary(engine.trace_id)
    assert summary["build"]["programs"] == 1 and summary["build"]["compiles"] == 1
    assert summary["init_ns"] > 0 and list(summary["phases_ns"]) == PHASES

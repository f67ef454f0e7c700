"""What every model kind served through the v2 engine must do, written once.

**How a kind enters.** Its ``test_<kind>.py`` states a module-level ``CASE``
(``kinds.Case``: the preset, the engine's settings, the seeds, its plain
reference, its tolerance, the tables of what it refuses, its traffic and what
its step records must count) and derives the classes below that fit it::

    class TestServing(conformance.Slots, conformance.NotKV):
        pass

The fixtures (``conftest.py``) read ``CASE``; a table of the case becomes the
parameters of the test that names it. Beside the class the file keeps what
only that kind has: catalog shapes, its mixers called alone, the mutations of
its reference. A kind's own assertion inside a shared test is a method of
this file that does nothing (``retired``, ``idle``, ``around_the_traffic``,
...) and that the kind's class overrides - never a branch on the kind here.

**Why inherited into a file a kind, not one file.** The driver runs tier-1
with ``-n 6 --dist loadfile``: a file is one worker's, and one file of every
kind's tests would be the longest of the run. **The rule** (ROADMAP D11): a
``model_config`` PR's kind file holds its case and what only it has; what a
second kind would say again goes here, once.

Not collected by its name: the classes bear no ``Test`` and are collected
where a ``test_<kind>.py`` derives them. The gateway's test serves a second
engine on the module's engine's weights, so the module's engine outlives it.
"""

import dataclasses
import re

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
from deepspeed_tpu.utils import tracing

from unit.inference.v2.kinds import counted, engine_config, rel_err, second_engine, serve


def sliced(tokens, steps):
    """A plan's steps with the tokens themselves."""
    return [[(u, tokens[r][a:b]) for u, r, a, b in step] for step in steps]


def announced(tokens, prompts):
    return {u: tokens[r][:n] for u, (r, n) in prompts.items()}


def fed_by(steps):
    """{uid: the tokens a plan's steps feed it in all}."""
    return {u: stop for step in steps for u, _, _, stop in step}


class Served:
    """Every kind. The engine's logits against the case's plain reference, at
    the case's tolerance, through the traffic of the case's tables."""

    # ---- what a kind's class overrides to look at its own state
    def retired(self, engine, uid, fed):
        """A sequence of a shared test, before it is flushed; ``fed``: the
        tokens the shared test gave it (the kind's own hooks may have fed more)."""

    def idle(self, engine):
        """The engine after every sequence of a shared test was flushed."""

    def after_the_rows(self, engine, uid, row, fed, reference):
        """``test_prefill_in_chunks_then_decode``'s sequence, ``fed`` tokens
        of ``row``, after its last row."""

    def recorded(self, engine, tokens):
        """After the record of ``case.records``' last step was read."""

    def around_the_traffic(self, gateway, served):
        """The gateway's test runs this up to its first ``yield`` before a
        request is submitted, to its second when every stream is in and the
        gateway still open, and to its end after the shutdown (which takes
        the engine's pools: keep what is to be read then)."""
        yield
        yield

    def retire(self, engine, fed):
        """``fed``: {uid: the tokens the test gave it}."""
        for uid, n in fed.items():
            self.retired(engine, uid, n)
            engine.flush(uid)
        self.idle(engine)

    # ---- the tests
    def test_what_is_not_implemented_is_refused_by_name(self, model, refused):
        with pytest.raises(ValueError, match=refused.words or refused.field):
            dataclasses.replace(model.config, **{refused.field: refused.value}, **refused.also)

    def test_each_subsystem_that_does_not_serve_the_kind_refuses_it_by_name(self, case, model,
                                                                             subsystem):
        """At construction, naming itself, the state's kind and the model's."""
        name, over = subsystem
        with pytest.raises(NotImplementedError, match=name) as e:
            InferenceEngineV2(model=model, config=engine_config(case, **over), dtype=jnp.float32)
        kind = model_runner.kind_of(model.config)
        assert repr(kind.state_kind) in str(e.value) and repr(kind.name) in str(e.value)

    def test_prefill_in_chunks_then_decode(self, case, engine, tokens, reference, prefill):
        """A prompt whole or in chunks, then decode rows: each step's logits
        are the reference's at the step's last position."""
        self.prefilled(case, engine, reference, tokens[0], 7, *prefill)

    def prefilled(self, case, engine, reference, row, uid, prompt, steps, chunks):
        assert sum(chunks) == prompt
        seq = row[:prompt + steps]
        edges = np.cumsum([0, *chunks]).tolist() + [prompt + j + 1 for j in range(steps)]
        rows = serve(engine, [[(uid, seq[a:b])] for a, b in zip(edges, edges[1:])],
                     {uid: seq[:prompt]})[uid]
        want = reference(seq, prompt)
        errs = [rel_err(row, want[end - 1]) for row, end in zip(rows, edges[1:])]
        assert len(errs) == len(chunks) + steps and max(errs) < case.tol, errs
        self.after_the_rows(engine, uid, row, len(seq), reference)
        self.retire(engine, {uid: len(seq)})

    def test_sequences_side_by_side_in_a_step(self, case, step_engine, state_step, tokens,
                                              reference, plan):
        """Steps that hold decode rows beside the chunks of one prompt or of
        two: every row a step returns is the reference's for its sequence,
        and the steps' records count what the plan says."""
        engine, plan = step_engine, case.plans[plan]
        prompts, rows, ends = announced(tokens, plan.prompts), {}, {}
        for i, step in enumerate(sliced(tokens, plan.steps)):
            for uid, out in serve(engine, [step], prompts).items():
                rows.setdefault(uid, []).extend(out)
            counted(engine.last_step.counts, plan.counts.get(i, {}))
            for uid, _, _, stop in plan.steps[i]:
                ends.setdefault(uid, []).append(stop)
        assert engine.last_step.state_step == state_step
        self.retire(engine, fed_by(plan.steps))
        errs = []
        for uid, (row, prompt) in plan.prompts.items():
            want = reference(tokens[row][:ends[uid][-1]], prompt)
            errs += [rel_err(got, want[end - 1]) for got, end in zip(rows[uid], ends[uid])]
        assert len(errs) == sum(len(step) for step in plan.steps) and max(errs) < case.tol, errs

    def test_decode_bursts_carry_every_state(self, case, engine, tokens, reference):
        """A prompt, then bursts (one program each, the pools and whatever
        else the kind keeps carried through its scan): the logits of one more
        step read every row the bursts wrote, and the bursts' tokens are the
        reference's greedy ones wherever its margin is clear of rounding."""
        b, uid = case.burst, 50
        prompt = tokens[b.row][b.start:b.start + b.prompt]
        out = serve(engine, [[(uid, prompt[at:at + case.rows])]
                             for at in range(0, b.prompt, case.rows)], {uid: prompt})[uid][-1]
        generated = [int(np.argmax(out))]
        for k in b.bursts:
            generated += [int(t) for t in engine.decode_burst([uid], generated[-1:], k)[:, 0]]
        counted(engine.last_step.counts, b.counts)
        after = engine.put([uid], [np.asarray(generated[-1:], np.int32)])[0]
        self.retire(engine, {uid: b.prompt + len(generated)})
        want = reference(np.concatenate([prompt, np.asarray(generated, np.int32)]), b.prompt)
        assert rel_err(after, want[-1]) < case.tol
        told = want[b.prompt - 1:-1]
        top2 = np.sort(told, axis=-1)[:, -2:]
        clear = (top2[:, 1] - top2[:, 0]) > b.margin
        assert clear.sum() >= (len(generated) if b.clear is None else b.clear)
        assert (np.argmax(told, axis=-1)[clear] == np.asarray(generated)[clear]).all()

    def test_step_records_carry_the_counts_and_the_scopes_are_in_the_program(self, case, engine,
                                                                             tokens):
        plan = case.records
        steps, prompts = sliced(tokens, plan.steps), announced(tokens, plan.prompts)
        for uid, prompt in prompts.items():
            engine.prefix_match(uid, prompt)
        serve(engine, steps[:-1])
        syncs = engine.host_syncs
        serve(engine, steps[-1:])
        assert engine.host_syncs - syncs == 2            # as for any model kind: pack + fetch
        counts = engine.last_step.counts
        assert tuple(counts) == engine.kind.step_counts == case.step_counts
        counted(counts, plan.counts)
        last = tracing.snapshot()["steps"][-1]
        assert last["counts"] == counts
        assert last["state_step"] == ("xla" if case.state_step else None)
        self.recorded(engine, tokens)
        self.retire(engine, fed_by(plan.steps))
        lowered = engine._step.lower(engine.params, engine.kv_cache.k, engine.kv_cache.v,
                                     engine.state_extra, engine._batch.finalize_packed()).as_text(
                                         debug_info=True)
        for scope in case.scopes:
            assert scope in lowered, scope

    def test_the_gateway_serves_it_through_the_same_scheduler(self, case, engine, tokens,
                                                              reference):
        """Behind ``ServingGateway`` (admission, SplitFuse scheduler, decode
        bursts): the greedy stream of each request is the one the engine
        gives alone, and the reference's, whose margin at every position of
        it is clear of rounding; prompts longer than the token budget
        included; the scheduler tells the engine each prompt's length before
        its first chunk."""
        from deepspeed_tpu.serving import ServingConfig, ServingGateway
        prompts, new = [tokens[r][:n] for r, n in case.gateway.prompts], case.gateway.new
        alone = [self.alone(engine, 500 + i, prompt, new, case.rows)
                 for i, prompt in enumerate(prompts)]
        served = second_engine(case, engine)
        gateway = ServingGateway(served, config=ServingConfig(default_max_new_tokens=new))
        watch = self.around_the_traffic(gateway, served)
        try:
            next(watch)
            handles = [gateway.submit(p, max_new_tokens=new) for p in prompts]
            streams = [[int(t) for t in h.result(timeout=300)] for h in handles]
            next(watch)
        finally:
            gateway.shutdown()
        assert next(watch, None) is None
        assert streams == alone
        for prompt, stream in zip(prompts, streams):
            full = np.concatenate([prompt, np.asarray(stream[:-1], np.int32)])
            told = reference(full, len(prompt))[len(prompt) - 1:]
            top2 = np.sort(told, axis=-1)[:, -2:]
            assert (top2[:, 1] - top2[:, 0] > case.gateway.margin).all()   # clear of rounding
            assert stream == [int(t) for t in np.argmax(told, axis=-1)]
        records = [r for r in tracing.snapshot()["steps"] if r["engine"] == served.trace_id]
        assert {"burst", "put"} <= {r["kind"] for r in records}
        assert all(r["counts"] is not None for r in records if r["kind"] in ("burst", "put"))

    def test_a_pool_run_dry_preempts_by_recompute_and_every_stream_stands(self, case, engine,
                                                                          tokens):
        """The gate lets a request in on its prompt's blocks; here into a
        pool of the prompts' blocks and not one more (the gate's reserve
        taken away: the scheduler's half must hold alone), each prompt a
        token short of its last block's end. After one decode step every row
        needs a block that is not there: rows wait, and one request at a time is given up,
        flushed - blocks, slot, window ring - and made again from its tokens.
        No ``put`` is asked for a block the pool has not got, and each greedy
        stream is the one the request gives with the pool to itself."""
        from deepspeed_tpu.serving import ServingConfig, ServingGateway
        new, block = case.gateway.new, case.block
        prompts = [tokens[r][:-(-(n + 1) // block) * block - 1] for r, n in case.gateway.prompts]
        # one step holds every prompt, so that the rows reach their blocks' ends together
        rows = -(-sum(len(p) for p in prompts) // case.rows) * case.rows
        served = second_engine(case, engine, rows=rows,
                               blocks=1 + sum(-(-(len(p) + 1) // block) for p in prompts))
        gateway = ServingGateway(served, auto_start=False, config=ServingConfig(
            default_max_new_tokens=new, max_burst=4))
        gateway.gate.reserve = lambda live: 0

        def streams(handles):
            for _ in range(600):
                if all(h.done for h in handles):
                    return [[int(t) for t in h.result(timeout=1)] for h in handles]
                gateway._pump_once()
            raise AssertionError("the requests did not end")

        seen_at, preempt = [], gateway.scheduler.preempt_for_room

        def watched():
            seen = {uid: served.query(uid)[0] for uid in gateway._active}
            request = preempt()
            seen_at.append(seen[request.uid])
            assert request.recomputed == seen_at[-1] >= block
            return request
        gateway.scheduler.preempt_for_room = watched
        watch = self.around_the_traffic(gateway, served)
        try:
            next(watch)
            alone = [streams([gateway.submit(p)])[0] for p in prompts]
            assert not seen_at and gateway.snapshot()["counters"]["rows_held_back"] == 0
            handles = [gateway.submit(p) for p in prompts]
            gateway._pump_once()
            assert gateway.gate.active == len(prompts) and gateway.gate.headroom() == 0
            together = streams(handles)
            counters = gateway.snapshot()["counters"]
            assert gateway.gate.active == 0 and gateway.gate.committed_blocks == 0
            next(watch)
        finally:
            gateway.shutdown()
        assert next(watch, None) is None
        assert together == alone and all(len(s) == new for s in together)
        assert counters["rows_held_back"] >= len(prompts) and counters["failed"] == 0
        assert counters["preempted_for_room"] == len(seen_at) >= 1
        assert counters["recomputed_tokens"] == sum(seen_at)
        assert counters["completed"] == 2 * len(prompts)

    def alone(self, engine, uid, prompt, new, rows):
        """The greedy stream of ``new`` tokens that ``engine`` gives ``prompt``
        by itself: chunks of ``rows``, then a row a step."""
        out = serve(engine, [[(uid, prompt[at:at + rows])] for at in range(0, len(prompt), rows)],
                    {uid: prompt})[uid][-1]
        stream = [int(np.argmax(out))]
        while len(stream) < new:
            out = engine.put([uid], [np.asarray(stream[-1:], np.int32)])[0]
            stream.append(int(np.argmax(out)))
        self.retire(engine, {uid: len(prompt) + new - 1})
        return stream


class ChunkCuts(Served):
    """A kind whose layers carry something over a chunk's edge that is no row
    of a pool - a convolution's tail, a kernel's block of rows, a window's
    ring: a prompt cut at every offset of it."""

    def test_a_chunk_cut_at_every_offset(self, case, step_engine, state_step, tokens,
                                         reference, cut):
        prompt, steps, chunks = cut
        self.prefilled(case, step_engine, reference, tokens[1], 9, prompt, steps, chunks)
        assert step_engine.last_step.state_step == state_step


class Slots(Served):
    """A kind that keeps a slot of state a sequence beside its blocks."""

    def idle(self, engine):
        assert engine.slot_pool.free_slots == engine.slot_pool.slots   # every slot came back

    def around_the_traffic(self, gateway, served):
        pool = served.slot_pool
        yield
        yield
        assert pool.free_slots == pool.slots

    def test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero(
            self, case, step_engine, state_step, tokens, reference):
        """The pool is not cleared between owners: the state the last owner
        left is still in the slot when the next sequence's first rows run, and
        they take it as zero. Were it carried, every logit would move."""
        engine = step_engine
        assert engine.state_kind == engine.kind.state_kind
        assert set(engine.state_extra) == set(case.state_extra)
        assert engine.slot_pool.free_slots == engine.slot_pool.slots == case.sequences
        serve(engine, [[(11, tokens[2][:30])]])
        slot = engine.state_manager.query(11).state_row[0]
        assert slot >= 1 and engine.slot_pool.free_slots == case.sequences - 1
        engine.flush(11)
        for name in engine.kind.slot_state:          # what a missing reset would carry
            assert np.abs(np.asarray(engine.state_extra[name][:, slot])).max() > 1e-3, name
        seq = tokens[3][:32]
        rows = serve(engine, [[(12, seq[:2])], [(12, seq[2:31])], [(12, seq[31:32])]],
                     {12: seq[:31]})[12]
        assert engine.state_manager.query(12).state_row[0] == slot           # the same slot
        assert engine.last_step.state_step == state_step
        assert set(engine.state_step_impls.values()) == (
            {state_step} if case.state_step else set())
        self.retire(engine, {12: len(seq)})
        want = reference(seq, 31)
        assert max(rel_err(r, want[p]) for r, p in zip(rows, (1, 30, 31))) < case.tol
        # a slot's bytes are every entry's: what the gate and the start-up line count
        held = [engine.state_extra[name] for name in engine.kind.slot_state]
        assert engine.slot_pool.bytes_per_slot == sum(
            x.shape[0] * int(np.prod(x.shape[2:])) * x.dtype.itemsize for x in held)
        assert engine.slot_pool.bytes_per_slot == case.slot_bytes

    def test_the_gate_on_slots_admits_no_more_sequences_than_slots(self, case, engine, tokens):
        uids = range(30, 30 + case.sequences)
        for uid in uids:
            serve(engine, [[(uid, tokens[0][:5])]])
        assert engine.slot_pool.free_slots == 0
        with pytest.raises(Exception):
            serve(engine, [[(uids[-1] + 1, tokens[0][:5])]])
        self.retire(engine, dict.fromkeys(uids, 5))

    def test_a_prompt_the_engine_was_not_told_is_refused_by_name(self, engine, tokens):
        """How a sequence starts depends on its slot, and for some kinds on
        the whole prompt's length: ``put`` takes no chunk of a sequence that
        ``prefix_match`` did not announce, and tracks nothing of it."""
        with pytest.raises(ValueError, match="needs the whole prompt.*prefix_match"):
            engine.put([70], [tokens[0][:5]])
        assert engine.state_manager.query(70) is None
        self.idle(engine)


class NotKV(Served):
    """A kind whose state is not two pools of keys and values alone."""

    def test_suspend_is_refused_by_name(self, engine, tokens):
        serve(engine, [[(70, tokens[0][:5])]])
        words = "suspend/resume export.*" + re.escape(repr(engine.kind.state_kind))
        with pytest.raises(NotImplementedError, match=words):
            engine.suspend(70)
        self.retire(engine, {70: 5})

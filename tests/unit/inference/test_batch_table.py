"""The table of live sequences (``ragged_manager``) against the packer it replaced.

A step's block tables, state rows and positions are gathered with numpy from a
table the state manager keeps between steps; until PR 47 every packer wrote
them sequence by sequence. **The reference here is that per-sequence packer,
copied from the parent commit**: ``ParentWrapper.insert_sequence`` and the
four engine methods below it (``put``, ``_validate_burst``, ``_dispatch_burst``,
``verify_burst``, comments and docstrings dropped, nothing else changed). A
pair of engines - this tree's, and one with the parent's methods bound over
its own - is driven through the same random schedule with the compiled
programs replaced by a recorder, and the metadata vector each hands its
program (``finalize_packed``'s, the burst's and the verify program's
``meta``) must be byte for byte the same at every step, with the pools and
the descriptors beside them. No program is compiled: seconds."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import models
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        PrefixCacheConfig, RaggedInferenceEngineConfig,
                                        SpecDecodeConfig)
from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig
from deepspeed_tpu.inference.v2.engine_v2 import (_burst_ctx_tokens, _burst_layout,
                                                  _verify_layout, pack_sample_meta)
from deepspeed_tpu.inference.v2.ragged import (BlockedKVCache, DSStateManager,
                                               RaggedBatchWrapper)
from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK
from deepspeed_tpu.utils import tracing

BLOCK, MAX_SEQS, MAX_TOKENS, MAX_CTX, TRACKED = 8, 6, 48, 64, 8


# ====================================================================== the parent's packer
class ParentWrapper(RaggedBatchWrapper):
    """``clear`` and ``insert_sequence`` as the parent had them: new arrays a
    step, nine small writes a sequence."""

    def clear(self):
        self.token_ids = np.zeros(self.max_tokens, np.int32)
        self.token_seq = np.full(self.max_tokens, self.max_seqs, np.int32)
        self.token_pos = np.zeros(self.max_tokens, np.int32)
        self.block_tables = np.full((self.max_seqs + 1, self.max_blocks), NULL_BLOCK, np.int32)
        self.last_index = np.zeros(self.max_seqs, np.int32)
        self.seq_valid = np.zeros(self.max_seqs, bool)
        if self.lora:
            self.seq_adapters = np.zeros(self.max_seqs + 1, np.int32)
        if self.seq_rows:
            self.seq_state = np.zeros((self.max_seqs + 1, self.seq_rows), np.int32)
        self._cursor = 0
        self._order = []

    def insert_sequence(self, desc, tokens):
        n = len(tokens)
        if self._cursor + n > self.max_tokens:
            raise ValueError(f"ragged batch overflow: {self._cursor}+{n} > {self.max_tokens}")
        if desc.slot >= self.max_seqs:
            raise ValueError(f"slot {desc.slot} out of range")
        if len(desc.blocks) > self.max_blocks:
            raise ValueError(f"sequence {desc.uid} owns {len(desc.blocks)} blocks > "
                             f"max_blocks_per_seq={self.max_blocks} (context overflow)")
        sl = slice(self._cursor, self._cursor + n)
        self.token_ids[sl] = np.asarray(tokens, np.int32)
        self.token_seq[sl] = desc.slot
        self.token_pos[sl] = desc.seen_tokens + np.arange(n, dtype=np.int32)
        blocks = desc.blocks
        self.block_tables[desc.slot, :len(blocks)] = blocks
        self.last_index[desc.slot] = self._cursor + n - 1
        self.seq_valid[desc.slot] = True
        if self.lora:
            self.seq_adapters[desc.slot] = getattr(desc, "adapter_slot", 0)
        if self.seq_rows:
            self.seq_state[desc.slot] = desc.state_row
        self._cursor += n
        self._order.append(desc.slot)


def parent_put(self, batch_uids, batch_tokens, do_checks=True, sample=None):
    with tracing.step("put", engine=self.trace_id, uids=tuple(batch_uids)) as rec:
        with tracing.phase("engine.pack"):
            mode, specs = self._classify_sample(sample, len(batch_uids))
            if self.structured is not None and \
                    any(self.structured.bound(u) for u in batch_uids):
                if mode == "logits":
                    raise RuntimeError(
                        "constrained sequences sample on device — call put "
                        "with sample='greedy' or a sampling spec, not the "
                        "raw-logits path")
                mode = "packed"
                specs = specs if specs is not None else [None] * len(batch_uids)
            self.count_host_sync()
            batch_tokens = [np.atleast_1d(np.asarray(t, np.int32)) for t in batch_tokens]
            total = sum(len(t) for t in batch_tokens)
            if total > self.max_tokens:
                raise ValueError(f"batch has {total} tokens > "
                                 f"max_ragged_batch_size={self.max_tokens}")
            if len(batch_uids) > self.max_seqs:
                raise ValueError(f"{len(batch_uids)} sequences > "
                                 f"max_ragged_sequence_count={self.max_seqs}")
            max_ctx = self.max_ctx_tokens
            blocks_needed = 0
            new_seqs = 0
            for uid, tokens in zip(batch_uids, batch_tokens):
                desc = self.state_manager.query(uid)
                seen = desc.seen_tokens if desc is not None else 0
                if desc is None:
                    new_seqs += 1
                if self.slot_pool is not None and (desc is None or desc.state_row is None):
                    raise ValueError(
                        f"sequence {uid}: a {self.kind.name!r} model needs the whole "
                        f"prompt before its first chunk — call prefix_match(uid, prompt) "
                        f"first (a scheduler does)")
                if seen + len(tokens) > max_ctx:
                    raise ValueError(f"sequence {uid}: {seen}+{len(tokens)} tokens exceed "
                                     f"max_context={max_ctx}")
                blocks_needed += (desc.blocks_needed(len(tokens)) if desc is not None
                                  else -(-len(tokens) // self.block_size))
            if blocks_needed > self._reclaimable_blocks():
                raise RuntimeError(f"KV pool exhausted: need {blocks_needed} blocks, "
                                   f"{self._reclaimable_blocks()} reclaimable — "
                                   f"flush() sequences first")
            if new_seqs + self.state_manager.n_tracked_sequences > \
                    self.state_manager.max_tracked_sequences:
                raise RuntimeError("max_tracked_sequences exceeded for this batch")

            self._batch.clear()
            slots = []
            for i, (uid, tokens) in enumerate(zip(batch_uids, batch_tokens)):
                desc = self.state_manager.get_or_create_sequence(uid)
                desc.slot = i
                if self.lora_store is not None:
                    desc.adapter_slot = self.lora_store.slot_of(uid)
                self.state_manager.allocate_for(desc, len(tokens))
                self._batch.insert_sequence(desc, tokens)
                desc.advance(len(tokens))
                rec.n_ctx_tokens += desc.seen_tokens
                if self._log_tokens:
                    desc.tokens.fence()
                    desc.tokens.extend(int(t) for t in tokens)
                slots.append(desc.slot)
            # (the one line that is not the parent's: which program a step takes is the
            # engine's ladder since PR 55 - test_put_ladder.py - and no packer's business)
            bucket = next(b for b in self.put_buckets if total <= b)
            arrays = self._batch.finalize_packed(bucket=bucket)
            if mode == "packed":
                for s in specs:
                    if s is not None and "seed" not in s:
                        s["seed"] = self.draw_seed()
                dfa = None
                if self.structured is not None:
                    dfa = [(self.structured.slot_of(u), self.structured.state_of(u))
                           for u in batch_uids]
                arrays = np.concatenate(
                    [arrays, pack_sample_meta(specs, self.max_seqs, dfa=dfa)])
            if self.mesh is not None:
                arrays = jax.device_put(arrays, self._replicated)
            rec.program, rec.n_seqs, rec.n_tokens = str(bucket), len(batch_uids), total
            rec.n_rows = bucket
            rec.n_prompt_tokens = sum(len(t) for t in batch_tokens if len(t) > 1)
        extra = (self.lora_store.slabs(),) if self.lora_store is not None else ()
        with tracing.phase("engine.dispatch"):
            if mode == "packed":
                sargs = (self._base_key,)
                if self.structured is not None:
                    sargs += (self.structured.slabs(),)
                out, self.kv_cache.k, self.kv_cache.v, *counts, self.state_extra = \
                    self._step_sampled(self.params, self.kv_cache.k, self.kv_cache.v,
                                       self.state_extra, arrays, *sargs, *extra)
            else:
                fn = self._step_greedy if mode == "greedy" else self._step
                out, self.kv_cache.k, self.kv_cache.v, *counts, self.state_extra = fn(
                    self.params, self.kv_cache.k, self.kv_cache.v, self.state_extra,
                    arrays, *extra)
        self.count_host_sync()
        self.tokens_emitted += len(batch_uids)
        self._note_chunks(rec)
        with tracing.phase("engine.fetch"):
            host, *counts = jax.device_get((out, *counts))
            host = host[slots]
            self._note_counts(rec, counts)
        self.last_step = rec
        return host


def parent_validate_burst(self, batch_uids, k):
    descs = []
    need = 0
    for uid in batch_uids:
        desc = self.state_manager.query(uid)
        if desc is None or desc.seen_tokens == 0:
            return None, ValueError(
                f"sequence {uid} has no prefilled context — "
                f"bursts continue existing sequences only")
        if desc.seen_tokens + k > self.max_ctx_tokens:
            return None, ValueError(
                f"sequence {uid}: {desc.seen_tokens}+{k} tokens exceed "
                f"max_context={self.max_ctx_tokens}")
        need += desc.blocks_needed(k)
        descs.append(desc)
    if need > self._reclaimable_blocks():
        return None, RuntimeError(
            f"KV pool exhausted: need {need} blocks, "
            f"{self._reclaimable_blocks()} reclaimable — "
            f"flush() sequences first")
    return descs, None


def parent_dispatch_burst(self, rec, batch_uids, batch_tokens, k, sample, prev=None):
    with tracing.phase("engine.pack"):
        if k < 1:
            raise ValueError("k must be >= 1")
        n, ms = len(batch_uids), self.max_seqs
        mode, specs = self._classify_sample(sample, n)
        if self.structured is not None and \
                any(self.structured.bound(u) for u in batch_uids):
            mode = "packed"
            specs = specs if specs is not None else [None] * n
        sampled = mode == "packed"
        if prev is None:
            if n != len(batch_tokens):
                raise ValueError(f"{n} uids vs {len(batch_tokens)} tokens")
        elif list(prev.uids) != list(batch_uids):
            raise ValueError(
                "chained async burst must keep its predecessor's uid "
                "order — drain the pipeline when the live set changes")
        elif sampled and prev.st is None:
            raise ValueError(
                "sampled async burst chained onto a greedy handle — "
                "drain the pipeline before changing decode mode")
        if n > ms:
            raise ValueError(f"{n} sequences > max_ragged_sequence_count={ms}")
        from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK
        descs, err = self._validate_burst(batch_uids, k)
        if err is not None:
            raise err
        if prev is not None:
            entry_np, entry = None, prev.entry_next
        else:
            entry_np = np.zeros(ms, np.int32)
            entry_np[:n] = [int(np.asarray(tok).reshape(-1)[-1]) for tok in batch_tokens]
            entry = self._replicated_input(entry_np)

        lora_on = self.lora_store is not None
        token_seq = np.full(ms, ms, np.int32)
        pos0 = np.zeros(ms, np.int32)
        tables = np.full((ms + 1, self.max_blocks_per_seq), NULL_BLOCK, np.int32)
        adapters = np.zeros(ms + 1, np.int32)
        seq_state = np.zeros((ms + 1, self._seq_rows), np.int32)
        for i, desc in enumerate(descs):
            desc.slot = i
            if self._seq_rows:
                seq_state[i] = desc.state_row
            if lora_on:
                desc.adapter_slot = self.lora_store.slot_of(desc.uid)
                adapters[i] = desc.adapter_slot
            self.state_manager.allocate_for(desc, k)
            token_seq[i] = i
            pos0[i] = desc.seen_tokens
            tables[i, :len(desc.blocks)] = desc.blocks
            desc.advance(k)
            rec.n_ctx_tokens += _burst_ctx_tokens(int(pos0[i]), k)
        parts = [token_seq, pos0, tables.ravel()]
        opt = {}
        if lora_on:
            parts.append(adapters)
            opt["lora"] = self.lora_store.slabs()
        if self._seq_rows:
            parts.append(seq_state.ravel())
        if sampled:
            for s in specs:
                if s is not None and "seed" not in s:
                    s["seed"] = self.draw_seed()
            dfa = None
            if self.structured is not None:
                dfa = [(self.structured.slot_of(u), self.structured.state_of(u))
                       for u in batch_uids]
                opt["dfa"] = self.structured.slabs()
            parts.append(pack_sample_meta(specs, ms, dfa=dfa))
            opt["base"] = self._base_key
            if prev is not None:
                opt["state"] = prev.st
            else:
                state = np.zeros(ms, np.int32)
                if dfa is not None:
                    state[:n] = [int(st) for _, st in dfa]
                opt["state"] = self._replicated_input(state)
        meta = np.concatenate(parts)
        assert meta.shape[0] == sum(e - s for s, e in _burst_layout(
            ms, self.max_blocks_per_seq, lora=lora_on, sampled=sampled,
            seq_rows=self._seq_rows).values())
        meta = self._replicated_input(meta)
        skey = "sampled" if sampled else None
        key = ("burst", k, skey)
        if "dfa" in opt:
            key = key + (("dfa",) + self.structured.signature(),)
        if lora_on:
            key = key + (self.lora_store.signature(),)
        fn = self._get_burst_fn(key, lambda: self._make_burst_fn(k, skey))
    with tracing.phase("engine.dispatch"):
        out, st, self.kv_cache.k, self.kv_cache.v, *counts, self.state_extra = fn(
            self.params, self.kv_cache.k, self.kv_cache.v, self.state_extra, meta, entry,
            opt)
    self.tokens_emitted += k * n
    return descs, entry_np, out, st, counts


def parent_verify_burst(self, batch_uids, batch_tokens, batch_drafts, sample=None):
    with tracing.step("verify", engine=self.trace_id, uids=tuple(batch_uids)) as rec:
        with tracing.phase("engine.pack"):
            from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK
            if self.spec is None:
                raise RuntimeError("speculative decoding is disabled "
                                   "(config.spec_decode / DS_SPEC_DECODE)")
            mode, specs = self._classify_sample(sample, len(batch_uids))
            if mode == "logits":
                mode = "greedy"
            sampled = mode == "packed"
            if self.structured is not None and \
                    any(self.structured.bound(u) for u in batch_uids):
                raise RuntimeError(
                    "constrained sequences cannot enter verify bursts — the "
                    "drafter proposed tokens without the DFA mask; schedulers "
                    "route schema-bound sequences through plain bursts")
            if not (len(batch_uids) == len(batch_tokens) == len(batch_drafts)):
                raise ValueError(f"{len(batch_uids)} uids vs {len(batch_tokens)} "
                                 f"tokens vs {len(batch_drafts)} drafts")
            if len(batch_uids) > self.max_seqs:
                raise ValueError(f"{len(batch_uids)} sequences > "
                                 f"max_ragged_sequence_count={self.max_seqs}")
            d = max((len(dr) for dr in batch_drafts), default=0)
            if d < 1:
                raise ValueError("verify_burst needs at least one draft token; "
                                 "use put()/decode_burst for draft-free decoding")
            descs, err = self._validate_burst(batch_uids, d + 1)
            if err is not None:
                raise err
            rec.program, rec.n_seqs = f"verify{d}", len(batch_uids)
            rec.n_tokens = len(batch_uids) * (d + 1)
            rec.n_rows = self.max_seqs * (d + 1)
            ms, mb = self.max_seqs, self.max_blocks_per_seq
            lora_on = self.lora_store is not None
            toks = np.zeros((ms, d + 1), np.int32)
            dlen = np.zeros(ms, np.int32)
            token_seq = np.full(ms, ms, np.int32)
            pos0 = np.zeros(ms, np.int32)
            tables = np.full((ms + 1, mb), NULL_BLOCK, np.int32)
            adapters = np.zeros(ms + 1, np.int32)
            entries = []
            for i, (desc, tok, drafts) in enumerate(
                    zip(descs, batch_tokens, batch_drafts)):
                desc.slot = i
                if lora_on:
                    desc.adapter_slot = self.lora_store.slot_of(desc.uid)
                    adapters[i] = desc.adapter_slot
                self.state_manager.allocate_for(desc, d + 1)
                self.count_host_sync()
                entry = int(np.asarray(tok).reshape(-1)[-1])
                entries.append(entry)
                row = [entry] + [int(t) for t in drafts]
                toks[i, :len(row)] = row
                toks[i, len(row):] = entry
                dlen[i] = len(drafts)
                token_seq[i] = i
                pos0[i] = desc.seen_tokens
                tables[i, :len(desc.blocks)] = desc.blocks
                rec.n_ctx_tokens += desc.seen_tokens + d + 1
            parts = [toks.ravel(), dlen, token_seq, pos0, tables.ravel()]
            if lora_on:
                parts.append(adapters)
            if sampled:
                for s in specs:
                    if s is not None and "seed" not in s:
                        s["seed"] = self.draw_seed()
                parts.append(pack_sample_meta(specs, ms))
            meta = np.concatenate(parts)
            assert meta.shape[0] == sum(
                e - s for s, e in _verify_layout(ms, mb, d, lora=lora_on,
                                                 sampled=sampled).values())
            if self.mesh is not None:
                meta = jax.device_put(meta, self._replicated)
            key = ("verify", d) if not sampled else ("verify", d, "sampled")
            packed = self.async_burst_depth > 0
            if packed:
                key = key + ("packed",)
            if lora_on:
                key = key + (self.lora_store.signature(),)
            fn = self._get_burst_fn(
                key, lambda: self._make_verify_fn(d, sampled, packed=packed))
            extra = (self.lora_store.slabs(),) if lora_on else ()
            sargs = (self._base_key,) if sampled else ()
        if packed:
            with tracing.phase("engine.dispatch"):
                wire, self.kv_cache.k, self.kv_cache.v = fn(
                    self.params, self.kv_cache.k, self.kv_cache.v, meta,
                    *sargs, *extra)
            self.count_host_sync()
            with tracing.phase("engine.fetch"):
                wire = np.asarray(wire)
            out = wire[:ms * (d + 1)].reshape(ms, d + 1)
            acc = wire[ms * (d + 1):].astype(np.int64)
        else:
            with tracing.phase("engine.dispatch"):
                out, acc, self.kv_cache.k, self.kv_cache.v = fn(
                    self.params, self.kv_cache.k, self.kv_cache.v, meta,
                    *sargs, *extra)
            self.count_host_sync(2)
            with tracing.phase("engine.fetch"):
                out = np.asarray(out)
                acc = np.asarray(acc)
        n = len(batch_uids)
        with tracing.phase("engine.log"):
            for i, desc in enumerate(descs):
                a = int(acc[i])
                self.tokens_emitted += a + 1
                desc.advance(a + 1)
                if self._log_tokens:
                    desc.tokens.fence()
                    desc.tokens.append(entries[i])
                    desc.tokens.extend(int(t) for t in out[i, :a])
                self.state_manager.release_unused_blocks(desc)
                if int(dlen[i]):
                    self.spec.note(desc.uid, accepted=a, drafted=int(dlen[i]))
        self.last_step = rec
        return out[:n], acc[:n]


# ====================================================================== the harness
class Programs:
    """Stands in for an engine's compiled programs: keeps the metadata vector
    the host packed for each and answers with zeros of the shape the engine
    unpacks. A verify program accepts a share of each row's drafts that
    depends on the row and on the step alone, so that both engines of a pair
    see the same rejections."""

    def __init__(self, engine):
        self.ms, self.sent = engine.max_seqs, []
        engine._step = engine._step_greedy = engine._step_sampled = self.step
        engine._get_burst_fn = self.burst_fn

    def keep(self, meta):
        self.sent.append(np.array(meta))

    def step(self, p, kc, vc, xc, arrays, *extra):
        self.keep(arrays)
        return np.zeros(self.ms, np.int32), kc, vc, xc

    def burst_fn(self, key, make):
        if key[0] == "burst":
            def burst(p, kc, vc, xc, meta, entry, opt):
                self.keep(meta)
                return np.zeros((key[1], self.ms), np.int32), None, kc, vc, xc
            return burst

        def verify(p, kc, vc, meta, *rest):
            self.keep(meta)
            d, ms = key[1], self.ms
            dlen = np.asarray(meta[ms * (d + 1):ms * (d + 2)])
            acc = (np.arange(ms) * 7 + len(self.sent)) % (dlen + 1)
            return np.ones((ms, d + 1), np.int32), acc.astype(np.int64), kc, vc
        return verify


def build(preset="debug", parent=False, params=None, blocks=0, **config):
    model = models.build_model(preset)
    engine = InferenceEngineV2(
        model=model, params=params, dtype=jnp.float32,
        config=RaggedInferenceEngineConfig(
            kv_block_size=BLOCK, num_kv_blocks=blocks,
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=MAX_TOKENS, max_ragged_sequence_count=MAX_SEQS,
                max_tracked_sequences=TRACKED, max_context=MAX_CTX), **config))
    engine.programs = Programs(engine)
    if parent:
        engine._batch = ParentWrapper(engine.max_tokens, engine.max_seqs, engine.max_blocks_per_seq,
                                      lora=engine.lora_store is not None,
                                      seq_rows=engine._seq_rows)
        for name, fn in (("put", parent_put), ("_validate_burst", parent_validate_burst),
                         ("_dispatch_burst", parent_dispatch_burst),
                         ("verify_burst", parent_verify_burst)):
            setattr(engine, name, types.MethodType(fn, engine))
    return engine


ADAPTERS = (101, 102)


def adapter(store, seed, rank=2):
    rs = np.random.RandomState(seed)
    return {site: (rs.randn(store.num_layers, din, rank).astype(np.float32),
                   rs.randn(store.num_layers, rank, dout).astype(np.float32))
            for site, (din, dout) in store.dims.items()}


CONFIGS = {
    # a tight pool: batches are refused for want of blocks, and rows are reused
    "plain": dict(blocks=20),
    "cache+spec": dict(blocks=24, prefix_cache=PrefixCacheConfig(enabled=True),
                       spec_decode=SpecDecodeConfig(enabled=True, draft_len=3)),
    "lora": dict(lora=LoRAServingConfig(enabled=True, hot_set=4, max_rank=4, prefetch=False)),
    "sala": dict(preset="minicpm-sala-debug"),       # seq_rows 2: (slot, sparse_from)
    "jamba": dict(preset="jamba-debug", blocks=20),  # seq_rows 1: (slot,)
}


class Pair:
    """This tree's engine and the parent's packer over the same engine, told
    the same things; ``same()`` is what must hold after every one of them."""

    def __init__(self, name=None, **config):
        config = config or dict(CONFIGS[name])
        self.new = build(**config)
        self.old = build(parent=True, params=self.new.params, **config)
        self.both = (self.new, self.old)
        for engine in self.both:
            if engine.lora_store is not None:
                for seed, adapter_id in enumerate(ADAPTERS):
                    engine.register_adapter(adapter_id, adapter(engine.lora_store, seed), alpha=4.0)

    def tell(self, method, *args, **kwargs):
        """→ what the call gave, or the exception it raised: the same of both."""
        got = []
        for engine in self.both:
            try:
                got.append(getattr(engine, method)(*args, **kwargs))
            except (ValueError, RuntimeError, KeyError) as err:
                got.append(err)
        new, old = got
        if isinstance(new, Exception) or isinstance(old, Exception):
            assert type(new) is type(old) and str(new) == str(old), (method, new, old)
        self.same()
        return new

    def state(self, engine):
        sm = engine.state_manager
        return {"free": list(engine.kv_cache._allocator._free),
                "slots": None if engine.slot_pool is None else list(engine.slot_pool._free),
                "seqs": {uid: (d.seen_tokens, list(d.blocks), d.slot, d.state_row,
                               d.adapter_slot, d.shared_blocks, d.cached_tokens)
                         for uid, d in sm._seqs.items()}}

    def same(self):
        new, old = self.new.programs.sent, self.old.programs.sent
        assert len(new) == len(old)
        if new:
            assert new[-1].dtype == old[-1].dtype == np.int32
            assert new[-1].tobytes() == old[-1].tobytes()
        assert self.state(self.new) == self.state(self.old)
        check_table(self.new)

    def sent(self):
        return len(self.new.programs.sent)


def check_table(engine):
    """The table says what the descriptors say, and nothing besides."""
    sm = engine.state_manager
    want = np.full_like(sm.block_table, NULL_BLOCK)
    rows = set()
    for desc in sm._seqs.values():
        want[desc.row, :len(desc.blocks)] = desc.blocks
        rows.add(desc.row)
        if sm.state_table is not None:
            assert sm.state_table[desc.row].tolist() == list(desc.state_row or [0] * engine._seq_rows)
    assert np.array_equal(sm.block_table, want)
    assert len(rows) == len(sm._seqs) and rows.isdisjoint(sm._free_rows)
    assert sorted(rows | set(sm._free_rows)) == list(range(sm.max_tracked_sequences))
    if sm.state_table is not None:
        assert not sm.state_table[sorted(set(range(len(sm.state_table))) - rows)].any()


@pytest.fixture(scope="module", params=list(CONFIGS))
def pair(request):
    return Pair(request.param)


# ====================================================================== random schedules
class Schedule:
    """A few dozen requests through a pair: prompts in chunks beside decode
    rows, bursts, an end inside a burst (rewind, flush), drafts of which some
    are refused, suspend and resume, and every uid's row taken by the next."""

    def __init__(self, pair, seed):
        self.pair, self.rng = pair, np.random.RandomState(seed)
        self.engine = pair.new
        self.prompts = {}       # uid -> tokens not sent yet
        self.decoding = []      # uids whose prompt is in the cache
        self.suspended = []
        self.next_uid = 1000 * (seed + 1)
        self.kinds = set()

    def seen(self, uid):
        return self.engine.query(uid)[0]

    def admit(self):
        uid, self.next_uid = self.next_uid, self.next_uid + 1
        n = int(self.rng.randint(3, 30))
        # few distinct tokens and shared openings: the prefix cache finds leases
        prompt = ([7] * 8 + [9] * 8)[:int(self.rng.choice([0, 8, 16]))]
        prompt = (prompt + self.rng.randint(0, 50, n).tolist())[:n] or [1]
        if self.engine.lora_store is not None and self.rng.rand() < 0.7:
            self.pair.tell("bind_adapter", uid, int(self.rng.choice(ADAPTERS)))
        cached = self.pair.tell("prefix_match", uid, prompt)
        self.prompts[uid] = prompt[cached:]

    def put(self):
        budget, uids, chunks = MAX_TOKENS, [], []
        for uid in self.decoding[:MAX_SEQS]:
            uids.append(uid)
            chunks.append([int(self.rng.randint(0, 50))])
            budget -= 1
        for uid in list(self.prompts):
            if len(uids) == MAX_SEQS or budget < 1:
                break
            take = min(budget, int(self.rng.randint(1, 20)), len(self.prompts[uid]))
            uids.append(uid)
            chunks.append(self.prompts[uid][:take])
            budget -= take
        if not uids:
            return
        order = self.rng.permutation(len(uids))     # a batch's rows in any order
        uids, chunks = [uids[i] for i in order], [chunks[i] for i in order]
        if isinstance(self.pair.tell("put", uids, chunks, sample="greedy"), Exception):
            return self.end(self.decoding or list(self.prompts))     # the pool is full
        self.kinds.add("put")
        for uid, chunk in zip(uids, chunks):
            if uid in self.prompts:
                del self.prompts[uid][:len(chunk)]
                if not self.prompts[uid]:
                    del self.prompts[uid]
                    self.decoding.append(uid)

    def burst(self):
        uids = self.decoding[:MAX_SEQS]
        k = int(self.rng.choice([2, 4]))
        if not uids or max(self.seen(uid) for uid in uids) + k > MAX_CTX:
            return
        if not self.pair.tell("can_burst", uids, k):
            return self.end(uids)
        self.pair.tell("decode_burst", uids, [[3]] * len(uids), k)
        self.kinds.add("burst")
        if self.rng.rand() < 0.5:       # an end inside the burst: the tail goes back
            uid = uids[int(self.rng.randint(len(uids)))]
            self.pair.tell("rewind", uid, int(self.rng.randint(1, k + 1)))
            self.end([uid])
            self.kinds.add("rewind")

    def verify(self):
        uids = self.decoding[:MAX_SEQS]
        if self.engine.spec is None or not uids or \
                max(self.seen(uid) for uid in uids) + 4 > MAX_CTX:
            return
        drafts = [self.rng.randint(0, 50, int(self.rng.randint(0, 4))).tolist() for _ in uids]
        drafts[0] = drafts[0] or [5]
        if not self.pair.tell("can_burst", uids, 4):
            return self.end(uids)
        self.pair.tell("verify_burst", uids, [[3]] * len(uids), drafts)
        self.kinds.add("verify")

    def end(self, uids):
        uid = uids[int(self.rng.randint(len(uids)))]
        self.pair.tell("flush", uid)
        self.prompts.pop(uid, None)
        if uid in self.decoding:
            self.decoding.remove(uid)

    def swap(self):
        if self.engine.state_kind != "kv" or self.engine.slot_pool is not None:
            return
        if self.suspended and self.rng.rand() < 0.6:
            uid = self.suspended[0]
            if not isinstance(self.pair.tell("resume", uid), Exception):
                self.suspended.remove(uid)
                self.decoding.append(uid)
                self.kinds.add("resume")
        elif self.decoding:
            uid = self.decoding.pop(int(self.rng.randint(len(self.decoding))))
            self.pair.tell("suspend", uid)
            self.suspended.append(uid)

    def run(self, steps):
        for _ in range(steps):
            live = len(self.prompts) + len(self.decoding) + len(self.suspended)
            if live < TRACKED - 1 and self.rng.rand() < 0.5:
                self.admit()
            if len(self.decoding) > 4 or any(self.seen(uid) > MAX_CTX - 12
                                             for uid in self.decoding):
                self.end(sorted(self.decoding, key=self.seen)[-1:])
            getattr(self, self.rng.choice(["put", "put", "put", "burst", "verify", "swap"]))()
        for uid in self.decoding + list(self.prompts) + self.suspended:
            self.pair.tell("flush", uid)
        self.decoding, self.prompts, self.suspended = [], {}, []


@pytest.mark.parametrize("seed", range(3))
def test_random_schedule_packs_the_parents_bytes(pair, seed):
    schedule = Schedule(pair, seed)
    before = pair.sent()
    schedule.run(120)
    assert pair.sent() - before > 60 and {"put", "burst", "rewind"} <= schedule.kinds
    if pair.new.spec is not None:
        assert "verify" in schedule.kinds
    if pair.new.slot_pool is None:
        assert "resume" in schedule.kinds
    sm = pair.new.state_manager
    assert not sm._seqs and not (sm.block_table != NULL_BLOCK).any()
    if pair.new.prefix_cache is not None:
        assert pair.new.prefix_cache.hits > 0      # leases were taken: rows written at creation


def test_bare_tokens_and_arrays_pack_as_lists_do():
    """What ``put`` takes for a chunk: a list, an array, or one bare token."""
    pair = Pair("plain")
    pair.tell("put", [1, 2, 3], [[4, 5, 6], np.arange(9), (7,)], sample="greedy")
    pair.tell("put", [3, 1, 2], [8, np.int64(9), np.asarray(3)], sample="greedy")
    sent = pair.new.programs.sent[-1]
    assert sent[:3].tolist() == [8, 9, 3] and pair.new.query(2) == (10, 6)


# ====================================================================== a refused batch
def refusals(engine):
    """name → (what to call, the parent's message) on an engine that holds
    sequence 1 with 40 tokens and sequence 2 with 5."""
    return {
        "too many tokens": (("put", [1, 2], [[1] * 40, [1] * 9]),
                            "batch has 49 tokens > max_ragged_batch_size=48"),
        "too many sequences": (("put", list(range(10, 17)), [[1]] * 7),
                               "7 sequences > max_ragged_sequence_count=6"),
        "context overflow": (("put", [2, 1], [[1], [1] * 25]),
                             "sequence 1: 40+25 tokens exceed max_context=64"),
        "pool exhausted": (("put", [2, 3, 4], [[1] * 20, [1] * 10, [1] * 10]),
                           "KV pool exhausted: need 7 blocks, 5 reclaimable — "
                           "flush() sequences first"),
        "max_tracked_sequences": (("put", list(range(10, 15)), [[1]] * 5),
                                  "max_tracked_sequences exceeded for this batch"),
        "burst without context": (("decode_burst", [1, 9], [[1], [1]], 2),
                                  "sequence 9 has no prefilled context — "
                                  "bursts continue existing sequences only"),
        "burst past the context": (("decode_burst", [2, 1], [[1], [1]], 32),
                                   "sequence 1: 40+32 tokens exceed max_context=64"),
        "burst past the pool": (("decode_burst", [1, 2], [[1], [1]], 24),
                                "KV pool exhausted: need 6 blocks, 5 reclaimable — "
                                "flush() sequences first"),
    }


@pytest.fixture(scope="module")
def tight():
    """13 blocks beyond the null block, of which 5 are free, and 4 tracked sequences of 8."""
    pair = Pair(blocks=14)
    pair.tell("put", [1, 2], [[1] * 40, [1] * 5], sample="greedy")
    pair.tell("put", [5, 6], [[1], [1]], sample="greedy")
    return pair


@pytest.mark.parametrize("name", list(refusals(None)))
def test_refused_batch_leaves_everything_untouched(tight, name):
    (method, *args), message = refusals(tight.new)[name]
    sm = tight.new.state_manager
    before = (tight.state(tight.new), sm.block_table.copy(), sm.rows_written, tight.sent())
    err = tight.tell(method, *args)
    assert isinstance(err, (ValueError, RuntimeError)) and str(err) == message
    assert (tight.state(tight.new), sm.rows_written, tight.sent()) == \
        (before[0], before[2], before[3])
    assert np.array_equal(sm.block_table, before[1])


# ====================================================================== the counter
def test_counter_rows_written_a_step():
    engine = build()
    records = []

    def step(method, *args, **kwargs):
        getattr(engine, method)(*args, **kwargs)
        records.append(engine.last_step.n_table_rows_written)
        assert tracing.snapshot()["steps"][-1]["n_table_rows_written"] == records[-1]

    uids = [1, 2, 3]
    step("put", uids, [[1] * 5] * 3, sample="greedy")
    assert records == [3]                       # new sequences: a row each
    for _ in range(3):                          # positions 5, 6, 7: inside the first block
        step("put", uids, [[1]] * 3, sample="greedy")
    assert records[1:] == [0, 0, 0]
    step("put", uids, [[1]] * 3, sample="greedy")
    assert records[-1] == 3                     # position 8: every sequence's block is full
    step("put", uids + [4], [[1]] * 3 + [[1] * 9], sample="greedy")
    assert records[-1] == 1                     # the new prompt alone
    step("decode_burst", uids, [[1]] * 3, 4)    # positions 10-13: inside the second block
    assert records[-1] == 0
    step("decode_burst", uids, [[1]] * 3, 4)    # 14-17: over its end
    assert records[-1] == 3
    assert "n_table_rows_written" in tracing.STEP_FIELDS
    assert engine.state_manager.rows_written == sum(records)


# ====================================================================== manager and wrapper alone
class TestTable:

    def manager(self, seq_rows=0, tracked=4):
        cache = BlockedKVCache(num_layers=1, num_blocks=17, block_size=BLOCK, n_kv_heads=1,
                               head_dim=4)
        return DSStateManager(cache, tracked, max_blocks_per_seq=6, seq_rows=seq_rows)

    def test_row_follows_blocks(self):
        sm = self.manager()
        a, b = sm.get_or_create_sequence("a"), sm.get_or_create_sequence("b")
        assert (a.row, b.row) == (0, 1) and sm.rows_written == 0
        sm.allocate_for(a, 20)
        sm.allocate_for(b, 3)
        assert sm.block_table[0].tolist() == a.blocks + [NULL_BLOCK] * 3
        assert sm.block_table[1].tolist() == b.blocks + [NULL_BLOCK] * 5
        a.advance(20)
        sm.allocate_for(a, 4)                       # fits: nothing is written
        assert sm.rows_written == 2
        sm.rewind_sequence(a, 11)                   # 9 tokens: the third block goes back
        assert sm.block_table[0].tolist() == a.blocks + [NULL_BLOCK] * 4 and len(a.blocks) == 2
        assert sm.rows_written == 3
        sm.rewind_sequence(a, 0)                    # nothing to give back: nothing written
        assert sm.rows_written == 3

    def test_flushed_row_is_null_and_the_next_uids(self):
        sm = self.manager(seq_rows=5)
        a, b = sm.get_or_create_sequence("a"), sm.get_or_create_sequence("b")
        sm.allocate_for(a, 30)
        sm.set_state_row(a, [3, 1, 4, 1, 5])
        free = sm.free_blocks
        sm.flush_sequence("a")
        assert a.row == -1 and sm.free_blocks == free + 4
        assert not sm.block_table[0].any() and not sm.state_table[0].any()
        c = sm.get_or_create_sequence("c")
        assert c.row == 0 and b.row == 1
        tables, state = sm.gather([c, b], rows=4)
        assert tables.shape == (4, 6) and state.shape == (4, 5) and not tables.any()

    def test_drop_keeps_the_blocks_for_their_new_owner(self):
        sm = self.manager()
        a = sm.get_or_create_sequence("a")
        sm.allocate_for(a, 12)
        free, blocks = sm.free_blocks, list(a.blocks)
        assert sm.drop_sequence("a") is a and a.blocks == blocks and sm.free_blocks == free
        assert not sm.block_table.any()
        with pytest.raises(KeyError):
            sm.drop_sequence("a")

    def test_row_is_as_wide_as_a_steps_table(self):
        sm = self.manager()
        a = sm.get_or_create_sequence("a")
        sm.allocate_for(a, 6 * BLOCK)
        with pytest.raises(ValueError, match=r"owns 7 blocks > max_blocks_per_seq=6 \(context"):
            sm.extend_blocks(a, [9])
        assert len(a.blocks) == 6 and sm.block_table[0].tolist() == a.blocks

    def test_tracked_limit(self):
        sm = self.manager(tracked=2)
        sm.get_or_create_sequence(1), sm.get_or_create_sequence(2)
        with pytest.raises(RuntimeError, match="max_tracked_sequences=2 exceeded"):
            sm.get_or_create_sequence(3)
        sm.flush_sequence(1)
        assert sm.get_or_create_sequence(3).row == 0

    @pytest.mark.parametrize("lora", [False, True])
    @pytest.mark.parametrize("seq_rows", [0, 5])
    def test_batch_insert_is_the_parents_inserts(self, lora, seq_rows):
        rng = np.random.RandomState(seq_rows + lora)
        sm = self.manager(seq_rows, tracked=8)
        new = RaggedBatchWrapper(40, 5, 6, lora=lora, seq_rows=seq_rows)
        old = ParentWrapper(40, 5, 6, lora=lora, seq_rows=seq_rows)
        live = {}
        for step in range(60):
            if len(live) < 7 and rng.rand() < 0.6:
                desc = live[step] = sm.get_or_create_sequence(step)
                desc.adapter_slot = int(rng.randint(0, 4))
                if seq_rows:
                    sm.set_state_row(desc, rng.randint(0, 99, seq_rows).tolist())
            if live and rng.rand() < 0.2:
                sm.flush_sequence(live.pop(list(live)[int(rng.randint(len(live)))]).uid)
            descs = [live[u] for u in rng.permutation(list(live))[:5]]
            chunks = [rng.randint(0, 99, int(rng.randint(1, 9))).astype(np.int32) for _ in descs]
            descs = [d for d, c in zip(descs, chunks) if d.seen_tokens + len(c) <= 6 * BLOCK]
            if sm.free_blocks < 10 or not descs:
                continue
            old.clear()
            new.clear()
            for slot, (desc, chunk) in enumerate(zip(descs, chunks)):
                desc.slot = slot
                sm.allocate_for(desc, len(chunk))
                old.insert_sequence(desc, chunk)
            tables, state = sm.gather(descs)
            new.insert_batch(0, [d.seen_tokens for d in descs],
                             [len(c) for c in chunks], np.concatenate(chunks), tables,
                             adapters=[d.adapter_slot for d in descs], seq_state=state)
            for desc, chunk in zip(descs, chunks):
                desc.advance(len(chunk))
            for bucket in (None, 40):
                assert new.finalize_packed(bucket).tobytes() == old.finalize_packed(bucket).tobytes()
            assert new.slots_in_order() == old.slots_in_order()
            assert new.current_tokens == old.current_tokens
            assert np.array_equal(new.seq_valid, old.seq_valid)

    def test_one_sequence_is_a_batch_of_one(self):
        new, old = RaggedBatchWrapper(16, 3, 4, seq_rows=2), ParentWrapper(16, 3, 4, seq_rows=2)

        class Desc:
            uid, slot, seen_tokens, blocks, state_row = 7, 2, 11, [5, 6], (4, 1)

        for wrapper in (new, old):
            wrapper.insert_sequence(Desc(), [1, 2, 3])
            Desc.slot, Desc.seen_tokens = 0, 0
            wrapper.insert_sequence(Desc(), [4])
            Desc.slot, Desc.seen_tokens = 2, 11
        assert new.finalize_packed().tobytes() == old.finalize_packed().tobytes()
        for wrapper in (new, old):
            with pytest.raises(ValueError, match=r"ragged batch overflow: 4\+13 > 16"):
                wrapper.insert_sequence(Desc(), [0] * 13)
            Desc.blocks = [1, 2, 3, 4, 5]
            with pytest.raises(ValueError, match="sequence 7 owns 5 blocks > max_blocks_per_seq=4"):
                wrapper.insert_sequence(Desc(), [0])
            Desc.blocks = [5, 6]

"""InferenceEngineV2: ragged (continuous-batching) serving engine.

Capability match for the reference's
``deepspeed/inference/v2/engine_v2.py`` (``InferenceEngineV2`` at
engine_v2.py:107: ``put(batch_uids, batch_tokens)`` runs one ragged
batch; ``flush``/``query`` manage sequence state). TPU execution: one
jitted step (compiled once, KV pool donated) consumes the padded flat
batch from ``RaggedBatchWrapper``; mixed prefill chunks and decodes
run in the same program — the Dynamic SplitFuse model."""

import bisect
import itertools
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.model_runner import kind_of, ragged_forward
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache, WindowPool
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
from deepspeed_tpu.inference.v2.ragged.slot_pool import SlotPool
from deepspeed_tpu.utils import tracing
from deepspeed_tpu.utils.env_registry import env_int
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.sanitize import maybe_checkify_jit, sanitize_enabled


from deepspeed_tpu.inference.sampling import \
    validate_sample_spec as _validate_sample
from deepspeed_tpu.inference.structured.prng import (base_sampling_key,
                                                     token_keys)
from deepspeed_tpu.inference.structured.sampling import (SAMPLE_META_ROWS,
                                                         apply_dfa_mask,
                                                         pack_sample_meta,
                                                         sample_rows,
                                                         unpack_sample_meta)


def _burst_ctx_tokens(seen, k):
    """Context positions one row attends over a burst of ``k`` steps that
    starts with ``seen`` tokens cached: step ``j`` attends ``seen + j + 1``."""
    return k * seen + k * (k + 1) // 2


def put_ladder(max_seqs, max_tokens, rungs=True):
    """The row counts a ``put`` program may have, ascending: the decode-sized
    one (``max_seqs``), the budget-sized one (``max_tokens``) and, between
    them, one rung of half the budget where the decode batch is at most an
    eighth of the rung (``max_tokens // 2 >= 8 * max_seqs``). A rung is a
    program to build, seconds of every start, and it serves the prompt steps
    that fit it beside their decode rows: with 16 sequences under a rung of
    256 nearly every one did (0.95-0.98, a step 120 -> 69 ms), with 64 a
    fifth, with 128 a twenty-fifth to a third, for 4-10 % of a warm start
    each (PERF.md section 6, PR 55). A step runs the smallest that holds its
    tokens. Two numbers the engine has, and nothing else: a tuple, so that a
    rung more is an entry more.
    ``rungs=False``: the two ends alone (the engine says when)."""
    if max_seqs >= max_tokens:
        return (max_seqs,)
    rung = max_tokens // 2
    if rungs and rung >= 8 * max_seqs:
        return (max_seqs, rung, max_tokens)
    return (max_seqs, max_tokens)


def _offsets(fields, lora, sampled, ms, seq_rows=0):
    """field → (start, end) into a flat int32 metadata vector. ``lora``
    appends the per-sequence adapter-slot row, ``seq_rows`` the model
    kind's per-sequence state rows and ``sampled`` the per-sequence
    sampling-spec rows — each strictly opt-in, so the off-state wire
    format carries none."""
    if lora:
        fields.append(("seq_adapters", ms + 1))
    if seq_rows:
        fields.append(("seq_state", (ms + 1) * seq_rows))
    if sampled:
        fields.append(("sample_meta", SAMPLE_META_ROWS * ms))
    o, lay = 0, {}
    for name, size in fields:
        lay[name] = (o, o + size)
        o += size
    return lay


def _burst_layout(ms, mb, lora=False, sampled=False, seq_rows=0):
    """Single source for the decode-burst metadata wire format. Both the
    host pack (``_dispatch_burst``) and the traced unpack
    (``_make_burst_fn``) read this, so the layout cannot silently
    diverge. Entry tokens are not in it: they are a device argument of
    their own, from the host or chained from the burst before."""
    return _offsets([("token_seq", ms), ("pos0", ms), ("tables", (ms + 1) * mb)],
                    lora, sampled, ms, seq_rows)


def _verify_layout(ms, mb, d, lora=False, sampled=False):
    """Wire format of the verify-burst metadata vector, ``_burst_layout``'s
    twin for the speculative path: per sequence, the entry token plus
    ``d`` (padded) draft tokens, the real draft count, and the usual
    slot/position/block-table fields (plus the adapter-slot row when
    LoRA serving is on and the sampling-spec rows for the
    rejection-sampled verify)."""
    return _offsets([("tokens", ms * (d + 1)), ("dlen", ms), ("token_seq", ms),
                     ("pos0", ms), ("tables", (ms + 1) * mb)], lora, sampled, ms)


class AsyncBurstHandle:
    """One dispatched-but-unfenced pipelined decode burst.

    ``out`` is the device ``[k, max_seqs]`` token array the burst's
    scan produced (a future under JAX async dispatch — holding it costs
    nothing); ``out[-1]`` is the next burst's device entry row and
    ``st`` (sampled bursts only) the chained DFA state row. ``fetch()``
    performs THE one device→host copy for the burst; until then the
    host knows nothing about the burst's tokens — EOS, accept counts
    and the token log are all discovered one burst late, when the
    scheduler fences.

    Pump-thread only (it is part of the engine step surface)."""

    def __init__(self, engine, uids, descs, k, out, st=None,
                 entry_np=None, prev=None, record=None, counts=()):
        self.uids = list(uids)
        self.k = int(k)
        self.out = out            # device [k, max_seqs] int32
        self.st = st              # device [max_seqs] chained DFA state (sampled)
        self._engine = engine
        self._descs = descs
        self._entry_np = entry_np  # host entry tokens, or None when chained
        self._prev = prev          # previous handle in the device chain
        self._toks = None
        self._record = record      # step record, open until fetch()
        self._counts = counts      # the program's device-side counts (engine._note_counts)

    @property
    def entry_next(self):
        """Device entry row for the next chained burst (no sync)."""
        return self.out[-1]

    def entry_values(self):
        """Host values of this burst's entry tokens ([n] np.int32). For
        a chained burst this reads the PREVIOUS handle's fetched output
        — in-order fencing makes that a no-op re-read, never an early
        sync of a younger burst."""
        if self._entry_np is None:
            self._entry_np = self._prev.fetch()[-1][:len(self.uids)]
        return self._entry_np

    def fetch(self):
        """THE one device→host copy for this burst → np.int32 [k, n].
        Idempotent; also counts the engine's per-burst sync site. After
        the copy the handle drops its device buffer and its ``_prev``
        link (resolving the host entry row first — in-order fencing
        makes that a cached re-read), so a long pipeline never chains
        unbounded memory."""
        if self._toks is None:
            self._engine.count_host_sync()
            rec = self._record
            if rec is not None:
                tracing.resume(rec)
            try:
                with tracing.phase("engine.fetch"):
                    toks, *counts = jax.device_get((self.out, *self._counts))  # ds-lint: disable=host-sync -- THE one intended sync per pipelined burst, paid at fence time
                    self._toks = toks[:, :len(self.uids)]
                    if rec is not None:
                        self._engine._note_counts(rec, counts)
            finally:
                if rec is not None:
                    tracing.end(rec)
                    self._engine.last_step = rec
            self.out, self._counts = None, ()
            if self._prev is not None:
                if self._entry_np is None:
                    self._entry_np = self._prev.fetch()[-1][:len(self.uids)]
                self._prev = None
        return self._toks

    def fence_logs(self):
        """Materialize the pending token-log segments of every sequence
        this burst touched. NOTE: a descriptor's log fences in append
        order ACROSS bursts, so this forces the fetch of any younger
        in-flight burst over the same rows — call it at drain time (or
        let flush/suspend/propose_drafts fence lazily), never from the
        steady-state fence loop."""
        for desc in self._descs:
            desc.tokens.fence()


class InferenceEngineV2:

    def __init__(self, model=None, config: RaggedInferenceEngineConfig = None,
                 params=None, model_config=None, dtype=jnp.bfloat16, rng=None):
        """``model``: a ``LlamaForCausalLM``, ``GPTForCausalLM``,
        ``MoonlightForCausalLM``, ``LongcatFlashForCausalLM`` or ``MiniCPMSalaForCausalLM`` (its scan-stacked params are
        initialized here when ``params`` is not given), or pass
        ``params`` + ``model_config`` directly. The config's type picks
        the model kind (``model_runner.kind_of``), which says what state
        the paged pool holds, in how many layers, and how a layer steps.

        The constructor is one step record of kind ``setup`` (utils/tracing.py)
        in four contiguous phases. None of them waits for the device: the
        weights' program and the pools' fills are launched in ``ds.setup.params``
        and ``ds.setup.pools`` and end under a later phase or under the first
        program's ``ds.engine.fetch``, as they always did."""
        # step records: this engine's number in them
        self.trace_id = tracing.engine_id()
        with tracing.setup(self.trace_id) as setup:
            self._construct(setup, model, config, params, model_config, dtype, rng)
        phases = " ".join(f"{name.rsplit('.', 1)[-1]}={(exit_ - enter) / 1e9:.2f}"
                          for name, enter, exit_ in setup.record.phases)
        age = setup.record.process_age_ns
        logger.info(f"InferenceEngineV2: max_tokens={self.max_tokens} "
                    f"max_seqs={self.max_seqs} kv_blocks={self.kv_cache.num_blocks} "
                    f"block_size={self.block_size} "
                    f"tp={int(self._config.tensor_parallel_degree)} "
                    f"ep={int(self._config.expert_parallel_degree)} "
                    f"kv_bytes={self.kv_cache.bytes()/1e6:.1f}MB "
                    f"state_layers={self.kv_cache.num_layers} "
                    f"param_layers={self.param_layers} "
                    f"state_bytes_per_token={self.state_bytes_per_token} "
                    f"experts={self.kind.experts_form(self.params, self.mesh)}"
                    + ("" if self.state_extra is None else
                       f" kind={self.kind.name} " + " ".join(
                           f"{name}_bytes={x.nbytes/1e6:.1f}MB{list(x.shape)}"
                           for name, x in sorted(self.state_extra.items())))
                    + f" setup_s={(setup.record.end_ns - setup.record.start_ns) / 1e9:.2f} "
                    f"({phases}; compiling {setup.record.compile_ns / 1e9:.2f}) "
                    f"process_age_s={'n/a' if age is None else f'{age / 1e9:.2f}'}")

    def _construct(self, setup, model, config, params, model_config, dtype, rng):
        """The constructor's work, under its ``setup`` record."""
        # made or taken, cast, quantized, sharded - launched, not waited for
        setup.phase("setup.params")
        self._config = config or RaggedInferenceEngineConfig()
        sm = self._config.state_manager
        self.dtype = dtype

        if model_config is None:
            model_config = model.config
        self.model_config = model_config
        cfg = self.model_config
        kind = self.kind = kind_of(cfg)
        # Serving mesh (reference engine_v2.py:30 builds the model over its
        # TP group via model_implementations/sharding/): tensor- and, for
        # MoE, expert-parallel. Params/KV-pool are placed sharded so models
        # larger than one chip serve.
        tp = int(self._config.tensor_parallel_degree)
        ep = int(self._config.expert_parallel_degree)
        if tp * ep > 1:
            from deepspeed_tpu.parallel.topology import make_mesh_topology
            assert tp * ep <= len(jax.devices()), \
                f"tp={tp} x ep={ep} exceeds {len(jax.devices())} visible devices"
            self.mesh = make_mesh_topology(tensor=tp, expert=ep, data=1,
                                           devices=jax.devices()[:tp * ep])
        else:
            self.mesh = None

        # ZeRO-Inference weight-only quantization for the ragged path
        # (reference inference/v2 + FP6-LLM serving, including its sharded
        # TP2 headline): quantized bytes live in HBM, the jitted step
        # dequantizes per leaf and XLA fuses the decode into each consuming
        # matmul. Quantization happens BEFORE sharding in the grouped
        # (structure-preserving) layout so each quantized carrier takes the
        # leaf's own PartitionSpec.
        qmode = getattr(self._config.quantization, "quantization_mode", "none")
        self._qmode = qmode
        self._quantized = bool(qmode and qmode != "none")
        if kind.state_kind != "kv" or kind.refuses:
            self._refuse_unsupported(kind, tp * ep)
        if params is not None:
            owns = all(not isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(params))
            self.params = self._place_params(params, owns)
        else:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            self.params = self._init_params(model, rng)
        # the two paged pools, the sequence table, the kind's own state and its slots
        setup.phase("setup.pools")
        # monotone weight-version tag: bumped by swap_params (live weight
        # refresh); stamped into the prefix trie's root key so every
        # cached KV identity — and every exported handoff record — is
        # version-tagged (version 0 == the trie's historical root key)
        self.weight_version = 0

        self.max_tokens = int(sm.max_ragged_batch_size)
        self.max_seqs = int(sm.max_ragged_sequence_count)
        self.block_size = int(self._config.kv_block_size)
        self.max_blocks_per_seq = -(-int(sm.max_context) // self.block_size)
        num_blocks = int(self._config.num_kv_blocks) or (
            1 + self.max_seqs * self.max_blocks_per_seq)
        if not int(self._config.num_kv_blocks):
            # Derived sizing (max_seqs x max_context worst case) can dwarf
            # HBM for wide-KV models — e.g. the default 512-seq manager at
            # 20 KV heads x Dh 128 derives a 43 GB pool. Cap the DEFAULT
            # at 8 GB PER POOL SHARD (the pool shards whole KV heads over
            # the 'tensor' axis when divisible) with a warning; an explicit
            # num_kv_blocks is honored as given.
            bytes_per_block = (kind.state_layers(cfg) * self.block_size *
                               sum(kind.state_rows(cfg)) * jnp.dtype(dtype).itemsize)
            pool_shards = 1
            if self.mesh is not None:
                tp_size = dict(self.mesh.shape).get("tensor", 1)
                if cfg.num_key_value_heads % max(tp_size, 1) == 0:
                    pool_shards = tp_size
            cap = max(2, int(8e9 * pool_shards // bytes_per_block))
            if num_blocks > cap:
                logger.warning(
                    f"derived KV pool ({num_blocks} blocks, "
                    f"{num_blocks * bytes_per_block / 1e9:.1f} GB) exceeds the 8 GB "
                    f"default budget — capping at {cap} blocks; set "
                    f"num_kv_blocks or a smaller state_manager to silence")
                num_blocks = cap
        pool = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from deepspeed_tpu.inference.v2.sharding import kv_pool_spec
            pool = NamedSharding(self.mesh, kv_pool_spec(self.mesh, cfg.num_key_value_heads))
        self.kv_cache = BlockedKVCache(kind.state_layers(cfg), num_blocks, self.block_size,
                                       cfg.num_key_value_heads, getattr(cfg, "head_dim", None),
                                       dtype=dtype, sharding=pool, state_kind=kind.state_kind,
                                       row_widths=kind.state_rows(cfg))
        # what the pool holds, for whoever sizes or reads it (docs/OBSERVABILITY.md)
        self.state_kind = kind.state_kind
        self._state_step_said = set()       # the programs whose state step has been logged
        self.state_bytes_per_token = self.kv_cache.bytes_per_token()
        # the layers of parameters under the pools' ``state_layers``: fewer where a layer
        # keeps no paged state, **fewer still where the stack runs several times** (Ouro)
        self.param_layers = getattr(cfg, "num_hidden_layers", None) or cfg.num_layers
        # A kind with window layers: a second pool for their keys and values, whose blocks
        # a sequence gives back as they fall behind its window. Its device arrays are the
        # programs' ``extra`` (carried and donated like any kind's), its table a sequence
        # the batch's ``seq_state`` row (a ring: ragged_manager); None for every other kind.
        self.window_pool = None
        self._seq_rows = kind.seq_rows
        slots = int(sm.max_tracked_sequences)
        window = kind.window(cfg)
        if window is not None:
            positions, window_layers = window
            self.window_pool = WindowPool(
                positions, self.block_size, self.max_tokens,
                int(self._config.num_window_blocks) or WindowPool.default_blocks(
                    positions, self.block_size, self.max_tokens, slots))
            self._seq_rows = self.window_pool.ring
        self.state_manager = DSStateManager(self.kv_cache, slots,
                                            max_blocks_per_seq=self.max_blocks_per_seq,
                                            seq_rows=self._seq_rows,
                                            window_pool=self.window_pool)
        # State beyond the two paged pools, where the model kind keeps any: its own tree
        # of device arrays, carried and donated through every program beside the pools,
        # and a slot of it a tracked sequence (ragged/slot_pool.py). None for a kind
        # that has none, whose programs are then the ones they were.
        self.slot_pool = None
        if window is not None:
            self.state_extra = self.window_pool.arrays(window_layers, kind.state_rows(cfg)[0], dtype)
        else:
            from deepspeed_tpu.inference.v2.prefix_cache import prefix_cache_enabled
            if kind.snapshots and prefix_cache_enabled(self._config.prefix_cache):
                # the prefix cache's own slots, beyond the tracked sequences', in the same
                # arrays: a trailing snapshot a sequence where nobody says how many
                slots += int(self._config.prefix_cache.snapshot_slots) or slots
            self.state_extra = kind.extra_state(cfg, num_blocks, slots, dtype)
        if self.state_extra is not None and kind.slot_state:
            # a slot is one row of each entry the kind names (their second axis)
            self.slot_pool = SlotPool(slots, sum(
                self.state_extra[name].nbytes // self.state_extra[name].shape[1]
                for name in kind.slot_state))
        # what the config turns on for this model kind (prefix cache, spill tier, drafting,
        # adapters, schemas), the step's host batch and the attention table
        setup.phase("setup.kind")
        # Radix prefix cache (cross-request KV reuse): config-gated with
        # the DS_PREFIX_CACHE env kill switch. When live, retired
        # sequences' full blocks become content-addressable and new
        # prompts start past their longest cached prefix.
        from deepspeed_tpu.inference.v2.prefix_cache import (PrefixCacheManager,
                                                             prefix_cache_enabled)
        self.prefix_cache = None
        if prefix_cache_enabled(self._config.prefix_cache):
            self.prefix_cache = PrefixCacheManager(
                self.kv_cache,
                max_cached_blocks=int(self._config.prefix_cache.max_cached_blocks),
                slot_pool=self.slot_pool)
            self.state_manager.attach_prefix_cache(self.prefix_cache)
        # a kind with snapshots: the copies a step asked for (slot -> slot, on the device,
        # dispatched behind the step's program) and their counts for its record
        self._copy_slots_fn = None
        self._snapshots_restored = 0
        self._snapshot_counts = {}      # a step record's seq -> (taken, restored)
        self._snapshotting = self.prefix_cache is not None and self.slot_pool is not None
        # Host-RAM KV spill tier (tier-2): trie eviction demotes blocks
        # into a byte-budgeted host store instead of dropping them.
        # Config-gated with the DS_KV_TIER env kill switch; layered on
        # the prefix cache (tier-2 keys ARE the trie's chained hashes),
        # so without a prefix cache it cannot exist.
        from deepspeed_tpu.inference.v2.kv_tier import (TierManager,
                                                        kv_tier_bytes,
                                                        kv_tier_enabled,
                                                        kv_tier_quantized)
        self.kv_tier = None
        if kv_tier_enabled(self._config.kv_tier):
            if self.prefix_cache is None:
                logger.warning(
                    "kv_tier enabled but the prefix cache is off — the "
                    "spill tier stores evicted TRIE blocks, so it is "
                    "inert without one; skipping")
            else:
                tier_cfg = self._config.kv_tier
                self.kv_tier = TierManager(
                    self.prefix_cache,
                    capacity_bytes=kv_tier_bytes(tier_cfg),
                    quantize=kv_tier_quantized(tier_cfg),
                    quant_group_size=int(tier_cfg.quant_group_size),
                    prefetch=bool(tier_cfg.prefetch))
                self.prefix_cache.attach_tier(self.kv_tier)
        # Self-speculative decoding (n-gram drafting + batched verify):
        # config-gated with the DS_SPEC_DECODE env kill switch. When
        # live, schedulers draft via propose_drafts() and score drafts
        # in one forward via verify_burst().
        from deepspeed_tpu.inference.v2.spec import (SpecDecodeState,
                                                     spec_decode_enabled)
        self.spec = None
        if spec_decode_enabled(self._config.spec_decode):
            self.spec = SpecDecodeState(self._config.spec_decode)
        # Multi-tenant LoRA serving: config-gated with the DS_LORA env
        # kill switch. When live, per-request adapter ids bind to hot
        # AdapterStore slots and every forward adds the segmented
        # adapter delta; OFF, nothing below changes — the batch wire
        # format, step signatures, and burst program keys are exactly
        # the pre-LoRA ones.
        from deepspeed_tpu.serving.lora import (AdapterStore, lora_hot_set,
                                                lora_max_rank,
                                                lora_serving_enabled)
        self.lora_store = None
        if lora_serving_enabled(self._config.lora):
            if not kind.lora:
                logger.warning(
                    "lora serving enabled but the model is GPT-family — "
                    "the segmented adapter path targets the Llama layer "
                    "stack; serving base-only")
            else:
                lcfg = self._config.lora
                H, Hkv, Dh = (cfg.num_attention_heads,
                              cfg.num_key_value_heads, cfg.head_dim)
                dims = {"q_proj": (cfg.hidden_size, H * Dh),
                        "k_proj": (cfg.hidden_size, Hkv * Dh),
                        "v_proj": (cfg.hidden_size, Hkv * Dh),
                        "o_proj": (H * Dh, cfg.hidden_size)}
                self.lora_store = AdapterStore(
                    dims, cfg.num_hidden_layers,
                    n_hot=lora_hot_set(lcfg),
                    max_rank=lora_max_rank(lcfg),
                    host_bytes=int(lcfg.host_bytes),
                    publish_root=(lcfg.publish_root or None),
                    prefetch=bool(lcfg.prefetch), dtype=dtype)
        # Structured (grammar/JSON-schema constrained) decoding:
        # config-gated with the DS_CONSTRAINED env kill switch. When
        # live, bound schemas install token-DFA slabs and the sampled
        # programs gather a per-sequence logits mask from them; OFF,
        # nothing below changes — wire formats and program keys are
        # exactly the pre-structured ones.
        from deepspeed_tpu.inference.structured import constrained_enabled
        from deepspeed_tpu.inference.structured.store import StructuredStore
        self.structured = None
        if constrained_enabled(self._config.structured):
            scfg = self._config.structured
            self.structured = StructuredStore(
                int(cfg.vocab_size),
                max_schemas=int(scfg.max_schemas),
                max_states=int(scfg.max_states))
        # the per-sequence KV-content token log feeds BOTH the prefix
        # cache (retire-time content addressing) and the n-gram drafter
        self._log_tokens = self.prefix_cache is not None or self.spec is not None
        # positions are bounded by BOTH the block table and the RoPE table
        self.max_ctx_tokens = min(self.max_blocks_per_seq * self.block_size,
                                  int(cfg.max_position_embeddings))
        self._batch = RaggedBatchWrapper(self.max_tokens, self.max_seqs,
                                         self.max_blocks_per_seq,
                                         lora=self.lora_store is not None,
                                         seq_rows=self._seq_rows)
        # the row counts a put may run at (put_ladder), and the (mode, rows) a put or
        # build_put_programs has run: each is one compiled program. No rung where the
        # kind's expert layers run behind a share (``n_share_passes`` among its counts):
        # laguna-xs2-ep8-20l's 256-row program never came back from its first step on the
        # chip, and did with ``lax.ragged_dot`` for the share's grouped matmul, or with
        # 1024 rows a pass of it for 512; the layer alone runs at every size, and which op
        # of that one compile waits is not known (PERF.md section 6, PR 55)
        self.put_buckets = put_ladder(self.max_seqs, self.max_tokens,
                                      rungs="n_share_passes" not in kind.step_counts)
        self._put_built, self._put_mode = set(), None   # ... and the mode of the last
        mesh = self.mesh
        # the config's attention pin, and (filled as programs trace) the
        # implementation each program actually selected — see
        # :attr:`attention_impls`
        from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
        self._attention = AttentionChoice(
            (self._config.implementation_overrides or {}).get("attention"))
        attn_impl = self._attention
        quantized = self._quantized
        # DS_SANITIZE sampled ONCE at construction: when off every step
        # below is a plain jax.jit (identical HLO); when on the steps are
        # checkified (NaN/Inf + OOB-gather checks in the traced forward).
        self._sanitize = sanitize_enabled()
        sanitize = self._sanitize

        # the step functions wrapped for jit (none is traced before its first call), the
        # burst programs' table, the sampling keys
        setup.phase("setup.programs")
        ms, mb = self.max_seqs, self.max_blocks_per_seq
        lora_on = self.lora_store is not None
        seq_rows = self._seq_rows

        # ``xc``, in every program: the kind's state beyond the two pools
        # (``self.state_extra``), donated like them and returned last; None — no
        # argument and no result of the compiled program — for a kind that has none.
        def step(p, kc, vc, xc, packed, lora_slabs=None):
            # one flat int32 metadata vector per step (single host→device
            # transfer); static slices rebuild the batch dict on device.
            # The vector's length IS the token bucket, so decode-sized
            # and budget-sized batches compile separate specializations.
            from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import unpack_batch
            b = unpack_batch(packed, ms, mb, lora=lora_on, seq_rows=seq_rows)
            if quantized:
                # embed/head/norm leaves dequantize here; the scanned
                # 'layers' stack stays quantized — each scan step
                # dequantizes only its own slice (model_runner) so peak
                # HBM holds the quantized stack + O(1 layer) transient.
                from deepspeed_tpu.inference.quantization import \
                    dequantize_tree_except
                p = dequantize_tree_except(p, dtype)
            lora_arg = None
            if lora_slabs is not None:
                la, lb, scales = lora_slabs
                lora_arg = (la, lb, scales, b["seq_adapters"], None)
            return forward(p, kc, vc, xc, b, lora_arg)

        def forward(p, kc, vc, xc, b, lora_arg):
            return ragged_forward(p, kc, vc, b, cfg, dtype, mesh=mesh, attn_impl=attn_impl,
                                  lora=lora_arg, extra=xc)

        self._step = maybe_checkify_jit(step, donate_argnums=(1, 2, 3),
                                        enabled=sanitize)

        def step_greedy(p, kc, vc, xc, b, lora_slabs=None):
            # (a model kind that counts on the device gives its counts fourth: they
            # ride out beside the tokens, here and in every program below)
            logits, kc, vc, *counts, xc = step(p, kc, vc, xc, b, lora_slabs)
            # On-device greedy sampling: ship [n_seqs] int32 tokens to the
            # host instead of [n_seqs, vocab] fp32 logits — vocab-factor
            # less PCIe traffic per decode step (servers sample on-device
            # for the same reason; reference FastGen returns logits only
            # because torch keeps them resident).
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), kc, vc, *counts, xc)

        self._step_greedy = maybe_checkify_jit(step_greedy, donate_argnums=(1, 2, 3),
                                               enabled=sanitize)

        # ONE sampled program for every per-sequence spec: temperature /
        # top_k / top_p / seed (+ DFA slot/state) ride the packed batch
        # as int32 DATA, so multi-tenant sampled traffic cannot explode
        # the jit cache the way per-(t, k, p) specializations did. Rows
        # whose temperature bits are 0.0 take the argmax branch, so one
        # program serves any mix of greedy/sampled/constrained rows.
        structured_on = self.structured is not None

        def step_sampled(p, kc, vc, xc, packed, base, slabs=None, lora_slabs=None):
            from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import unpack_batch
            b = unpack_batch(packed, ms, mb, lora=lora_on, sampled=True, seq_rows=seq_rows)
            if quantized:
                from deepspeed_tpu.inference.quantization import \
                    dequantize_tree_except
                p = dequantize_tree_except(p, dtype)
            lora_arg = None
            if lora_slabs is not None:
                la, lb, scales = lora_slabs
                lora_arg = (la, lb, scales, b["seq_adapters"], None)
            logits, kc, vc, *counts, xc = forward(p, kc, vc, xc, b, lora_arg)
            temp, topk, topp, seed, slot, state = unpack_sample_meta(
                b["sample_meta"], ms)
            if slabs is not None:
                logits = apply_dfa_mask(logits, slabs[0], slot, state)
            # the token this step emits lands one past the row's last
            # scheduled token — the SAME absolute position (and so the
            # same counter key) every other path derives for it
            pos_out = b["token_pos"][b["last_index"]] + 1
            keys = token_keys(base, seed, pos_out)
            return (sample_rows(logits, keys, temp, topk, topp), kc, vc, *counts, xc)

        if structured_on and lora_on:
            sampled_fn = step_sampled
        elif structured_on:
            sampled_fn = lambda p, kc, vc, xc, packed, base, slabs: \
                step_sampled(p, kc, vc, xc, packed, base, slabs)
        elif lora_on:
            sampled_fn = lambda p, kc, vc, xc, packed, base, lslabs: \
                step_sampled(p, kc, vc, xc, packed, base, None, lslabs)
        else:
            sampled_fn = lambda p, kc, vc, xc, packed, base: \
                step_sampled(p, kc, vc, xc, packed, base)
        self._step_sampled = maybe_checkify_jit(sampled_fn, donate_argnums=(1, 2, 3),
                                                enabled=sanitize)
        # LRU of compiled multi-step programs: ("burst", k, sample_key)
        # decode bursts and ("verify", d) speculative verifies. Bounded —
        # spec decoding adds a draft-length dimension to the key space,
        # and an unbounded map would pin every program's HLO forever.
        self._burst_fns = OrderedDict()
        self._burst_fn_cap = max(1, int(self._config.burst_fn_cache_cap))
        self.burst_fn_evictions = 0
        # How many decode bursts the scheduler keeps in flight, unfetched.
        # 0: every burst is fetched in the call that dispatched it; 2 is
        # the double buffer. One burst program family serves every depth.
        self.async_burst_depth = max(0, int(self._config.async_burst.depth))
        # Host-sync accounting: host_syncs increments at every pragma'd
        # host-sync site EXECUTION (the graft-lint host-sync rule maps
        # the sites; the counter measures how often serving actually
        # pays them); tokens_emitted counts tokens handed to callers as
        # per-sequence step/burst outputs. Their ratio is the
        # syncs_per_generated_token the serving lanes report — the
        # number the pipelined pump exists to drive toward 1/k.
        self.host_syncs = 0
        self.tokens_emitted = 0
        # the step record of the program run last — the scheduler reads its seq
        self.last_step = None
        # host work a caller leaves for the time a program runs, called between
        # every fetched program's dispatch and its fetch: the scheduler hands the
        # step before's tokens to their streams there. The fetch is in a finally:
        # a dispatched program is fetched whatever this raises
        self.while_running = None
        self._suspended = {}  # uid -> {"handle": host KV, "seen_tokens": int}
        # Counter-PRNG root for sampling: every sampled token's key folds
        # (request seed, absolute position) into this DS_SEED-derived
        # base. Sampling never consumes a sequential stream, so a replica
        # replaying a half-finished request reproduces it bit-identically
        # — requests decorrelate through their per-request seed, NOT
        # through replica-local entropy (the old os.urandom fallback,
        # which silently broke failover replay the moment anyone sampled).
        self._base_key = base_sampling_key(env_int("DS_SEED"))
        # per-request seed fallback stream (draw_seed), decorrelated from
        # the param-init key; DS_SEED-rooted so it is deterministic by
        # default. Pass rng explicitly to decorrelate engines in-process.
        if rng is None:
            rng = jax.random.PRNGKey(env_int("DS_SEED"))
        self._rng = jax.random.fold_in(rng, 7)
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as _P
            self._replicated = NamedSharding(self.mesh, _P())

    # ------------------------------------------------------------------
    def _refuse_unsupported(self, kind, n_devices):
        """A model kind whose state is not keys and values alone (a latent
        row, a selection's pooled keys, a slot of state that is not paged
        rows at all) is served by the core path only: every optional
        subsystem that reads, moves or shards the two KV pools refuses it
        here, at construction and by name, instead of failing inside a
        program: the KV tier and the handoff, ``suspend``, speculative
        decoding, LoRA, quantization and sharding on every such kind, and
        the prefix cache on ``latent``, ``sparse_kv+slots``, ``kv+window``
        and every ``kv+slots`` kind but one that says its slots may be
        snapshotted (``kind.snapshots``). A kind whose state is keys and values refuses what it
        names itself (``kind.refuses``: a stack run several times has no
        adapter slabs a pass) and keeps the rest."""
        from deepspeed_tpu.inference.v2.kv_tier import kv_tier_enabled
        from deepspeed_tpu.inference.v2.prefix_cache import prefix_cache_enabled
        from deepspeed_tpu.inference.v2.spec import spec_decode_enabled
        from deepspeed_tpu.serving.lora import lora_serving_enabled
        c = self._config
        asked = {
            "prefix cache": prefix_cache_enabled(c.prefix_cache),
            "KV tier (and the disaggregated handoff, which exports through it)":
                kv_tier_enabled(c.kv_tier),
            "speculative decoding": spec_decode_enabled(c.spec_decode),
            "LoRA serving": lora_serving_enabled(c.lora),
            "weight-only quantization": self._quantized,
            "tensor/expert-parallel sharding": n_devices > 1,
        }
        for subsystem, on in asked.items():
            if on and subsystem == "prefix cache" and kind.snapshots:
                # keys and values beside slots that may be snapshotted: the cache keeps a
                # copy of a sequence's slot at a block boundary (prefix_cache/manager.py).
                # The other kinds of such state (Nemotron-H, LFM2, Jamba, Solar Open 2:
                # ``snapshots`` False) are refused below by name until a cell runs them.
                continue
            if on and (kind.state_kind != "kv" or subsystem in kind.refuses):
                raise NotImplementedError(
                    f"{subsystem} does not support the {kind.state_kind!r} state of "
                    f"model kind {kind.name!r} ({type(self.model_config).__name__}); "
                    f"turn it off to serve this model")

    def _init_params(self, model, rng):
        """Random-initialize ``model`` straight into serving placement:
        one jitted program whose outputs are already cast to the serving
        dtype and sharded over the mesh, so neither an fp32 copy of the
        whole model nor all of it on the first device ever exists (an
        eager ``model.init`` does both). Quantized serving needs the
        full-precision tree first and goes through :meth:`_place_params`."""
        def init(rng):
            return model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]

        if self._quantized:
            return self._place_params(init(rng), owns=True)

        def init_cast(rng):
            return jax.tree.map(
                lambda x: x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                init(rng))

        shardings = None
        if self.mesh is not None:
            from deepspeed_tpu.inference.v2.sharding import param_sharding, tp_rule_for
            from deepspeed_tpu.runtime.zero.partitioning import path_tree_map
            rule = tp_rule_for(self.model_config)
            shardings = path_tree_map(
                lambda path, x: param_sharding(self.mesh, rule, path, x.shape),
                jax.eval_shape(init, rng))
        return jax.jit(init_cast, out_shardings=shardings)(rng)

    def _place_params(self, params, owns):
        """Quantize/shard/cast a raw param tree into serving placement —
        the constructor's path, reused verbatim by :meth:`swap_params` so
        refreshed weights land bit-identical to a cold start."""
        if self._quantized:
            # One jitted program with the source donated so XLA frees each
            # full-precision leaf as its carrier forms — no full-tree +
            # carriers memory spike. Donation is safe when the engine owns
            # the tree: it built the params itself, or every caller leaf is
            # a host array whose jnp.asarray device copy is exclusively
            # ours (an existing jax.Array would be returned as-is and must
            # not be deleted out from under the caller).
            from deepspeed_tpu.inference.quantization.quantization import \
                quantize_params_tree
            params = jax.tree.map(jnp.asarray, params)
            params = jax.jit(
                lambda p: quantize_params_tree(p, self._qmode,
                                               dequant_dtype=self.dtype),
                donate_argnums=(0,) if owns else ())(params)
        if self.mesh is not None:
            from deepspeed_tpu.inference.v2.sharding import shard_params, tp_rule_for
            return shard_params(params, self.mesh, tp_rule_for(self.model_config),
                                dtype=self.dtype)
        from deepspeed_tpu.inference.quantization import QuantizedWeight
        return jax.tree.map(
            lambda x: x if isinstance(x, QuantizedWeight)
            else x.astype(self.dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params, is_leaf=lambda x: isinstance(x, QuantizedWeight))

    def swap_params(self, new_params, version):
        """Live weight refresh: adopt ``new_params`` in place, bumping
        :attr:`weight_version` and invalidating every piece of KV derived
        from the old weights (prefix trie, tier-2 store, staged copies,
        suspended host KV). Donated-buffer-safe by construction: no
        jitted step donates the params argument (``donate_argnums``
        covers only the KV pool), so rebinding ``self.params`` can never
        race a compiled program over freed buffers — and the compiled
        programs themselves are shape-stable, so NOTHING recompiles.

        PUMP-THREAD ONLY and requires an idle engine (no tracked or
        suspended sequences): the serving gateway quiesces in-flight
        work before calling this. Returns the adopted version."""
        if self.state_manager is None:
            raise RuntimeError("swap_params on a destroyed engine")
        if self.state_manager.n_tracked_sequences:
            raise RuntimeError(
                f"swap_params with {self.state_manager.n_tracked_sequences} "
                f"live sequence(s) — quiesce the engine first")
        if self._suspended:
            raise RuntimeError(
                f"swap_params with {len(self._suspended)} suspended "
                f"sequence(s) — their host KV predates the new weights")
        version = int(version)
        owns = all(not isinstance(leaf, jax.Array)
                   for leaf in jax.tree.leaves(new_params))
        self.params = self._place_params(new_params, owns)
        self.weight_version = version
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate_for_version(version)
        if self.lora_store is not None:
            # hot adapter deltas were tuned against the OLD base weights;
            # drop them so every tenant re-adopts against the new base
            self.lora_store.invalidate()
        return version

    # ------------------------------------------------------------------
    def put(self, batch_uids, batch_tokens, do_checks=True, sample=None):
        """Run one ragged batch: ``batch_tokens[i]`` are the NEW tokens
        (full prompt, a prefill chunk, or one decode token) for
        ``batch_uids[i]``. Returns fp32 logits ``[len(uids), vocab]``
        for each sequence's last scheduled token — or, with
        ``sample="greedy"``, int32 argmax token ids ``[len(uids)]``
        sampled on device (vocab-factor less host traffic per step).

        A ``{"temperature", "top_k", "top_p", "seed"}`` dict samples on
        device with per-sequence counter-PRNG keys; a per-uid LIST of
        dict/None mixes sampled and greedy rows in one batch (ONE
        compiled program serves every spec — the parameters ride the
        packed batch as data). Sequences with a bound schema
        (:meth:`bind_schema`) additionally gather their DFA logits mask
        on device and MUST use an on-device mode (``"greedy"`` or a
        spec): the raw-logits path cannot enforce the constraint.

        ``do_checks`` exists for reference API parity but is ignored:
        validation is what keeps sequence state consistent with the KV
        pool, so it always runs."""
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
                    mode = "packed"  # greedy rows still need the DFA mask rows
                    specs = specs if specs is not None else [None] * len(batch_uids)
                # host-side list→array prep on caller-provided tokens, no device sync
                self.count_host_sync()
                sm, n = self.state_manager, len(batch_uids)
                try:
                    lens = np.fromiter(map(len, batch_tokens), np.int64, n)
                except TypeError:   # a bare token is a chunk of one
                    batch_tokens = [np.atleast_1d(t) for t in batch_tokens]
                    lens = np.fromiter(map(len, batch_tokens), np.int64, n)
                total = int(lens.sum())
                tokens = np.fromiter(itertools.chain.from_iterable(batch_tokens), np.int32, total)
                # Validate the WHOLE batch before touching any sequence state: a
                # failure after allocate/advance would leave earlier
                # sequences claiming KV that was never written.
                if total > self.max_tokens:
                    raise ValueError(f"batch has {total} tokens > "
                                     f"max_ragged_batch_size={self.max_tokens}")
                if n > self.max_seqs:
                    raise ValueError(f"{n} sequences > "
                                     f"max_ragged_sequence_count={self.max_seqs}")
                descs = [sm.query(uid) for uid in batch_uids]
                seen, need = self._seen_and_need(descs, lens)
                bad = seen + lens > self.max_ctx_tokens
                if self.slot_pool is not None:
                    # how such a model attends to a prompt's rows depends on the whole
                    # prompt's length, which a first chunk does not say
                    untold = np.fromiter([desc is None or desc.state_row is None
                                          for desc in descs], bool, n)
                    bad |= untold
                if bad.any():
                    i = int(np.argmax(bad))     # the first sequence at fault, as a walk finds it
                    if self.slot_pool is not None and untold[i]:
                        raise ValueError(
                            f"sequence {batch_uids[i]}: a {self.kind.name!r} model needs the "
                            f"whole prompt before its first chunk — call prefix_match(uid, "
                            f"prompt) first (a scheduler does)")
                    raise ValueError(f"sequence {batch_uids[i]}: {seen[i]}+{lens[i]} tokens "
                                     f"exceed max_context={self.max_ctx_tokens}")
                blocks_needed = int(need.sum())
                if blocks_needed > self._reclaimable_blocks():
                    raise RuntimeError(f"KV pool exhausted: need {blocks_needed} blocks, "
                                       f"{self._reclaimable_blocks()} reclaimable — "
                                       f"flush() sequences first")
                need_window = self._window_need(descs, lens)
                new_seqs = descs.count(None)
                if new_seqs + sm.n_tracked_sequences > sm.max_tracked_sequences:
                    raise RuntimeError("max_tracked_sequences exceeded for this batch")

                written = sm.rows_written
                if new_seqs:
                    descs = [sm.get_or_create_sequence(uid) if desc is None else desc
                             for uid, desc in zip(batch_uids, descs)]
                sm.reserve(descs, need)
                if need_window is not None:
                    sm.reserve_window(descs, need_window)
                adapters = self._seat(descs)    # sequence i takes row i of the step's tables
                self._batch.clear()
                _, seq_state = sm.gather(descs, out=self._batch.block_tables[:n])
                self._batch.insert_batch(0, seen, lens, tokens, adapters=adapters,
                                         seq_state=seq_state)
                for desc, new in zip(descs, lens.tolist()):
                    desc.advance(new)
                if need_window is not None:
                    sm.release_behind(descs)    # the step's tables are gathered: see _window_need
                rec.n_ctx_tokens += int((seen + lens).sum())
                rec.n_table_rows_written = sm.rows_written - written
                if self._log_tokens:
                    # content log: retire-time insertion into the prefix
                    # trie, and the n-gram drafter's lookup corpus. A host
                    # append must land AFTER any pending device segments
                    # from drained pipelined bursts, so fence first (a
                    # cached re-read once the scheduler has fetched them)
                    for desc, chunk in zip(descs, batch_tokens):
                        desc.tokens.fence()
                        desc.tokens.extend(int(t) for t in chunk)
                # the smallest program that holds the step (put_ladder): a pure decode
                # round runs the decode-sized one, a short prompt beside its decode rows
                # the rung where there is one, a full chunk the budget-sized one. One
                # program a rung and mode — shapes stay static per bucket.
                bucket = self.put_buckets[bisect.bisect_left(self.put_buckets, total)]
                if mode == "packed":
                    # resolve engine-stream seeds for specs submitted without one
                    for s in specs:
                        if s is not None and "seed" not in s:
                            s["seed"] = self.draw_seed()
                arrays = self._packed_batch(bucket, mode, specs, batch_uids)
                rec.program, rec.n_seqs, rec.n_tokens = str(bucket), n, total
                rec.n_rows = bucket
                # without a scheduler to say which chunks are prompt: rows longer than one
                rec.n_prompt_tokens = int(lens[lens > 1].sum())
            with tracing.phase("engine.dispatch"):
                out, counts = self._dispatch_put(mode, bucket, arrays)
                if self._snapshotting:
                    self._snapshot_landings(descs, rec)
            self.count_host_sync()
            self.tokens_emitted += len(batch_uids)
            try:
                self._note_chunks(rec)  # while the program runs
                if self.while_running is not None:
                    self.while_running()
            finally:
                with tracing.phase("engine.fetch"):
                    host, *counts = jax.device_get((out, *counts))  # ds-lint: disable=host-sync -- THE one intended sync per step: callers consume host tokens/logits
                    host = host[:n]
                    self._note_counts(rec, counts)
            self.last_step = rec
            return host

    def _packed_batch(self, bucket, mode, specs=(), batch_uids=()):
        """The batch ``self._batch`` holds as ONE flat int32 vector at
        ``bucket`` rows, as the ``put`` program of ``mode`` takes it: in
        ``"packed"`` mode the sampling specs of ``batch_uids`` (and their DFA
        slot and state, with constrained decoding on) ride the same vector,
        six int32 rows a sequence."""
        arrays = self._batch.finalize_packed(bucket=bucket)
        if mode == "packed":
            dfa = None
            if self.structured is not None:
                dfa = [(self.structured.slot_of(u), self.structured.state_of(u))
                       for u in batch_uids]
            arrays = np.concatenate(
                [arrays, pack_sample_meta(specs, self.max_seqs, dfa=dfa)])
        if self.mesh is not None:
            # batch metadata is replicated over the serving mesh (the flat
            # token batch carries no sharding — only weights/KV do)
            arrays = jax.device_put(arrays, self._replicated)
        return arrays

    def _dispatch_put(self, mode, bucket, arrays):
        """Launch the ``put`` program of ``mode`` over ``arrays``
        (:meth:`_packed_batch` at ``bucket`` rows): the pools and the kind's
        further state are donated and taken back. → ``(out, counts)``, device
        arrays; nothing is waited for."""
        # hot adapter slabs ride as jit ARGUMENTS (not captured constants)
        # so promotions/hot-swaps rebind buffers without any retrace
        extra = (self.lora_store.slabs(),) if self.lora_store is not None else ()
        if mode == "packed":
            fn, sargs = self._step_sampled, (self._base_key,)
            if self.structured is not None:
                sargs += (self.structured.slabs(),)  # rebind, never retrace
        else:
            fn, sargs = (self._step_greedy if mode == "greedy" else self._step), ()
        out, self.kv_cache.k, self.kv_cache.v, *counts, self.state_extra = fn(
            self.params, self.kv_cache.k, self.kv_cache.v, self.state_extra, arrays,
            *sargs, *extra)
        self._put_mode = mode
        self._put_built.add((mode, bucket))
        return out, counts

    def build_put_programs(self, max_tokens=None):
        """Build the ``put`` programs of the mode the last ``put`` ran in
        (``"logits"``, ``"greedy"`` or ``"packed"``, :meth:`_classify_sample`'s)
        over ``max_seqs`` rows that no step has run yet
        - those a step of at most ``max_tokens`` tokens can land on (a
        scheduler's budget; None: the engine's) - by running each on a
        **batch of the null sequence alone**: one token of a sequence whose
        table is all null blocks and whose state row is the null slot's, every
        other row padding, so every write lands in the null block and the null
        slot and the pools hold afterwards what they held. Whoever serves traffic calls this after a
        prompt step (a scheduler does; two set look-ups once every size is
        built): the stall of a compile then comes once, before traffic, and
        not again at the first step that fits another rung. A caller of
        :meth:`put` alone compiles each program at its first use, as ever.
        Each build is a step record of kind ``build`` - no reader of the
        programs' steps counts it, and the build table lists the program under
        this engine. PUMP-THREAD ONLY, with no burst in flight. → the row
        counts built."""
        mode, built = self._put_mode, []
        reach = bisect.bisect_left(self.put_buckets, max_tokens or self.max_tokens)
        for bucket in self.put_buckets[1:reach + 1]:
            if (mode, bucket) in self._put_built:
                continue
            with tracing.step("build", engine=self.trace_id, program=str(bucket),
                              n_rows=bucket) as rec:
                with tracing.phase("engine.pack"):
                    # one row of the null sequence (its table all null blocks, its state
                    # row the null slot's) and padding: what a step's first token is to
                    # every kernel, where a batch of no live row at all is an input no
                    # step ever gives them
                    self._batch.clear()
                    self._batch.insert_batch(
                        0, [0], [1], [0], adapters=[0],
                        seq_state=np.zeros((1, self._seq_rows), np.int32))
                    arrays = self._packed_batch(bucket, mode)
                with tracing.phase("engine.dispatch"):
                    out, counts = self._dispatch_put(mode, bucket, arrays)
                with tracing.phase("engine.fetch"):
                    _, *counts = jax.device_get((out, *counts))  # ds-lint: disable=host-sync -- before traffic: a program's build ends with its first run
                    self._note_counts(rec, counts)
            built.append(bucket)
        return built

    def _classify_sample(self, sample, n):
        """Normalize ``put``/burst ``sample`` arguments → ``(mode,
        specs)``: ``("logits", None)`` for raw logits, ``("greedy",
        None)`` for on-device argmax, or ``("packed", [dict|None] * n)``
        with every dict VALIDATED and copied (seeds resolve later, after
        batch validation — no state mutates for a rejected batch)."""
        if sample is None:
            return "logits", None
        if sample == "greedy":
            return "greedy", None
        if isinstance(sample, dict):
            _validate_sample(sample)
            return "packed", [dict(sample) for _ in range(n)]
        if isinstance(sample, (list, tuple)):
            if len(sample) != n:
                raise ValueError(f"sample list has {len(sample)} specs for "
                                 f"{n} sequences")
            out = []
            for s in sample:
                if s is None:
                    out.append(None)
                    continue
                if not isinstance(s, dict):
                    raise ValueError(f"sample list entries are dict/None, "
                                     f"got {s!r}")
                _validate_sample(s)
                out.append(dict(s))
            if not any(s is not None for s in out):
                return "greedy", None  # all-greedy list: plain argmax program
            return "packed", out
        raise ValueError(f"sample={sample!r}: supported modes are None (logits), "
                         f"'greedy' (on-device argmax), a sampling dict "
                         f"{{'temperature', 'top_k', 'top_p', 'seed'}}, or a "
                         f"per-sequence list of dict/None")

    def _note_chunks(self, rec):
        """``rec.n_chunk_rows`` / ``n_chunk_tiles`` of the ``put`` whose batch
        is the one packed: the rows its program's paged kernel attends through
        a query tile, and the tiles - what ``paged_attention.query_tiles``
        lays for those rows inside the program, counted here from the host's
        copy of them (numpy; no device work, no sync). 0 where the program's
        kernel was given none (the gather, a latent or a selecting model
        kind), which ``model_runner._paged_attend`` has said in tracing it
        (``AttentionChoice.tiled``) by the time it is launched."""
        if rec.n_rows in self._attention.tiled:
            from deepspeed_tpu.ops.pallas.paged_attention import chunk_counts
            b = self._batch
            rec.n_chunk_rows, rec.n_chunk_tiles = chunk_counts(
                b.token_seq[:rec.n_rows], b.token_pos[:rec.n_rows], self.max_seqs,
                b.current_tokens)

    def _note_counts(self, rec, counts):
        """The device-side counts of the program behind step record ``rec``
        (``kind.step_counts``) → ``rec.counts``. ``counts``: what the
        program gave past its pools, as it came to the host in the one
        ``device_get`` that fetched the step's result (every copy is
        started before the first is waited for); empty for a kind that
        counts nothing. A program's first step says a line here (the
        start-up lines of a warm-up): what building it cost, where this
        record built it (``rec.build``: the compile events of its dispatch),
        and what serves its state step, where the model kind has one."""
        rows = rec.n_rows // max(rec.k, 1)
        said = []
        if counts:
            rec.counts = dict(zip(self.kind.step_counts, counts[0].tolist()))
            if self._snapshotting:
                taken, restored = self._snapshot_counts.pop(rec.seq, (0, 0))
                rec.counts.update(n_snapshots_taken=taken, n_snapshots_restored=restored)
            rec.state_step = self._attention.state_step.get(rows)
            if rec.state_step is not None and rows not in self._state_step_said:
                self._state_step_said.add(rows)
                said.append(f"'s state step is {rec.state_step} (kind={self.kind.name})")
        if rec.build is not None and rec.build.compiles:
            said.append(f" built in {rec.build.describe()}")
        if said:
            name = "" if rec.program == str(rows) else f" {rec.program}"
            logger.info(f"InferenceEngineV2: the {rows}-row program{name}" + ";".join(said))

    def count_host_sync(self, n=1):
        """Record ``n`` executions of a pragma'd host-sync site. Every
        place the graft-lint host-sync rule allows a sync (the inline
        ``ds-lint: disable=host-sync`` pragmas) increments this when it
        actually runs, so ``syncs_per_generated_token`` measures the
        live sync tax — not the static site count."""
        self.host_syncs += n

    @property
    def syncs_per_generated_token(self):
        """Pragma'd host-sync site executions per emitted token — the
        serving lanes' headline sync-tax metric. The stepwise loop pays
        ~2/token, a fetched-every-burst loop ~(n+1)/(n*k), and the
        pipelined pump ~1/(n*k)."""
        return round(self.host_syncs / max(self.tokens_emitted, 1), 4)

    @property
    def attention_impls(self):
        """``{token count: implementation name}`` for every program
        traced so far — which ``modules/heuristics`` attention
        implementation (``pallas_paged``, ``pallas_paged_sharded``,
        ``xla_gather``) each one compiled in."""
        return dict(self._attention.selected)

    @property
    def state_step_impls(self):
        """``{token count: implementation name}`` of the state step - Mamba-2's
        (``pallas_ssm_state`` / ``xla``: ``ops/pallas/ssm_state``), Mamba-1's
        (``pallas_selective_scan`` / ``xla``: ``ops/pallas/selective_scan``) or
        the Kimi delta rule's (``pallas_kda`` / ``xla``: ``ops/pallas/kda``) -
        for every program traced so far; empty for a model kind without one. A
        step record's ``state_step`` says the same of the program it ran."""
        return dict(self._attention.state_step)

    def draw_seed(self):
        """One per-request sampling seed from the engine's deterministic
        DS_SEED-rooted stream — the compatibility path for specs
        submitted WITHOUT an explicit ``seed`` straight at the engine /
        scheduler surface. Serving front-ends (gateway, fleet router)
        resolve seeds at submit time from the stable request uid instead,
        so cross-replica replay never depends on engine-local stream
        order."""
        self._rng, sub = jax.random.split(self._rng)
        self.count_host_sync()
        return int(jax.random.randint(sub, (), 0, 2 ** 31 - 1))  # ds-lint: disable=host-sync -- per-request seed resolution is a host decision

    # ---------------------------------------------- constrained decoding
    def bind_schema(self, uid, schema, token_strings=None, eos_token_id=None):
        """Constrain ``uid``'s generated tokens to ``schema``: a
        :class:`~deepspeed_tpu.inference.structured.grammar.CompiledSchema`,
        or a raw JSON-schema dict / regex string compiled through the
        process-wide schema cache (``token_strings`` — the vocab's
        per-token surface strings — required then). The token-DFA mask
        composes into the on-device sampling step for every subsequent
        batch containing ``uid``. → the leased device slot."""
        if self.structured is None:
            raise RuntimeError("constrained decoding is disabled "
                               "(config.structured / DS_CONSTRAINED)")
        from deepspeed_tpu.inference.structured.grammar import CompiledSchema
        if not isinstance(schema, CompiledSchema):
            if token_strings is None:
                raise ValueError(
                    "raw schemas need token_strings to compile against — "
                    "pass a CompiledSchema or the vocab surface strings")
            from deepspeed_tpu.inference.structured.store import schema_cache
            schema = schema_cache().get_or_compile(schema, token_strings,
                                                   eos_token_id=eos_token_id)
        return self.structured.bind(uid, schema)

    def advance_schema(self, uid, token):
        """Advance ``uid``'s authoritative host DFA state through one
        ACCEPTED token (no-op → 0 for unconstrained uids). Schedulers
        call this from their accept loop only — tokens a burst drew past
        EOS/max_new and then discarded never advance it, which is what
        keeps rewinds and truncation consistent with the device state
        the next batch packs."""
        if self.structured is None:
            return 0
        return self.structured.advance(uid, int(token))

    def schema_accepting(self, uid):
        """True when ``uid``'s constraint (if any) is at an accepting DFA
        state — i.e. the emitted stream so far is schema-complete and
        EOS is currently grammatical."""
        return self.structured is None or self.structured.accepting(uid)

    def _seen_and_need(self, descs, new_tokens):
        """→ ``(seen, need)``, int64 arrays over ``descs`` (None: a
        sequence not tracked yet): the tokens each has in the KV cache
        and the blocks it lacks to hold ``new_tokens`` (one number, or
        one a sequence) more."""
        n = len(descs)
        seen = np.fromiter([0 if desc is None else desc.seen_tokens for desc in descs],
                           np.int64, n)
        held = np.fromiter([0 if desc is None else len(desc.blocks) for desc in descs],
                           np.int64, n)
        return seen, np.maximum(0, -(-(seen + new_tokens) // self.block_size) - held)

    def _window_need(self, descs, new_tokens):
        """→ the window-pool blocks each of ``descs`` lacks to hold
        ``new_tokens`` more (``DSStateManager.window_need``), None for a kind
        without window layers; raises where the pool cannot give them. A
        step reserves them with the full pool's, gathers its tables, advances
        its sequences and then gives back what fell behind their windows
        (``release_behind``): the program it dispatches reads those blocks,
        and whatever writes them next is dispatched after it."""
        if self.window_pool is None:
            return None
        need = self.state_manager.window_need(descs, new_tokens)
        if int(need.sum()) > self.window_pool.free_blocks:
            raise RuntimeError(f"window pool exhausted: need {int(need.sum())} blocks, "
                               f"{self.window_pool.free_blocks} free — flush() sequences first")
        return need

    def _seat(self, descs):
        """``descs[i]`` takes row ``i`` of the step's tables (``desc.slot``:
        per batch, not the sequence's row of the manager's table). → the
        adapter slot each selects, None without LoRA serving: re-resolved
        per batch, since a hot-swap or an eviction between steps may have
        moved an adapter to another slot."""
        for i, desc in enumerate(descs):
            desc.slot = i
        if self.lora_store is None:
            return None
        adapters = [self.lora_store.slot_of(desc.uid) for desc in descs]
        for desc, slot in zip(descs, adapters):
            desc.adapter_slot = slot
        return adapters

    def _pack_rows(self, rec, plan):
        """The rows of a burst or verify program, which holds sequence
        ``i`` at row ``i`` throughout: reserve the blocks ``plan``
        (:meth:`_validate_burst`'s) found lacking and gather the tables.
        → ``(descs, token_seq [ms], pos0 [ms], tables [ms + 1, mb],
        adapters [ms + 1], seq_state [ms + 1, seq_rows] | None)``; rows
        past the sequences are padding's (the null slot, null blocks, the
        base adapter). Advancing the sequences is the caller's."""
        descs, seen, need, need_window = plan
        sm, n, ms = self.state_manager, len(descs), self.max_seqs
        written = sm.rows_written
        sm.reserve(descs, need)
        if need_window is not None:
            sm.reserve_window(descs, need_window)
        rec.n_table_rows_written = sm.rows_written - written
        tables, seq_state = sm.gather(descs, ms + 1)
        token_seq = np.full(ms, ms, np.int32)   # pad rows write the null slot
        token_seq[:n] = np.arange(n)
        pos0 = np.zeros(ms, np.int32)
        pos0[:n] = seen
        adapters = np.zeros(ms + 1, np.int32)   # pad row stays slot 0 = base
        selected = self._seat(descs)
        if selected is not None:
            adapters[:n] = selected
        return descs, token_seq, pos0, tables, adapters, seq_state

    def _validate_burst(self, batch_uids, k):
        """Shared pre-flight for the burst family (``can_burst``,
        ``decode_burst``, ``verify_burst``): every sequence must exist
        with prefilled context and room for ``k`` more tokens, and the
        pool must cover the whole up-front reservation. → ``((descs,
        seen, need), None)`` on success - the descriptors, their tokens
        in the KV cache and the blocks each lacks, as
        :meth:`_seen_and_need` gives them - ``(None, exception)`` on
        failure — raising is the caller's choice (``can_burst`` answers
        False, the burst entry points raise), so the probe and the entry
        points cannot drift."""
        descs = [self.state_manager.query(uid) for uid in batch_uids]
        seen, need = self._seen_and_need(descs, k)
        bad = (seen == 0) | (seen + k > self.max_ctx_tokens)
        if bad.any():
            i = int(np.argmax(bad))     # the first sequence at fault, as a walk finds it
            if seen[i] == 0:
                return None, ValueError(
                    f"sequence {batch_uids[i]} has no prefilled context — "
                    f"bursts continue existing sequences only")
            return None, ValueError(
                f"sequence {batch_uids[i]}: {seen[i]}+{k} tokens exceed "
                f"max_context={self.max_ctx_tokens}")
        need_total = int(need.sum())
        if need_total > self._reclaimable_blocks():
            return None, RuntimeError(
                f"KV pool exhausted: need {need_total} blocks, "
                f"{self._reclaimable_blocks()} reclaimable — "
                f"flush() sequences first")
        try:
            need_window = self._window_need(descs, k)
        except RuntimeError as e:
            return None, e
        return (descs, seen, need, need_window), None

    def can_burst(self, batch_uids, k):
        """True when a ``decode_burst(uids, ·, k)`` (or a ``verify_burst``
        with ``k = d+1``) can reserve KV blocks for all ``k`` tokens per
        sequence right now — schedulers call this to fall back to
        stepwise decoding on a tight pool instead of catching exceptions
        (a failure inside the compiled burst happens after state
        mutation and donation, so it is NOT safely recoverable; only
        this pre-check is)."""
        _, err = self._validate_burst(batch_uids, int(k))
        if err is None and self._snapshotting:
            # a burst may end on a block boundary and may not pass one: the state at the
            # boundary is copied between programs, not inside one
            bs = self.block_size
            return all(int(k) <= bs - self.state_manager.query(uid).seen_tokens % bs
                       for uid in batch_uids)
        return err is None

    def _get_burst_fn(self, key, make):
        """LRU lookup in the compiled-program cache; ``make()`` builds on
        miss, and the least-recently-used program is dropped past the
        cap (its next use recompiles)."""
        fn = self._burst_fns.get(key)
        if fn is not None:
            self._burst_fns.move_to_end(key)
            return fn
        fn = make()
        self._burst_fns[key] = fn
        while len(self._burst_fns) > self._burst_fn_cap:
            self._burst_fns.popitem(last=False)
            self.burst_fn_evictions += 1
        return fn

    def _replicated_input(self, host):
        """A host metadata row as a program's argument: replicated over
        the serving mesh where there is one (the flat batch carries no
        sharding — only weights/KV do); on one device ``jit`` moves it
        with the call."""
        if self.mesh is None:
            return host
        return jax.device_put(host, self._replicated)

    def _dispatch_burst(self, rec, batch_uids, batch_tokens, k, sample, prev=None):
        """Pack and dispatch one ``k``-step burst: the one packer behind
        :meth:`decode_burst` and :meth:`decode_burst_async`, inside the
        step record ``rec`` its caller opened. Entry tokens come from the
        host (``batch_tokens``; ``prev=None``) or chain on the device
        from ``prev``'s last output row, and with them a sampled burst's
        DFA state row. → ``(descs, entry_np, out, st)``: ``entry_np`` the
        host entry row (None when chained), ``out`` the device
        ``[k, max_seqs]`` tokens, ``st`` the final DFA state row (None
        for a greedy burst), and, fifth, the program's device-side counts
        (:meth:`_note_counts`). Nothing is fetched here."""
        with tracing.phase("engine.pack"):
            if k < 1:
                raise ValueError("k must be >= 1")
            n, ms = len(batch_uids), self.max_seqs
            mode, specs = self._classify_sample(sample, n)
            if self.structured is not None and \
                    any(self.structured.bound(u) for u in batch_uids):
                mode = "packed"  # constrained rows need their DFA meta rows
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
            plan, err = self._validate_burst(batch_uids, k)
            if err is not None:
                raise err
            if prev is not None:
                entry_np, entry = None, prev.entry_next  # device row, no sync
            else:
                entry_np = np.zeros(ms, np.int32)
                entry_np[:n] = [int(np.asarray(tok).reshape(-1)[-1]) for tok in batch_tokens]  # ds-lint: disable=host-sync -- host entry tokens are ints the caller already fetched (put()'s outputs, the burst before), not device data
                entry = self._replicated_input(entry_np)

            lora_on = self.lora_store is not None
            descs, token_seq, pos0, tables, adapters, seq_state = self._pack_rows(rec, plan)
            for desc in descs:
                desc.advance(k)
            if self.window_pool is not None:
                self.state_manager.release_behind(descs)
            rec.n_ctx_tokens += int(_burst_ctx_tokens(pos0[:n], k).sum())
            parts = [token_seq, pos0, tables.ravel()]
            # the optional inputs of the program: keys present or absent, and
            # jit specialises on which. Slabs ride as ARGUMENTS (not captured
            # constants), so promotions / hot-swaps rebind, never retrace
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
                    opt["state"] = prev.st  # device chain — the host DFA mirror lags
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
            # Sampled bursts run ONE program regardless of the specs (they are
            # data), keyed "sampled" plus — when constrained decoding is live —
            # the DFA slab shape signature, and the LoRA rank-bucket signature
            # when serving adapters, so a reconfigured store can't replay a
            # stale program.
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
            if self._snapshotting:
                self._snapshot_landings(descs, rec)
        self.tokens_emitted += k * n
        return descs, entry_np, out, st, counts

    def decode_burst(self, batch_uids, batch_tokens, k, sample=None):
        """Run ``k`` decode steps for one current token per uid in ONE
        compiled program: on-device-sampled tokens feed the next step
        inside a ``lax.scan``, so the host syncs once per ``k`` generated
        tokens instead of every token (multi-step scheduling: one
        host↔device round trip and one pass of scheduler CPU per burst
        instead of per step). ``sample=None`` decodes
        greedily; a ``{"temperature", "top_k", "top_p", "seed"}`` dict —
        or a per-uid list of dict/None — draws with counter-PRNG keys
        ``(seed, absolute position)``, so burst size and scheduling
        order never change the emitted stream. Sequences with a bound
        schema gather their DFA logits mask in-scan. Returns int32
        tokens ``[k, len(uids)]``.

        KV blocks for all ``k`` tokens are reserved up front, so the
        block tables are static across the burst. This is the pipeline
        with nothing in flight: the burst :meth:`decode_burst_async`
        dispatches, fetched and logged before the call returns."""
        k, n = int(k), len(batch_uids)
        with tracing.step("burst", engine=self.trace_id, program=f"burst{k}", k=k, n_seqs=n,
                          n_tokens=k * n, n_rows=k * self.max_seqs, uids=tuple(batch_uids)) as rec:
            descs, entry_np, out, _, counts = self._dispatch_burst(
                rec, batch_uids, batch_tokens, k, sample)
            # the fetched form reads its entry row from the host every
            # burst (one site a row) and pays the fetch
            self.count_host_sync(n + 1)
            try:
                if self.while_running is not None:
                    self.while_running()
            finally:
                with tracing.phase("engine.fetch"):
                    toks, *counts = jax.device_get((out, *counts))  # ds-lint: disable=host-sync -- THE one intended sync per k-step burst
                    toks = toks[:, :n]
                    self._note_counts(rec, counts)
            with tracing.phase("engine.log"):
                if self._log_tokens:
                    # log what the burst actually WROTE to the KV cache: step i
                    # writes its input token's KV, so positions [seen, seen+k)
                    # hold the entry token followed by the first k-1 outputs (the
                    # final sampled token is never written — it would be the next
                    # step's input). EOS truncation is a scheduler concern; the
                    # cache is content-addressed, so post-EOS tokens just hash to
                    # prefixes nobody asks for.
                    for i, desc in enumerate(descs):
                        desc.tokens.fence()  # order after drained pipelined segments
                        desc.tokens.append(int(entry_np[i]))
                        desc.tokens.extend(int(t) for t in toks[:-1, i])
            self.last_step = rec
            return toks

    def decode_burst_async(self, batch_uids, batch_tokens, k, sample=None,
                           prev=None):
        """Pipelined ``decode_burst``: dispatches the k-step burst and
        returns an :class:`AsyncBurstHandle` WITHOUT any device→host
        copy — the caller fences one burst late, so the host packs and
        dispatches burst k+1 while burst k executes.

        ``prev=None`` is the pipeline cold start: entry tokens come from
        ``batch_tokens`` (host ints, e.g. ``put()``'s last outputs).
        With ``prev`` set, entry tokens chain ON DEVICE from the
        previous handle's last output row (``prev.entry_next``) and
        ``batch_tokens`` is ignored — the uid order must match ``prev``
        exactly (the scheduler drains the pipeline whenever the live set
        changes). Sampled chains also carry the DFA state row from
        ``prev.st``, so constrained streams stay bit-identical to the
        fetched form. Token-log segments are appended as pending DEVICE
        segments (:meth:`TokenLog.append_device`); prefix-cache retire,
        suspend and handoff export fence them lazily."""
        k, n = int(k), len(batch_uids)
        rec = tracing.begin("burst_async", engine=self.trace_id, program=f"burst{k}", k=k, n_seqs=n,
                            n_tokens=k * n, n_rows=k * self.max_seqs, uids=tuple(batch_uids))
        try:
            descs, entry_np, out, st, counts = self._dispatch_burst(
                rec, batch_uids, batch_tokens, k, sample, prev)
        finally:
            # open until AsyncBurstHandle.fetch: the device runs meanwhile
            tracing.suspend(rec)
        handle = AsyncBurstHandle(self, batch_uids, descs, k, out, st=st,
                                  entry_np=entry_np, prev=prev, record=rec, counts=counts)
        if self._log_tokens:
            # KV content over [seen, seen+k) = the entry token plus the
            # first k-1 outputs, exactly like the fetched form — but it
            # stays a pending DEVICE segment until something fences
            for i, desc in enumerate(descs):
                desc.tokens.append_device(
                    lambda i=i, h=handle:
                        [int(h.entry_values()[i])]
                        + [int(t) for t in h.fetch()[:-1, i]])
        return handle

    def _make_burst_fn(self, k, skey=None):
        """The one burst program family: ``burst(p, kc, vc, xc, meta,
        tokens0, opt)``. Entry tokens are a device argument (``int32[max_seqs]``),
        so a burst can start from the host's row or from the burst
        before without being another program. ``opt`` holds the optional
        inputs under the keys that are present — ``base`` (sampling base
        key) and ``state`` (DFA state row) for a sampled program, ``dfa``
        and ``lora`` (their slabs) when those subsystems are live — and
        ``jit`` specialises on which. → ``(out, st, kc, vc, *counts, xc)``:
        the final DFA state row for the next link, None from a greedy
        program; a model kind that counts on the device gives its counts
        fifth, summed over the burst's steps; the kind's further state last."""
        from deepspeed_tpu.inference.v2.model_runner import ragged_forward
        cfg, dtype, mesh = self.model_config, self.dtype, self.mesh
        attn_impl = self._attention
        quantized = self._quantized
        seq_rows = self._seq_rows
        ms, mb = self.max_seqs, self.max_blocks_per_seq
        lora_on = self.lora_store is not None
        sampled = skey == "sampled"

        def burst(p, kc, vc, xc, meta, tokens0, opt):
            if quantized:
                from deepspeed_tpu.inference.quantization import dequantize_tree_except
                p = dequantize_tree_except(p, dtype)  # once per burst, not per step
            lay = _burst_layout(ms, mb, lora=lora_on, sampled=sampled, seq_rows=seq_rows)
            token_seq = meta[slice(*lay["token_seq"])]
            pos0 = meta[slice(*lay["pos0"])]
            tables = meta[slice(*lay["tables"])].reshape(ms + 1, mb)
            last = jnp.arange(ms, dtype=jnp.int32)
            lora_arg = None
            if "lora" in opt:
                la, lb, scales = opt["lora"]
                seq_adapters = meta[slice(*lay["seq_adapters"])]
                lora_arg = (la, lb, scales, seq_adapters, None)

            if sampled:
                # the state row packed in sample_meta is put()'s; a burst's is
                # opt["state"], so that it can chain on the device
                temp, topk, topp, seed, slot, _ = unpack_sample_meta(
                    meta[slice(*lay["sample_meta"])], ms)
                base, slabs = opt["base"], opt.get("dfa")

            seq_state = {}
            if seq_rows:
                seq_state["seq_state"] = meta[slice(*lay["seq_state"])].reshape(ms + 1, seq_rows)

            def one(carry, i):
                kc, vc, xc, toks, st = carry  # st is None in a greedy burst
                # one row a sequence by construction: no row shares a walk of its
                # context with a neighbour, and the kernel lowers a row a grid step
                b = {"token_ids": toks, "token_seq": token_seq,
                     "token_pos": pos0 + i, "block_tables": tables,
                     "last_index": last, "query_tiles": None, **seq_state}
                sel, kc, vc, *counts, xc = ragged_forward(p, kc, vc, b, cfg, dtype, mesh=mesh,
                                                          attn_impl=attn_impl, lora=lora_arg,
                                                          extra=xc)
                if not sampled:
                    nxt = jnp.argmax(sel, axis=-1).astype(jnp.int32)
                    return (kc, vc, xc, nxt, st), (nxt, *counts)
                if slabs is not None:
                    sel = apply_dfa_mask(sel, slabs[0], slot, st)
                # step i's token lands at absolute position pos0 + i + 1,
                # so its counter key matches the stepwise path exactly
                keys = token_keys(base, seed, pos0 + i + 1)
                nxt = sample_rows(sel, keys, temp, topk, topp)
                if slabs is not None:
                    st = slabs[1][slot, st, nxt]  # in-scan DFA advance
                return (kc, vc, xc, nxt, st), (nxt, *counts)

            (kc, vc, xc, _, st), (out, *counts) = jax.lax.scan(
                one, (kc, vc, xc, tokens0, opt.get("state")), jnp.arange(k, dtype=jnp.int32))
            return (out, st, kc, vc, *(c.sum(axis=0) for c in counts), xc)

        return maybe_checkify_jit(burst, donate_argnums=(1, 2, 3),
                                  enabled=self._sanitize)

    # -------------------------------------------- speculative decoding
    def propose_drafts(self, batch_uids, batch_tokens, max_lens=None):
        """Host-side n-gram (prompt-lookup) drafting against each
        sequence's KV-content token log plus its pending entry token.
        → one (possibly empty) list of draft ids per uid; empty when
        spec decoding is off, the per-sequence accept EMA disabled
        drafting for that uid, ``max_lens[i]`` caps it to 0, or the log
        holds no recurring suffix n-gram."""
        if self.spec is None:
            return [[] for _ in batch_uids]
        out = []
        for i, (uid, tok) in enumerate(zip(batch_uids, batch_tokens)):
            desc = self.state_manager.query(uid)
            cap = self.spec.draft_len(uid)
            if max_lens is not None:
                cap = min(cap, int(max_lens[i]))
            if desc is None or cap < 1:
                out.append([])
                continue
            self.count_host_sync()
            entry = int(np.asarray(tok).reshape(-1)[-1])  # ds-lint: disable=host-sync -- entry tokens come from the previous step's host copy
            # the drafter reads the WHOLE content log — any pending
            # device segments must land first (no-op when fenced)
            desc.tokens.fence()
            out.append(self.spec.drafter.propose(desc.tokens + [entry], cap))
        return out

    def verify_burst(self, batch_uids, batch_tokens, batch_drafts, sample=None):
        """Score each sequence's entry token plus its draft tokens in
        ONE ragged forward — the drafts enter as a (d+1)-token ragged
        chunk through the same packed-prefill path ``put`` uses — and
        accept the longest draft prefix matching the model's own
        choices, followed by the model's next token at the first
        mismatch.

        Greedy (``sample=None``): the model's choice is the argmax, so
        the emitted stream is bit-identical to stepwise greedy decoding
        by construction. Sampled (a spec dict or per-uid list):
        rejection-sampled speculative verification — position ``j``'s
        choice is drawn from the (temperature/top-k/top-p-filtered)
        target distribution with the SAME counter key ``(seed, pos0 +
        j + 1)`` stepwise decode would use there, and a draft survives
        iff it equals that draw. Because the n-gram drafter proposes
        point-mass drafts, accept-iff-equal IS the standard
        rejection-sampling correction (the residual distribution equals
        the target draw), and the emitted stream stays bit-identical to
        the spec-off sampled stream per seed.

        → ``(tokens [n, d+1] int32, accepted [n] int64)``: row ``i``
        emits ``tokens[i, :accepted[i] + 1]``. KV blocks are reserved
        for the full ``d+1`` tokens up front (static tables inside the
        program), but ``seen_tokens``/token-log advance only by the
        accepted count — the rejected tail is abandoned in place (the
        block tables make it unreachable; the next tokens overwrite it)
        and trailing whole blocks return to the pool."""
        with tracing.step("verify", engine=self.trace_id, uids=tuple(batch_uids)) as rec:
            with tracing.phase("engine.pack"):
                if self.spec is None:
                    raise RuntimeError("speculative decoding is disabled "
                                       "(config.spec_decode / DS_SPEC_DECODE)")
                mode, specs = self._classify_sample(sample, len(batch_uids))
                if mode == "logits":
                    mode = "greedy"  # verify has no raw-logits mode
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
                plan, err = self._validate_burst(batch_uids, d + 1)
                if err is not None:
                    raise err
                rec.program, rec.n_seqs = f"verify{d}", len(batch_uids)
                rec.n_tokens = len(batch_uids) * (d + 1)
                rec.n_rows = self.max_seqs * (d + 1)
                ms, mb = self.max_seqs, self.max_blocks_per_seq
                lora_on = self.lora_store is not None
                # KV blocks for all d+1 tokens now (static tables inside the program);
                # seen_tokens advances by what is accepted, once that is known
                descs, token_seq, pos0, tables, adapters, _ = self._pack_rows(rec, plan)
                toks = np.zeros((ms, d + 1), np.int32)
                dlen = np.zeros(ms, np.int32)
                entries = []
                for i, (tok, drafts) in enumerate(zip(batch_tokens, batch_drafts)):
                    self.count_host_sync()
                    entry = int(np.asarray(tok).reshape(-1)[-1])  # ds-lint: disable=host-sync -- entry tokens come from the previous step's host copy
                    entries.append(entry)
                    row = [entry] + [int(t) for t in drafts]
                    toks[i, :len(row)] = row
                    toks[i, len(row):] = entry  # inert pad: dlen masks acceptance
                    dlen[i] = len(drafts)
                rec.n_ctx_tokens += int(pos0.sum()) + len(descs) * (d + 1)
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
                # the verify must see the SAME adapter deltas decode does, or
                # acceptance silently diverges from stepwise decoding
                key = ("verify", d) if not sampled else ("verify", d, "sampled")
                packed = self.async_burst_depth > 0
                if packed:
                    # one-fetch-per-burst: the program concatenates tokens and
                    # accept counts into ONE int32 vector, so the host pays a
                    # single device→host copy instead of two. A distinct key.
                    key = key + ("packed",)
                if lora_on:
                    key = key + (self.lora_store.signature(),)
                fn = self._get_burst_fn(
                    key, lambda: self._make_verify_fn(d, sampled, packed=packed))
                extra = (self.lora_store.slabs(),) if lora_on else ()
                sargs = (self._base_key,) if sampled else ()
            with tracing.phase("engine.dispatch"):
                # result: packed, one vector (tokens, then accept counts); else both
                *result, self.kv_cache.k, self.kv_cache.v = fn(
                    self.params, self.kv_cache.k, self.kv_cache.v, meta, *sargs, *extra)
            self.count_host_sync(1 if packed else 2)
            try:
                if self.while_running is not None:
                    self.while_running()
            finally:
                with tracing.phase("engine.fetch"):
                    if packed:
                        wire = np.asarray(result[0])  # ds-lint: disable=host-sync -- THE one intended sync per verify burst (packed tokens + accept counts)
                        out = wire[:ms * (d + 1)].reshape(ms, d + 1)
                        acc = wire[ms * (d + 1):].astype(np.int64)
                    else:
                        out = np.asarray(result[0])  # ds-lint: disable=host-sync -- THE one intended sync per verify burst
                        acc = np.asarray(result[1])  # ds-lint: disable=host-sync -- host copy of the device result above, already synced
            n = len(batch_uids)
            with tracing.phase("engine.log"):
                written = self.state_manager.rows_written
                for i, desc in enumerate(descs):
                    a = int(acc[i])
                    self.tokens_emitted += a + 1
                    # KV positions [seen, seen+a] hold the entry token and the a
                    # accepted drafts; the bonus token out[i, a] is the NEXT
                    # step's entry and was never written (same convention as the
                    # plain burst). Advance by accepted only, then return whole
                    # unused trailing blocks.
                    desc.advance(a + 1)
                    if self._log_tokens:
                        desc.tokens.fence()  # order after drained pipelined segments
                        desc.tokens.append(entries[i])
                        desc.tokens.extend(int(t) for t in out[i, :a])
                    self.state_manager.release_unused_blocks(desc)
                    if int(dlen[i]):
                        self.spec.note(desc.uid, accepted=a, drafted=int(dlen[i]))
                # the rows whose rejected drafts gave blocks back, beside the pack's
                rec.n_table_rows_written += self.state_manager.rows_written - written
            self.last_step = rec
            return out[:n], acc[:n]

    def _make_verify_fn(self, d, sampled=False, packed=False):
        """One compiled verify program for draft length ``d``: a single
        ragged forward over ``max_seqs * (d+1)`` packed tokens
        (``last_index = arange`` selects EVERY token's logits, so no
        model-runner change is needed), per-position argmax — or, for
        the ``sampled`` variant, a per-position counter-keyed draw from
        the spec-filtered target — and on-device
        longest-matching-prefix acceptance."""
        from deepspeed_tpu.inference.v2.model_runner import ragged_forward
        cfg, dtype, mesh = self.model_config, self.dtype, self.mesh
        attn_impl = self._attention
        quantized = self._quantized
        ms, mb = self.max_seqs, self.max_blocks_per_seq
        lora_on = self.lora_store is not None

        def verify(p, kc, vc, meta, base=None, lora_slabs=None):
            if quantized:
                from deepspeed_tpu.inference.quantization import dequantize_tree_except
                p = dequantize_tree_except(p, dtype)
            lay = _verify_layout(ms, mb, d, lora=lora_on, sampled=sampled)
            toks = meta[slice(*lay["tokens"])].reshape(ms, d + 1)
            dlen = meta[slice(*lay["dlen"])]
            token_seq = meta[slice(*lay["token_seq"])]
            pos0 = meta[slice(*lay["pos0"])]
            tables = meta[slice(*lay["tables"])].reshape(ms + 1, mb)
            lora_arg = None
            if lora_slabs is not None:
                la, lb, scales = lora_slabs
                seq_adapters = meta[slice(*lay["seq_adapters"])]
                lora_arg = (la, lb, scales, seq_adapters, None)
            T = ms * (d + 1)
            steps = jnp.arange(d + 1, dtype=jnp.int32)
            # each sequence enters as one (d+1)-token chunk at positions
            # pos0..pos0+d — exactly a packed prefill chunk; the paged
            # attention scatters the chunk's KV first and masks by
            # position, so within-chunk causality holds as it does for
            # split prefills
            b = {"token_ids": toks.reshape(-1),
                 "token_seq": jnp.repeat(token_seq, d + 1),
                 "token_pos": (pos0[:, None] + steps[None, :]).reshape(-1),
                 "block_tables": tables,
                 "last_index": jnp.arange(T, dtype=jnp.int32)}
            logits, kc, vc, _ = ragged_forward(p, kc, vc, b, cfg, dtype, mesh=mesh,
                                               attn_impl=attn_impl, lora=lora_arg)
            if not sampled:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                # rejection-sampled verify: position j of row i draws
                # from its spec-filtered target with counter key
                # (seed[i], pos0[i] + j + 1) — exactly the key stepwise
                # decode uses for that position, so the accepted stream
                # is bit-identical to the spec-off stream per seed
                temp, topk, topp, seed, _slot, _state = unpack_sample_meta(
                    meta[slice(*lay["sample_meta"])], ms)
                rep = lambda x: jnp.repeat(x, d + 1)
                pos = (pos0[:, None] + steps[None, :] + 1).reshape(-1)
                keys = token_keys(base, rep(seed), pos)
                nxt = sample_rows(logits, keys, rep(temp), rep(topk), rep(topp))
            nxt = nxt.reshape(ms, d + 1)
            # acceptance: draft j survives iff every earlier draft did
            # AND it equals the model's own next token there — sum of
            # the running cumprod counts the matching prefix. For the
            # sampled verify this accept-iff-equal IS the rejection-
            # sampling correction: the drafter is a point mass, so the
            # residual distribution at a mismatch is the target draw.
            match = (toks[:, 1:] == nxt[:, :-1]) & (steps[None, :d] < dlen[:, None])
            acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            if packed:
                # one device→host copy per verify burst: tokens and
                # accept counts leave as a single int32 wire vector
                return jnp.concatenate(
                    [nxt.reshape(-1), acc.astype(jnp.int32)]), kc, vc
            return nxt, acc, kc, vc

        if not sampled and lora_on:
            fn = lambda p, kc, vc, meta, lslabs: \
                verify(p, kc, vc, meta, None, lslabs)
        elif not sampled:
            fn = lambda p, kc, vc, meta: verify(p, kc, vc, meta)
        elif lora_on:
            fn = verify
        else:
            fn = lambda p, kc, vc, meta, base: verify(p, kc, vc, meta, base)
        return maybe_checkify_jit(fn, donate_argnums=(1, 2),
                                  enabled=self._sanitize)

    def rewind(self, uid, n_tokens):
        """Roll ``uid`` back by ``n_tokens`` of KV content: the token
        log truncates to match, positions past the new length become
        unreachable, and now-unused trailing blocks return to the pool.
        Schedulers use this when EOS lands mid-burst — the burst
        reserved and advanced past the end of generation, and without a
        rewind the garbage tail would stay charged (and, with a prefix
        cache, be content-addressed into the trie). → new seen_tokens."""
        desc = self.state_manager.query(uid)
        if desc is None:
            raise KeyError(f"unknown sequence {uid}")
        self.state_manager.rewind_sequence(desc, int(n_tokens))
        return desc.seen_tokens

    def _reclaimable_blocks(self):
        """Blocks an allocation can actually obtain right now: the free
        list plus unreferenced cached blocks the prefix cache will evict
        under pressure. This is the number every pool-exhaustion check
        compares against — cached-but-evictable blocks must never cause
        a spurious reject."""
        free = self.kv_cache.free_blocks
        if self.prefix_cache is not None:
            free += self.prefix_cache.evictable_blocks
        return free

    @property
    def evictable_blocks(self):
        """Unreferenced prefix-cache blocks (0 without a cache) — serving
        admission counts these as reclaimable capacity."""
        return self.prefix_cache.evictable_blocks if self.prefix_cache is not None else 0

    def prefix_match(self, uid, prompt_tokens, breakpoints=()):
        """Start tracking ``uid`` with its longest cached prompt prefix
        pre-populated (no-op returning 0 when the prefix cache is off or
        the sequence already exists). → the number of leading prompt
        tokens whose KV is already in the pool; the caller starts
        prefill at that offset. Always capped one token short of the
        prompt, so the last prompt token is recomputed and first-token
        logits exist. This is also where a scheduler tells the engine
        the whole prompt before its first chunk, so a model kind that
        keeps state a sequence beyond its blocks (``kind.seq_state``: a
        slot, and what of the prompt's length decides how its rows
        attend) starts tracking ``uid`` here, with that state; ``put``
        refuses such a model a sequence it was not told of. With the prefix
        cache on, such a sequence starts behind the deepest **snapshot** on
        its prompt's cached path, that state copied into its own slot
        (``prefix_cache/manager.py``), or at 0. ``breakpoints``: token
        offsets of the prompt where a prefix shared with other requests ends
        (a system prompt's length): a snapshot is taken at the last block
        boundary at or before each (:meth:`chunk_cut`, :meth:`_snapshot_landings`)."""
        if self.slot_pool is not None:
            sm = self.state_manager
            desc = sm.query(uid)
            if desc is None:
                prompt = None if self.prefix_cache is None else \
                    [int(t) for t in np.atleast_1d(np.asarray(prompt_tokens))]
                desc = sm.get_or_create_sequence(uid, prompt_tokens=prompt)
            if desc.state_row is None:
                # (read before the sequence's own slot is taken: if taking it evicts this
                # very snapshot, the slot handed over is the one that holds the state)
                held = self.prefix_cache.snapshot_of(uid) if desc.cached_tokens else None
                slot = self.slot_pool.acquire()
                sm.set_state_row(desc, self.kind.seq_state(self.model_config, slot,
                                                           len(prompt_tokens)))
                if held is not None:
                    self._copy_slots([(held, slot)])
                    self._snapshots_restored += 1
                bs = self.block_size
                desc.snapshot_marks = sorted({int(b) // bs * bs for b in breakpoints
                                              if desc.cached_tokens < int(b) // bs * bs
                                              < len(prompt_tokens)})
            return desc.cached_tokens
        if self.prefix_cache is None:
            return 0
        desc = self.state_manager.query(uid)
        if desc is not None:
            return desc.cached_tokens
        prompt = [int(t) for t in np.atleast_1d(np.asarray(prompt_tokens))]
        desc = self.state_manager.get_or_create_sequence(uid, prompt_tokens=prompt)
        return desc.cached_tokens

    def chunk_cut(self, uid, cursor, take):
        """→ ``take``, or fewer rows where a prompt chunk of ``take`` rows from
        ``cursor`` would pass one of ``uid``'s breakpoints without ending on
        it: a scheduler asks before it cuts a chunk, so that the state can be
        copied where the shared prefix ends."""
        desc = self.state_manager.query(uid) if self.prefix_cache is not None else None
        for mark in desc.snapshot_marks if desc is not None else ():
            if cursor < mark < cursor + take:
                return mark - cursor
        return take

    def _copy_slots(self, pairs):
        """``pairs`` [(from, to)]: slot ``from``'s row of every entry of the
        kind's ``slot_state`` over slot ``to``'s, in every layer, on the
        device: one program, dispatched behind whatever wrote ``from`` (the
        arrays are donated through every program, so the device runs them in
        order), never a trip through the host."""
        if self._copy_slots_fn is None:
            names, scope = self.kind.slot_state, self.kind.snapshot_scope

            def snapshot_copy_slots(extra, src, dst, n):
                def one(i, extra):
                    with jax.named_scope(scope):
                        return {name: jax.lax.dynamic_update_slice_in_dim(
                            x, jax.lax.dynamic_slice_in_dim(x, src[i], 1, axis=1), dst[i], axis=1)
                            if name in names else x for name, x in extra.items()}
                return jax.lax.fori_loop(0, n, one, extra)

            # (the program's name is what a device trace knows it by: benchmark/readers/granite.py)
            self._copy_slots_fn = jax.jit(snapshot_copy_slots, donate_argnums=0)
        room = self.max_seqs            # at the most a copy a sequence of a step
        src, dst = (np.zeros(room, np.int32) for _ in range(2))
        src[:len(pairs)], dst[:len(pairs)] = zip(*pairs)
        self.state_extra = self._copy_slots_fn(self.state_extra, src, dst, np.int32(len(pairs)))

    def _snapshot_landings(self, descs, rec):
        """After a step's program is dispatched: each of its sequences whose
        length now lands on a block boundary has its slot copied into one of
        the cache's - a ``breakpoint`` where its request named the place, else
        ``trailing`` (the newer replaces the older). The rule, whole: **a
        snapshot is taken wherever a step leaves a sequence on a block
        boundary**; a scheduler cuts prompt chunks so that the boundaries a
        request named are such places (:meth:`chunk_cut`), and bursts so that
        none is passed over (:meth:`can_burst`)."""
        pairs, bs = [], self.block_size
        for desc in descs:
            seen = desc.seen_tokens
            if seen % bs or not seen:
                continue
            kind = "breakpoint" if seen in desc.snapshot_marks else "trailing"
            slot = self.prefix_cache.snapshot_slot(desc.uid, seen, kind)
            if slot is not None:
                pairs.append((desc.state_row[0], slot))
        if pairs:
            self._copy_slots(pairs)
        # for the step's record, once its device counts are in (_note_counts)
        self._snapshot_counts[rec.seq] = (len(pairs), self._snapshots_restored)
        self._snapshots_restored = 0

    def prefetch_prefix(self, prompt_tokens):
        """Fire-and-forget: stage this prompt's tier-2 KV extension on
        the spill tier's prefetch worker so the host→device copy
        overlaps queueing (no-op without a tier). Safe from any thread
        — staging never touches the donated pool; the restore happens
        on the pump thread at ``acquire`` time behind the fence."""
        if self.kv_tier is not None:
            self.kv_tier.prefetch([int(t) for t in
                                   np.atleast_1d(np.asarray(prompt_tokens))])

    def export_prefix(self, prompt_tokens, max_blocks=None):
        """Serialize this prompt's cached KV chain into a process-
        portable handoff record (disaggregated prefill→decode serving).
        PUMP-THREAD ONLY — the export gathers from the donated pool.
        None when no spill tier is attached or nothing is cached."""
        if self.kv_tier is None:
            return None
        prompt = [int(t) for t in np.atleast_1d(np.asarray(prompt_tokens))]
        return self.kv_tier.export_chain(prompt, max_blocks=max_blocks)

    def import_prefix(self, record):
        """Adopt a peer replica's exported KV chain into the local spill
        tier (validated; raises KVTierCorruptionError on a forged/torn
        record). Safe from any thread. → blocks adopted (0 tierless)."""
        if self.kv_tier is None or record is None:
            return 0
        return self.kv_tier.import_chain(record)

    # ------------------------------------------------- multi-tenant LoRA
    def bind_adapter(self, uid, adapter_id):
        """Pin ``uid``'s tokens to ``adapter_id``'s hot slot for the
        sequence's lifetime (promoting the adapter from the host tier or
        its publication dir if cold — may evict an unleased LRU hot
        adapter). ``adapter_id`` falsy → base model, slot 0. The lease
        holds the slot until :meth:`flush`; → the bound slot index."""
        if not adapter_id:
            return 0
        if self.lora_store is None:
            raise RuntimeError(
                "adapter routing requires LoRA serving "
                "(config.lora.enabled / DS_LORA)")
        slot = self.lora_store.bind(uid, int(adapter_id))
        desc = self.state_manager.query(uid)
        if desc is not None:
            desc.adapter_slot = slot
        return slot

    def has_adapter(self, adapter_id):
        """True when ``adapter_id`` is HOT (HBM-resident) — placement
        probes use this for adapter-affine routing."""
        return (self.lora_store is not None
                and self.lora_store.has_adapter(int(adapter_id)))

    def knows_adapter(self, adapter_id):
        """True when any tier (hot, host, publication dir) can serve
        ``adapter_id`` — gateway admission rejects unknown ids up front."""
        return (self.lora_store is not None
                and self.lora_store.known(int(adapter_id)))

    def prefetch_adapter(self, adapter_id):
        """Fire-and-forget: stage ``adapter_id``'s padded slabs on the
        store's prefetch worker so a later bind's device copy overlaps
        queueing (no-op without a store). Safe from any thread."""
        if self.lora_store is not None:
            self.lora_store.prefetch(int(adapter_id))

    def register_adapter(self, adapter_id, layers, alpha, version=0):
        """Install adapter weights into the host tier directly (tests /
        colocated trainers); the first bind promotes them to HBM."""
        if self.lora_store is None:
            raise RuntimeError("LoRA serving is disabled")
        self.lora_store.register(int(adapter_id), layers, alpha,
                                 version=version)

    def adopt_adapter(self, adapter_id, version=None):
        """Adopt a published adapter version (sha256-validated commit
        protocol; raises WeightPublicationError with nothing adopted on
        a forged/torn publication). Hot copies hot-swap in place."""
        if self.lora_store is None:
            raise RuntimeError("LoRA serving is disabled")
        return self.lora_store.adopt(int(adapter_id), version=version)

    def prefix_match_len(self, prompt_tokens):
        """Read-only twin of :meth:`prefix_match` for placement probes:
        → leading tokens of ``prompt_tokens`` whose KV is cached, WITHOUT
        creating a sequence, taking a lease, or touching hit-rate stats.
        0 when the prefix cache is off."""
        if self.prefix_cache is None:
            return 0
        prompt = [int(t) for t in np.atleast_1d(np.asarray(prompt_tokens))]
        return self.prefix_cache.match_len(prompt)

    def query(self, uid):
        """→ (seen_tokens, max_new_before_realloc) parity surface."""
        desc = self.state_manager.query(uid)
        if desc is None:
            return None
        room = desc.cur_allocated_blocks * self.block_size - desc.seen_tokens
        return desc.seen_tokens, room

    def flush(self, uid):
        """Discard everything the engine holds for ``uid`` — live KV
        blocks AND any suspended host copy (without this, a suspended
        sequence whose client went away could never be retired: resume
        needs pool room, which is exactly what the suspend relieved)."""
        suspended = self._suspended.pop(uid, None) is not None
        desc = self.state_manager.query(uid)
        if desc is not None:
            # prefix-cache retire content-addresses blocks by the token
            # log — materialize any pending device segments first
            desc.tokens.fence()
            self.state_manager.flush_sequence(uid)
            if desc.state_row is not None:
                self.slot_pool.release(desc.state_row[0])  # the slot goes with the blocks
        elif not suspended:
            raise KeyError(f"unknown sequence {uid}")
        if self.spec is not None:
            self.spec.forget(uid)
        if self.lora_store is not None:
            self.lora_store.release(uid)  # drop the adapter-slot lease
        if self.structured is not None:
            self.structured.release(uid)  # drop the schema lease + DFA state

    def suspend(self, uid):
        """Swap a live sequence's KV blocks to host memory and release
        them for other sequences (the surface the reference's
        BlockedKVCache declares but leaves NotImplementedError,
        kv_cache.py:166 — vLLM-style swapping). The sequence stops being
        tracked until :meth:`resume`."""
        if self.state_kind != "kv":
            raise NotImplementedError(
                f"suspend/resume export does not support the {self.state_kind!r} state "
                f"of model kind {self.kind.name!r}")
        desc = self.state_manager.query(uid)
        if desc is None:
            raise KeyError(f"unknown sequence {uid}")
        if uid in self._suspended:
            raise ValueError(f"sequence {uid} is already suspended")
        # the host copy must carry the WHOLE token log — materialize any
        # pending device segments before snapshotting it
        desc.tokens.fence()
        # the blocks are the handle's from here (freed by offload / kept by
        # the trie): off the descriptor and its row, never double-freed
        blocks = self.state_manager.trim_blocks(desc, 0)
        # Shared prefix blocks belong to the radix trie and other live
        # sequences may be attending over them RIGHT NOW: copy their KV
        # into the handle but leave the blocks cached (decref only). The
        # resumed sequence gets private copies — correct, at the price of
        # re-duplicating a prefix that may still be cache-resident.
        handle = self.kv_cache.offload(blocks, keep=blocks[:desc.shared_blocks])
        if self.prefix_cache is not None:
            self.prefix_cache.release_lease(uid)
        self._suspended[uid] = {"handle": handle, "seen_tokens": desc.seen_tokens,
                                "tokens": list(desc.tokens)}
        desc.shared_blocks = 0
        self.state_manager.drop_sequence(uid)

    def is_suspended(self, uid):
        """True when ``uid``'s KV lives in a suspended host copy."""
        return uid in self._suspended

    def suspended_blocks(self, uid):
        """Pool blocks a :meth:`resume` of ``uid`` would need — serving
        admission checks this against ``free_blocks`` before resuming."""
        ent = self._suspended.get(uid)
        if ent is None:
            raise KeyError(f"sequence {uid} is not suspended")
        return int(ent["handle"]["k"].shape[1])

    def resume(self, uid):
        """Restore a suspended sequence's KV into freshly reserved blocks
        (ids may differ; the descriptor re-points at them) and resume
        tracking — decode continues exactly where it stopped."""
        ent = self._suspended.get(uid)
        if ent is None:
            raise KeyError(f"sequence {uid} is not suspended")
        # validate EVERYTHING before restore() mutates the pool — a
        # failure after the scatter would leak the reserved blocks and
        # lose the host handle
        if self.state_manager.query(uid) is not None:
            raise ValueError(f"sequence {uid} was re-registered live while "
                             f"suspended; flush() it before resume()")
        n = ent["handle"]["k"].shape[1]
        if n > self._reclaimable_blocks():
            raise RuntimeError(f"KV pool exhausted: resume needs {n} blocks, "
                               f"{self._reclaimable_blocks()} reclaimable")
        if self.state_manager.n_tracked_sequences >= \
                self.state_manager.max_tracked_sequences:
            raise RuntimeError("max_tracked_sequences exceeded; flush() a live "
                               "sequence before resume()")
        if self.prefix_cache is not None:
            self.prefix_cache.ensure_free(n)
        blocks = self.kv_cache.restore(ent["handle"])
        del self._suspended[uid]
        desc = self.state_manager.get_or_create_sequence(uid)
        self.state_manager.extend_blocks(desc, blocks)
        desc.seen_tokens = ent["seen_tokens"]
        # every restored block is private (shared_blocks stays 0); the
        # token log survives suspension so retire can still cache them
        desc.tokens = list(ent.get("tokens", ()))
        return desc.seen_tokens

    def destroy(self):
        """Release engine HBM (params, KV pool) and jit caches — v1
        engine.destroy parity for back-to-back engine builds."""
        self.params = None
        self.kv_cache = None
        self.state_extra = self.slot_pool = None
        self.state_manager = None
        self.prefix_cache = None
        if self.kv_tier is not None:
            self.kv_tier.shutdown()  # stop the prefetch worker + drop host KV
        self.kv_tier = None
        if self.lora_store is not None:
            self.lora_store.shutdown()  # stop the adapter prefetch worker
        self.lora_store = None
        self.spec = None
        self.structured = None
        self._step = self._step_greedy = self._step_sampled = None
        self._burst_fns = OrderedDict()
        self._suspended = {}

    @property
    def free_blocks(self):
        return self.kv_cache.free_blocks

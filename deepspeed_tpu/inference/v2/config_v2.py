"""v2 inference engine config.

Capability match for the reference's
``deepspeed/inference/v2/config_v2.py`` (``RaggedInferenceEngineConfig``
with its ``DSStateManagerConfig``)."""

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class DSStateManagerConfig(DeepSpeedConfigModel):
    max_tracked_sequences: int = 2048
    max_ragged_batch_size: int = 768
    max_ragged_sequence_count: int = 512
    max_context: int = 8192


class QuantizationConfig(DeepSpeedConfigModel):
    quantization_mode: str = "none"


class PrefixCacheConfig(DeepSpeedConfigModel):
    """Radix prefix cache (cross-request KV reuse). ``enabled`` is the
    config gate; the ``DS_PREFIX_CACHE`` env var overrides it in both
    directions (kill switch). ``max_cached_blocks`` caps how many pool
    blocks the trie may own at once (0 = bounded only by pool pressure —
    unreferenced cached blocks are evicted LRU when allocation needs
    them). ``snapshot_slots``: for a model kind whose sequences hold a slot
    of state beside their blocks and lets it be snapshotted
    (``ModelKind.snapshots``), the slots of the slot pool the cache owns,
    beyond ``max_tracked_sequences`` - each a copy of a sequence's state at
    a block boundary (0 = as many as tracked sequences: a trailing snapshot
    each); evicted LRU, and a live sequence may always take one."""
    enabled: bool = False
    max_cached_blocks: int = 0
    snapshot_slots: int = 0


class KVTierConfig(DeepSpeedConfigModel):
    """Host-RAM spill tier for the radix prefix cache (requires
    ``prefix_cache.enabled``): trie eviction demotes full immutable KV
    blocks into a byte-budgeted host store instead of dropping them, and
    prompts whose cached prefix continues into demoted chains restore
    them back. ``enabled`` is the config gate; the ``DS_KV_TIER`` env
    var overrides it in both directions (kill switch).  ``host_bytes``
    is the tier-2 budget (``DS_KV_TIER_BYTES`` overrides when > 0).
    ``quantize`` stores tier-2 blocks as per-(layer, block)-grouped int8
    instead of the pool dtype — ~2x more blocks per byte, lossy,
    strictly opt-in (``DS_KV_TIER_QUANT`` overrides in both
    directions); ``quant_group_size`` subdivides the per-block group
    (0 = one scale per (layer, block) slab). ``prefetch`` stages
    host→device copies on a background worker at admission so the copy
    overlaps queueing (the restore itself always happens on the pump
    thread behind a completion fence)."""
    enabled: bool = False
    host_bytes: int = 1 << 30
    quantize: bool = False
    quant_group_size: int = 0
    prefetch: bool = True


class SpecDecodeConfig(DeepSpeedConfigModel):
    """Self-speculative decoding (n-gram prompt-lookup drafting + a
    batched greedy verify forward). ``enabled`` is the config gate; the
    ``DS_SPEC_DECODE`` env var overrides it in both directions (kill
    switch) and ``DS_SPEC_DRAFT_LEN`` overrides ``draft_len``. Works
    under both greedy decoding (acceptance = exact match against the
    argmax) and per-sequence stochastic sampling (rejection-sampled
    verification: acceptance = exact match against a counter-keyed draw
    from the filtered target, which for point-mass n-gram drafts is the
    standard rejection scheme — the emitted stream is bit-identical to
    the spec-off stream per seed). Schema-constrained sequences still
    fall back to plain bursts (drafts are proposed without the DFA
    mask)."""
    enabled: bool = False
    draft_len: int = 4       # max draft tokens proposed per verify step
    max_ngram: int = 3       # longest suffix n-gram the drafter looks up
    min_ngram: int = 1       # shortest n-gram worth matching
    ema_alpha: float = 0.4   # per-sequence accept-rate EMA smoothing
    disable_below: float = 0.25  # EMA under this stops drafting for the seq
    warmup_steps: int = 3    # verify steps before the EMA may disable


class LoRAServingConfig(DeepSpeedConfigModel):
    """Multi-tenant LoRA serving (segmented adapter matmul + paged
    AdapterStore). ``enabled`` is the config gate; the ``DS_LORA`` env
    var overrides it in both directions (kill switch), and the off
    state builds the exact pre-LoRA pipeline — no slot arrays packed,
    program keys unchanged. ``hot_set`` counts HBM-resident adapter
    slots (``DS_LORA_HOT_SET`` overrides when > 0); ``max_rank`` is
    the rank bucket every hot slab pads to (``DS_LORA_MAX_RANK``
    overrides when > 0; adapters above it are rejected at
    registration). ``host_bytes`` budgets the cold host tier;
    ``prefetch`` stages host→device adapter copies on a background
    worker at admission. ``publish_root`` roots sha256-validated
    adapter publications (rollout/rollback like base weights); None
    disables the disk tier."""
    enabled: bool = False
    hot_set: int = 8
    max_rank: int = 16
    host_bytes: int = 1 << 30
    prefetch: bool = True
    publish_root: str = ""


class StructuredConfig(DeepSpeedConfigModel):
    """Constrained (grammar/JSON-schema) decoding: bound schemas lower
    to token-level DFAs whose masks compose into the on-device sampling
    step. ``enabled`` is the config gate; the ``DS_CONSTRAINED`` env
    var overrides it in both directions (kill switch), and the off
    state builds the exact pre-structured pipeline — no DFA metadata
    packed, program keys unchanged. ``max_schemas`` bounds
    concurrently-installed schemas (the device slabs are
    ``[max_schemas + 1, max_states, vocab]``; slot 0 is the reserved
    all-allow DFA); ``max_states`` bounds any one schema's token DFA —
    both are program-shape parameters, so changing them retraces."""
    enabled: bool = False
    max_schemas: int = 4
    max_states: int = 64


class AsyncBurstConfig(DeepSpeedConfigModel):
    """Pipelined decode bursts. ``depth`` is the number of in-flight
    (dispatched, unfetched) bursts the scheduler keeps. 0, the default,
    fetches every burst in the call that dispatched it. Above 0 the host
    plans, packs and dispatches burst k+1 while burst k executes on
    device and consumes burst k's tokens only when it fences — EOS,
    finished state and the token log are discovered late, never by
    blocking the device; 2 is the classic double buffer (fence burst k
    before dispatching burst k+2). One burst program family serves every
    depth, and the emitted streams are bit-identical at every depth."""
    depth: int = 0


class RaggedInferenceEngineConfig(DeepSpeedConfigModel):
    tensor_parallel_degree: int = 1
    expert_parallel_degree: int = 1  # MoE expert sharding for serving
    # pin a registry implementation by op, e.g. {"attention": "xla_gather"}
    # (reference inference/v2/modules/heuristics.py config-driven selection)
    implementation_overrides: dict = {}
    kv_block_size: int = 16
    num_kv_blocks: int = 0  # 0 = derive from max_context * max sequences
    # a model kind with window layers keeps their keys and values in a pool of its own
    # (ragged/kv_cache.WindowPool): its blocks; 0 = every tracked sequence's bound
    # between steps + one step's rows
    num_window_blocks: int = 0
    state_manager: DSStateManagerConfig = DSStateManagerConfig()
    quantization: QuantizationConfig = QuantizationConfig()
    prefix_cache: PrefixCacheConfig = PrefixCacheConfig()
    kv_tier: KVTierConfig = KVTierConfig()
    spec_decode: SpecDecodeConfig = SpecDecodeConfig()
    lora: LoRAServingConfig = LoRAServingConfig()
    structured: StructuredConfig = StructuredConfig()
    async_burst: AsyncBurstConfig = AsyncBurstConfig()
    # compiled decode/verify programs kept per engine: each distinct
    # (burst length k, sampling key) and (verify, draft length) compiles
    # its own program; beyond the cap the least-recently-used is dropped.
    # Sizing: one burst family whatever the pipeline's depth, and burst
    # k / k+1 hold DIFFERENT keys alive simultaneously when bursts taper
    # (k halves toward max_new), so a steady mixed workload can keep live
    #   2 (greedy/sampled) x log2(max_burst)=4 burst keys (= 8)
    #   + 2 (plain/packed) x 2 x log2(draft cap)=4 verify keys (= 16)
    # = 24 programs at once. 48 leaves headroom so the steady state
    # never thrashes (the eviction-regression test asserts zero
    # evictions over a pipelined trace).
    burst_fn_cache_cap: int = 48

"""Kernel-implementation selection for the v2 ragged engine.

Capability match for the reference's
``deepspeed/inference/v2/modules/heuristics.py`` (``instantiate_attn``
etc. at heuristics.py:1 over the ``DSModuleRegistry``): each logical op
has a REGISTRY of implementations with a ``supports`` predicate; the
highest-priority supported one is chosen, and the engine config can pin
a specific implementation by name
(``RaggedInferenceEngineConfig.implementation_overrides``).

Implementations registered for ``attention`` (the ragged decode op):

- ``pallas_paged``          — single-device Pallas decode kernel
  (``ops/pallas/paged_attention``); needs ``head_dim % 128 == 0`` - or a
  head of 64 with an even number of key-value heads, which it takes a
  pair of heads a 128-lane slice - and ``block_size % 8 == 0`` (Mosaic
  lane alignment) and a ``[tokens, max_blocks]`` block table that fits
  the kernel's SMEM budget.
- ``pallas_paged_sharded``  — the same kernel per tensor-parallel shard
  under ``shard_map`` (query/KV heads divide over 'tensor').
- ``xla_gather``            — gather-based XLA reference; always
  supported, and the only path for ALiBi models.

Every implementation takes the whole pool ``[L, NB, bs, Hkv*Dh]`` and
the layer to read as an index (``kc_shape`` below is the pool's shape;
the KV-head count is its last dim over ``head_dim``).

All three also take the step's **query tiles** (``tiles``, after
``live_rows``; ``paged_attention.query_tiles`` of the batch, or None): the
adjacent rows of one sequence at consecutive positions - a prompt chunk, a
verify program's ``d + 1`` rows - which the two kernels attend through one
walk of the sequence's context a tile of up to ``QUERY_TILE`` rows, and
the gather ignores. It is no choice of this registry and no setting: the
caller that knows a row's table is its sequence's whole table
(``model_runner._paged_attend``) passes what the batch says, a burst
program (one row a sequence by construction) and a selection's call pass
None, and a row with no such neighbours is attended alone, as before.

Those three read the state kind ``kv`` (keys and values). A model kind
whose state is ``latent`` (``model_runner.MoonlightKind``: one
normalised compressed row and one rotated key a token, shared by all
heads, queried with absorbed weights) has its own two, and an
implementation is never offered a state kind other than its own
(``STATE_KIND``; ``kv`` where a class names none):

- ``pallas_paged_mla``      — single-device Pallas latent decode kernel
  (``ops/pallas/paged_mla_attention``); needs ``kv_lora_rank % 128 ==
  0``, the rotated key's row padded to whole 128-lane tiles,
  ``block_size % 16 == 0``, no mesh, and a block table that fits SMEM.
- ``xla_gather_mla``        — the same mathematics by gather; always
  supported for the latent state (the CPU's path).

For these ``head_dim`` is ``kv_lora_rank``, ``kc_shape`` the latent
pool's shape and ``q_shape`` ``[tokens, heads, rank + rope lanes]``.
"""

from jax.sharding import PartitionSpec as P

REGISTRY = {"attention": []}


class AttentionChoice:
    """The engine's side of the selection: the implementation its config
    pinned (``override``, None = choose), and ``selected`` — what each
    traced program actually got, keyed by the program's token count, so
    the engine can say which implementation serves and nothing has to be
    inferred from the backend. ``state_step``: the same for the state step
    of a model kind that has one - Mamba-2's (``pallas_ssm_state``, the
    kernel that visits a step's slots in place, or ``xla``:
    ``ops/pallas/ssm_state.state_step_impl``) or Mamba-1's
    (``pallas_selective_scan``, the kernel that runs each sequence's rows
    through its slot in one visit, or ``xla``, a ``lax.scan`` over the rows:
    ``ops/pallas/selective_scan.scan_impl``) or the Kimi delta rule's
    (``pallas_kda``, the same one visit of a slot for a transition that
    rotates the state as well as decaying it, or ``xla``:
    ``ops/pallas/kda.delta_rule_impl``); nothing pins it.
    ``tiled``: the token counts of the programs whose paged kernel
    ``model_runner._paged_attend`` gave the step's query tiles."""

    def __init__(self, override=None):
        self.override = override
        self.selected = {}
        self.state_step = {}
        self.tiled = set()


def register_implementation(op, name):
    """Decorator: register ``cls``-style factory with ``supports`` and
    ``instantiate`` staticmethods under ``op``."""
    def wrap(impl):
        REGISTRY[op].append((name, impl))
        return impl
    return wrap


def implementations(op):
    return [name for name, _ in REGISTRY[op]]


@register_implementation("attention", "pallas_paged")
class _PallasPaged:

    @staticmethod
    def supports(mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
        from deepspeed_tpu.ops.pallas import use_pallas
        from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
        return (alibi is None and (mesh is None or mesh.size == 1)
                and use_pallas()
                and kernel_supported(head_dim, block_size, kc_shape[3] // head_dim)
                and smem_table_fits(q_shape[0], max_blocks))

    @staticmethod
    def instantiate(mesh, head_dim, block_size, q_shape, kc_shape, alibi):
        from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
        return paged_decode_attention


@register_implementation("attention", "pallas_paged_sharded")
class _PallasPagedSharded:

    Q_SPEC = P(None, "tensor", None)
    KV_SPEC = P(None, None, None, "tensor")

    @staticmethod
    def supports(mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
        from deepspeed_tpu.ops.pallas import kernel_dispatch, spec_divides
        from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
        if alibi is not None or mesh is None or mesh.size == 1:
            return False
        tp = dict(mesh.shape).get("tensor", 1)
        n_kv = kc_shape[3] // head_dim
        return (kernel_dispatch(mesh) == "shard_map"
                and kernel_supported(head_dim, block_size, max(n_kv // tp, 1))
                # tokens and tables are replicated: every shard holds them whole
                and smem_table_fits(q_shape[0], max_blocks)
                and spec_divides(mesh, _PallasPagedSharded.Q_SPEC, q_shape)
                # a shard of the flattened last dim is a contiguous group of whole heads
                and n_kv % tp == 0
                # per-shard GQA grouping needs whole KV-head groups
                and (q_shape[1] // n_kv) * n_kv == q_shape[1])

    @staticmethod
    def instantiate(mesh, head_dim, block_size, q_shape, kc_shape, alibi):
        from deepspeed_tpu.ops.pallas import shard_map_kernel
        from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
        cls = _PallasPagedSharded
        # tables, positions, the layer, the live rows and the query tiles (four
        # arrays, or None: no leaf) are replicated: every shard holds them whole
        return shard_map_kernel(
            paged_decode_attention, mesh,
            in_specs=(cls.Q_SPEC, cls.KV_SPEC, cls.KV_SPEC, P(), P(), P(), P(), P()),
            out_specs=cls.Q_SPEC)


@register_implementation("attention", "xla_gather")
class _XlaGather:

    @staticmethod
    def supports(mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
        return True

    @staticmethod
    def instantiate(mesh, head_dim, block_size, q_shape, kc_shape, alibi):
        import functools

        from deepspeed_tpu.ops.pallas.paged_attention import xla_paged_attention
        return functools.partial(xla_paged_attention, alibi_slopes=alibi)


@register_implementation("attention", "pallas_paged_mla")
class _PallasPagedMLA:
    STATE_KIND = "latent"

    @staticmethod
    def supports(mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
        from deepspeed_tpu.ops.pallas import use_pallas
        from deepspeed_tpu.ops.pallas.paged_attention import smem_table_fits
        from deepspeed_tpu.ops.pallas.paged_mla_attention import mla_kernel_supported
        return (alibi is None and (mesh is None or mesh.size == 1) and use_pallas()
                and mla_kernel_supported(head_dim, q_shape[2] - head_dim, block_size)
                and smem_table_fits(q_shape[0], max_blocks))

    @staticmethod
    def instantiate(mesh, head_dim, block_size, q_shape, kc_shape, alibi):
        from deepspeed_tpu.ops.pallas.paged_mla_attention import paged_mla_decode_attention
        return paged_mla_decode_attention


@register_implementation("attention", "xla_gather_mla")
class _XlaGatherMLA:
    STATE_KIND = "latent"

    @staticmethod
    def supports(mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
        return alibi is None

    @staticmethod
    def instantiate(mesh, head_dim, block_size, q_shape, kc_shape, alibi):
        from deepspeed_tpu.ops.pallas.paged_mla_attention import xla_paged_mla_attention
        return xla_paged_mla_attention


def instantiate_attn(mesh, head_dim, block_size, q_shape, kc_shape, alibi,
                     max_blocks, override=None, state_kind="kv"):
    """→ ``(impl_name, fn(q, kc, vc, tab, pos, layer, live_rows, tiles))`` — the first supported
    implementation in registration (priority) order, or the named one
    when the config pins ``override`` (reference
    heuristics.instantiate_attn + config_bundle semantics). A pin that
    does not support the config raises; it never degrades.
    ``max_blocks``: the block table's width (blocks per sequence).
    ``state_kind``: what the pool holds (``kv`` | ``latent``); only the
    implementations of that kind are candidates."""
    for name, impl in REGISTRY["attention"]:
        if override is not None and name != override:
            continue
        if getattr(impl, "STATE_KIND", "kv") == state_kind and impl.supports(
                mesh, head_dim, block_size, q_shape, kc_shape, alibi, max_blocks):
            return name, impl.instantiate(mesh, head_dim, block_size,
                                          q_shape, kc_shape, alibi)
        if override is not None:
            import jax
            raise ValueError(
                f"implementation_overrides pinned attention={override!r}, but it "
                f"does not support this config (state kind {state_kind!r}, its own "
                f"{getattr(impl, 'STATE_KIND', 'kv')!r}; head_dim={head_dim}, "
                f"block_size={block_size}, tokens={q_shape[0]}, max_blocks={max_blocks}, "
                f"mesh={mesh and dict(mesh.shape)}, alibi={alibi is not None}, "
                f"backend={jax.default_backend()!r})")
    if override is None:
        raise ValueError(f"no attention implementation supports state kind {state_kind!r} here "
                         f"(head_dim={head_dim}, block_size={block_size}, alibi={alibi is not None})")
    raise ValueError(f"no attention implementation named {override!r}; "
                     f"available: {implementations('attention')}")

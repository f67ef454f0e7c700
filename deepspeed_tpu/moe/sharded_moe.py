"""Top-k gating + expert dispatch, TPU-native.

Capability match for the reference's ``deepspeed/moe/sharded_moe.py``
(``top1gating`` at sharded_moe.py:181, ``top2gating`` at 288,
``TopKGate`` at 372, ``MOELayer`` at 455, ``_AllToAll`` at 96). The
reference dispatches tokens with einsum algebra and two explicit
``all_to_all`` collectives; here the same einsum dispatch produces an
expert-major tensor whose leading dim is constrained to the 'expert'
mesh axis — XLA inserts the all-to-all pair over ICI.

Gating math (softmax → top-k → capacity truncation → normalized
combine weights + load-balancing aux loss) runs in fp32 with fully
static shapes, jit- and scan-safe.
"""

from typing import Optional, Tuple

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.sequence.layer import constrain

MIN_CAPACITY = 4

# The flax collection a dropless layer on an expert axis sows its exchange's counts into;
# the trainer makes it mutable, sums each name over the layers and writes the sums on its
# step record (``runtime/engine.py``; docs/OBSERVABILITY.md).
STEP_COUNTS = "step_counts"
EXCHANGE_COUNTS = ("n_expert_rows", "expert_rows_max_rank", "n_share_passes",
                   "rows_beyond_passes", "rows_by_kernel")


def exchange_count_names(mesh):
    """:data:`EXCHANGE_COUNTS` where a dropless layer under ``mesh`` runs the
    expert exchange (``ops/grouped_gemm.mesh_share_axes``), else ()."""
    from deepspeed_tpu.ops.grouped_gemm import mesh_share_axes
    return EXCHANGE_COUNTS if mesh_share_axes(mesh) is not None else ()


def _capacity(num_tokens: int, num_experts: int, k: int, capacity_factor: float,
              min_capacity: int = MIN_CAPACITY) -> int:
    cap = int(np.ceil(num_tokens * k * capacity_factor / num_experts))
    return max(cap, min_capacity)


def multiplicative_jitter(x, rng, epsilon: float = 1e-2):
    """Multiply by iid uniform noise in [1-eps, 1+eps] (reference
    ``multiplicative_jitter``, sharded_moe.py:55 — applied to the gate's
    input under ``noisy_gate_policy='Jitter'``)."""
    if epsilon == 0.0:
        return x
    noise = jax.random.uniform(rng, x.shape, jnp.float32,
                               minval=1.0 - epsilon, maxval=1.0 + epsilon)
    return x * noise.astype(x.dtype)


def gshard_aux_loss(gates, primary_mask):
    """GShard load-balancing loss from the primary assignment:
    sum(mean_prob * mean_routed_fraction) * E (reference sharded_moe
    l_aux) — shared by the capacity and dropless gates."""
    me = gates.mean(axis=0)
    ce = primary_mask.astype(jnp.float32).mean(axis=0)
    return jnp.sum(me * ce) * gates.shape[-1]


def topkgating(logits, k: int, capacity_factor: float = 1.0,
               min_capacity: int = MIN_CAPACITY, normalize: bool = True):
    """Compute gating for top-k routing.

    Args:
        logits: [T, E] raw gate scores.
    Returns:
        (aux_loss, combine_weights [T, E, C], dispatch_mask [T, E, C])
    """
    T, E = logits.shape
    C = _capacity(T, E, k, capacity_factor, min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]

    # Greedy top-k expert choice per token.
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # [T, k]

    masks, loc_toks, keeps = [], [], []
    offset = jnp.zeros((E,), jnp.int32)  # tokens already assigned per expert
    aux_loss = jnp.zeros((), jnp.float32)
    for j in range(k):
        mask_j = jax.nn.one_hot(topk_idx[:, j], E, dtype=jnp.int32)  # [T, E]
        if j == 0:
            aux_loss = gshard_aux_loss(gates, mask_j)
        # position of each token within its expert's capacity buffer
        loc_j = jnp.cumsum(mask_j, axis=0) - 1 + offset[None, :]  # [T, E]
        offset = offset + mask_j.sum(axis=0)
        within = (loc_j < C) & (mask_j > 0)
        masks.append(mask_j)
        loc_toks.append((loc_j * mask_j).sum(axis=-1))  # [T] slot in chosen expert
        keeps.append(within.any(axis=-1))

    # Drop over-capacity assignments, THEN normalize over the survivors
    # (reference top2gating renormalizes post-truncation).
    w = topk_vals * jnp.stack(keeps, axis=1).astype(jnp.float32)  # [T, k]
    if normalize and k > 1:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    combine = jnp.zeros((T, E, C), jnp.float32)
    for j in range(k):
        combine = combine + (w[:, j, None, None]
                             * masks[j].astype(jnp.float32)[:, :, None]
                             * jax.nn.one_hot(loc_toks[j], C, dtype=jnp.float32)[:, None, :])

    dispatch = combine > 0.0
    return aux_loss, combine, dispatch


def top1gating(logits, capacity_factor=1.0, min_capacity=MIN_CAPACITY):
    """Switch-style top-1 gating (reference sharded_moe.py:181)."""
    return topkgating(logits, k=1, capacity_factor=capacity_factor, min_capacity=min_capacity)


def top2gating(logits, capacity_factor=1.0, min_capacity=MIN_CAPACITY):
    """GShard top-2 gating (reference sharded_moe.py:288)."""
    return topkgating(logits, k=2, capacity_factor=capacity_factor, min_capacity=min_capacity)


class TopKGate(nn.Module):
    """Linear gate + top-k routing (reference ``TopKGate``, sharded_moe.py:372).

    ``drop_tokens=True`` (default) → capacity-truncated einsum routing:
    returns ``(aux_loss, combine [T, E, C], dispatch [T, E, C])``.
    ``drop_tokens=False`` → dropless routing (reference
    sharded_moe.py:186,212 no-drop gather; Mixtral-style training):
    returns ``(aux_loss, topk_weights [T, k], topk_idx [T, k])`` for the
    grouped-GEMM dispatch, where every token reaches its full top-k."""
    num_experts: int
    k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = MIN_CAPACITY
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        # gate weights always fp32 (reference keeps wg in fp32).
        # x may be [..., D]: the Dense runs on the un-reshaped activation
        # (reshaping the big multi-axis-sharded operand forces an XLA
        # reshard); only the small [T, E] logits are flattened.
        x32 = x.astype(jnp.float32)
        if self.noisy_gate_policy == "Jitter" and train:
            rng = self.make_rng("dropout") if self.has_rng("dropout") else None
            if rng is not None:
                x32 = multiplicative_jitter(x32, rng)
        # float32 in fact: at the default precision a TPU multiplies float32 operands in one
        # bfloat16 pass, which moves a logit by ~1e-2 and a step's picks with it (PERF.md, PR 58)
        logits = nn.Dense(self.num_experts, use_bias=False, name="wg", dtype=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)(x32)
        logits = logits.reshape(-1, self.num_experts)
        if self.noisy_gate_policy == "RSample" and train:
            rng = self.make_rng("dropout") if self.has_rng("dropout") else None
            if rng is not None:
                logits = logits + jax.random.normal(rng, logits.shape) / self.num_experts
        if not self.drop_tokens:
            gates = jax.nn.softmax(logits, axis=-1)  # [T, E]
            topk_vals, topk_idx = jax.lax.top_k(gates, self.k)
            mask1 = jax.nn.one_hot(topk_idx[:, 0], self.num_experts, dtype=jnp.float32)
            aux_loss = gshard_aux_loss(gates, mask1)
            if self.k > 1:
                topk_vals = topk_vals / jnp.maximum(topk_vals.sum(-1, keepdims=True), 1e-9)
            return aux_loss, topk_vals, topk_idx
        cf = self.capacity_factor if train else self.eval_capacity_factor
        return topkgating(logits, self.k, cf, self.min_capacity)


class MOELayer(nn.Module):
    """Dispatch → expert FFN → combine (reference ``MOELayer``,
    sharded_moe.py:455). Experts are a stacked param tensor with a
    leading E dim sharded over the 'expert' mesh axis; the dispatched
    activations are constrained to the same axis, so XLA materializes
    the token↔expert all-to-all exchange.
    """
    num_experts: int
    hidden_size: int
    intermediate_size: int
    k: int = 2
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = MIN_CAPACITY
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        B, S, D = x.shape

        # the gate consumes x 3-D (only its [T, E] logits flatten)
        with jax.named_scope("ds.moe_route"):
            aux_loss, combine, dispatch = TopKGate(
                num_experts=self.num_experts, k=self.k, capacity_factor=self.capacity_factor,
                eval_capacity_factor=self.eval_capacity_factor, min_capacity=self.min_capacity,
                noisy_gate_policy=self.noisy_gate_policy, drop_tokens=self.drop_tokens,
                name="gate")(x, train=train)

        if not self.drop_tokens:
            # Dropless dispatch (reference drop_tokens=False no-drop gather): the
            # serving grouped GEMM over expert-sorted rows IS the training dispatch -
            # every token reaches its full top-k, and the grouped matmul
            # differentiates. On an expert axis (``ops/grouped_gemm.exchanges_shares``)
            # every rank computes its own experts' share of its axis's tokens: the
            # rows gathered over the axis in the compute dtype, the picks its experts
            # hold listed in passes of a static size (one for an even router, as many
            # as the held picks take: none is dropped), each one's row copied once
            # from its token's row into its slot of the grouped matmul's layout and
            # the product's row read once from there into its token's sum (two row
            # kernels, each the other's transpose: ``ops/pallas/moe_rows.py``), and
            # that [T, D] reduce-scattered onto the token's rank
            # (``expert_share_exchange_ffn``); its counts go to the step record,
            # ``rows_by_kernel`` (the rows those two kernels copied: twice the held
            # picks, 0 where their ``jnp`` forms ran) among them.
            # With a tensor axis beside the expert axis the older dispatch stays:
            # every pick a row on every shard, the picks held elsewhere zeroed, a
            # float32 psum.
            #
            # Quantized (OptimizedLinear-style frozen-base) training:
            # dropless_moe_ffn also accepts grouped-layout
            # QuantizedWeight stacks and differentiates through them in
            # x only (integer carriers get float0 cotangents, scales
            # zeros). This flax path cannot hand them over itself —
            # self.param unboxes AxisMetadata — so a frozen-base trainer
            # passes the boxed stacks to dropless_moe_ffn directly, as
            # the v2 runner does.
            from deepspeed_tpu.ops.grouped_gemm import (ExpertShare, dropless_moe_ffn,
                                                        exchanges_shares,
                                                        expert_share_exchange_ffn, mesh_share_rows)
            from deepspeed_tpu.parallel import groups
            mesh = groups.get_mesh(required=False)
            topk_w, topk_idx = combine, dispatch  # [T, k] each (gate's dropless form)
            init = nn.initializers.lecun_normal()
            E, I = self.num_experts, self.intermediate_size
            w1 = self.param("experts_w1", init, (E, D, I))
            w3 = self.param("experts_w3", init, (E, D, I))
            w2 = self.param("experts_w2", init, (E, I, D))
            whole = ExpertShare(0, E, E)
            if exchanges_shares(mesh, B * S, whole, (w1, w3, w2)):
                combined, counts = expert_share_exchange_ffn(
                    x.reshape(B * S, D), topk_idx, topk_w.astype(x.dtype), w1, w3, w2,
                    whole, mesh)
                if not self.is_initializing():
                    held, passes = counts[..., 0], counts[..., 1]  # [copies over data, ranks]
                    rows = mesh_share_rows(B * S // held.shape[0], self.k, whole, held.shape[1],
                                           x.dtype)
                    for name, value in zip(EXCHANGE_COUNTS, (
                            jnp.sum(held), jnp.max(jnp.sum(held, axis=0)), jnp.max(passes),
                            jnp.sum(jnp.maximum(held - passes * rows, 0)),
                            jnp.sum(counts[..., 2]))):
                        self.sow(STEP_COUNTS, name, value.astype(jnp.int32),
                                 reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((), jnp.int32))
            else:
                combined = dropless_moe_ffn(x.reshape(B * S, D), topk_idx,
                                            topk_w.astype(x.dtype),
                                            w1, w3, w2, num_experts=E, mesh=mesh)
            return combined.reshape(B, S, D), aux_loss

        # [E, C, D] expert-major dispatch (XLA inserts token→expert a2a).
        # The big operand stays 3-D [B, S, D]: flattening it first would
        # reshape a multi-axis-sharded token dim and XLA pays an
        # involuntary full rematerialization on the reshard.
        E, C = dispatch.shape[1], dispatch.shape[2]
        disp4 = dispatch.reshape(B, S, E, C)
        dispatched = jnp.einsum("bsec,bsd->ecd", disp4.astype(x.dtype), x)
        dispatched = constrain(dispatched, ("expert", None, None))

        out = self.experts(dispatched)
        out = constrain(out, ("expert", None, None))

        # combine back to token-major (expert→token a2a)
        combined = jnp.einsum("bsec,ecd->bsd", combine.reshape(B, S, E, C).astype(x.dtype), out)
        # Note on the XLA "Involuntary full rematerialization" warnings
        # visible in multi-axis dryruns: they were chased to the GATE's
        # top-k bookkeeping tensors ([B, S, capacity]-sized, ~KBs), not
        # the activation path — the big operands above stay 3-D exactly
        # so their token dim is never reshaped across shardings.
        return combined, aux_loss

    def experts(self, dispatched):
        """SwiGLU expert FFNs over [E, C, D]; params stacked on E."""
        E, C, D = dispatched.shape
        I = self.intermediate_size
        init = nn.initializers.lecun_normal()
        w1 = self.param("experts_w1", init, (E, D, I))  # gate
        w3 = self.param("experts_w3", init, (E, D, I))  # up
        w2 = self.param("experts_w2", init, (E, I, D))  # down
        h = nn.silu(jnp.einsum("ecd,edi->eci", dispatched, w1.astype(dispatched.dtype)))
        h = h * jnp.einsum("ecd,edi->eci", dispatched, w3.astype(dispatched.dtype))
        h = constrain(h, ("expert", None, "tensor"))
        return jnp.einsum("eci,eid->ecd", h, w2.astype(dispatched.dtype))

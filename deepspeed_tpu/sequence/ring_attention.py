"""Ring attention: context parallelism over the 'sequence' mesh axis.

Beyond-reference long-context support (the reference snapshot ships only
Ulysses all-to-all SP, ``deepspeed/sequence/layer.py`` — no ring/context
parallelism). Ulysses is bounded by the head count (seq shards trade for
head shards); ring attention scales the SEQUENCE dimension itself:

- every shard keeps its local Q block resident;
- K/V blocks rotate around the ICI ring via ``lax.ppermute``;
- each arriving block folds into a flash-style running softmax
  (fp32 running max / denominator / weighted accumulator), so the full
  [S, S] score matrix never materializes and the communication is
  neighbour-only (ring bandwidth, not all-to-all bisection).

Causality is handled per block pair: a K/V block from a later shard is
skipped-by-mask (computed uniformly for SPMD, masked to -inf), the
diagonal block applies the triangular mask, earlier blocks attend fully.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import groups

NEG_INF = -jnp.inf


def _block_update(q, k, v, m, l, acc, q_pos, k_pos, causal, scale):
    """Fold one K/V block into the running softmax.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m/l: [B, H, Sq]; acc like q
    (fp32); q_pos/k_pos: [Sq]/[Sk] global positions. Masked entries are
    true -inf; the exp() guards below turn the would-be NaNs
    (-inf minus -inf) into exact zero contributions."""
    if k.shape[2] != q.shape[2]:
        # GQA: blocks travel the ring with Hkv heads (H/Hkv less traffic);
        # expansion is shard-local, just-in-time for the score matmul
        from deepspeed_tpu.models.llama import repeat_kv
        k, v = repeat_kv(k, v, q.shape[2] // k.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))               # [B, H, Sq]
    # m == -inf ⇔ nothing accumulated yet (l = 0, acc = 0): alpha moot
    alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
    # s == -inf ⇔ masked key (and possibly m_new still -inf): weight 0
    p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new[..., None]))  # [B, H, Sq, Sk]
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _ring_body(q, k, v, axis, causal, sm_scale):
    """shard_map body: q/k/v are the LOCAL [B, S_local, H, D] blocks."""
    B, Sl, H, D = q.shape
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)

    q_pos = idx * Sl + jnp.arange(Sl)
    m0 = jnp.full((B, H, Sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    acc0 = jnp.zeros((B, Sl, H, D), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # block i arrived from shard (idx - i) mod n
        src = (idx - i) % n
        k_pos = src * Sl + jnp.arange(Sl)
        m, l, acc = _block_update(q, k_cur, v_cur, m, l, acc, q_pos, k_pos,
                                  causal, scale)
        # rotate for the next step (the final rotation is harmless and
        # keeps the loop body uniform)
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return k_nxt, v_nxt, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, n, step, (k, v, m0, l0, acc0))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]  # [B, Sq, H, 1]
    return (acc / denom).astype(q.dtype)


def ring_attention(q, k, v, causal=True, sm_scale=None, axis="sequence", mesh=None,
                   impl="auto"):
    """Context-parallel attention on sequence-sharded [B, S, H, D] inputs.

    ``k``/``v`` may carry fewer (GQA) heads than ``q`` — they travel the
    ring unexpanded. Inputs arrive sharded ``[B, S/'sequence', H, D]``
    (the canonical Ulysses input layout); output has the same sharding.
    Falls back to single-device attention (``impl`` selects the kernel)
    when the axis is trivial.
    """
    mesh = mesh if mesh is not None else groups.get_mesh(required=False)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}
    if sizes.get(axis, 1) <= 1:
        from deepspeed_tpu.models.llama import _local_attention, repeat_kv
        k, v = repeat_kv(k, v, q.shape[2] // k.shape[2])
        if sm_scale is not None:
            # _local_attention hardcodes 1/sqrt(D); fold the caller's
            # scale into q so both topologies compute the same scores
            q = q * (sm_scale * np.sqrt(q.shape[-1]))
        return _local_attention(q, k, v, impl, causal=causal)
    from deepspeed_tpu.ops.pallas import current_manual_axes
    if current_manual_axes():
        # a nested full-mesh shard_map is not expressible inside another
        # manual region (e.g. the pipeline engine's 'pipe' shard_map)
        raise NotImplementedError(
            f"ring attention inside a manual shard_map region over "
            f"{sorted(current_manual_axes())} is not supported — use sp_impl='ulysses' "
            f"with the pipeline engine")

    from deepspeed_tpu.sequence.layer import live_spec
    spec = live_spec(mesh, (("data", "expert"), axis, ("tensor",), None))
    body = functools.partial(_ring_body, axis=axis, causal=causal, sm_scale=sm_scale)
    # fully-manual region (the repo's shard_map idiom): batch/heads are
    # simply partitioned; only the 'sequence' axis communicates (ppermute)
    mapped = shard_map(lambda a, b, c: body(a, b, c),
                           mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                           check_vma=False)
    return mapped(q, k, v)

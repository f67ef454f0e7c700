"""Pipeline-parallel training engine, TPU-native.

Capability match for the reference's ``deepspeed/runtime/pipe/engine.py``
(``PipelineEngine`` at engine.py:56, ``train_batch`` at 326,
``_exec_schedule`` at 1420). The execution model is redesigned for XLA:

Instead of per-stage processes dispatching schedule instructions and
exchanging tensors over NCCL P2P (reference pipe/p2p.py), the ENTIRE
pipeline — all stages, all micro-batches, forward and backward — is one
jitted SPMD program:

- the 'pipe' mesh axis carries the stages (``jax.shard_map`` manual
  over 'pipe' only; data/tensor/sequence/expert axes stay under GSPMD
  auto-sharding, so ZeRO/TP/SP compose unchanged inside each stage);
- a ``lax.scan`` over ``micro_batches + stages - 1`` virtual clock
  ticks advances the pipeline; activations move stage→stage with
  ``lax.ppermute`` over the ICI ring (the analogue of SendActivation/
  RecvActivation);
- the backward pipeline is not hand-written: differentiating through
  scan+ppermute yields exactly the reversed schedule with grads
  flowing by the reverse permute (SendGrad/RecvGrad), and the tick body
  is rematerialized (``jax.checkpoint``) so live activation memory
  stays at one stage-boundary tensor per tick — the fill-drain
  equivalent of 1F1B's memory bound;
- the last stage computes the loss scalar in-pipeline, so only
  [B, S, D] activations and one f32 scalar ever cross stages.

The instruction-stream schedules (``pipe/schedule.py``) describe this
same computation for tooling/tests.
"""

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, _is_float
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.pipe.schedule import InferenceSchedule, TrainSchedule
from deepspeed_tpu.runtime.zero.partitioning import batch_spec, path_tree_map
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.timer import TRAIN_BATCH_TIMER


class PipelineEngine(DeepSpeedEngine):
    """Training engine for :class:`PipelineModule` models."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        assert isinstance(self.module, PipelineModule), \
            "model must be deepspeed_tpu.pipe.PipelineModule"
        self.num_stages = groups.get_pipeline_parallel_world_size()
        if self.module._num_stages is not None and self.module._num_stages != self.num_stages:
            raise ValueError(
                f"PipelineModule was built for {self.module._num_stages} stages but the mesh "
                f"'pipe' axis has {self.num_stages} — the stacked body layout would silently "
                f"drop layers; rebuild the module with num_stages={self.num_stages}")
        self.micro_batches = self.gradient_accumulation_steps()
        self.micro_batch_size = self.train_micro_batch_size_per_gpu()
        self._act_struct = None
        log_dist(f"PipelineEngine: stages={self.num_stages} micro_batches={self.micro_batches}",
                 ranks=[0])

    # ------------------------------------------------------------------
    # The reference forbids forward/backward on the pipeline engine too
    # (train_batch/eval_batch are the only entry points).
    # ------------------------------------------------------------------
    def forward(self, *args, **kwargs):
        raise RuntimeError("PipelineEngine does not support forward(); use train_batch/eval_batch")

    def backward(self, *args, **kwargs):
        raise RuntimeError("PipelineEngine does not support backward(); use train_batch")

    def step(self, *args, **kwargs):
        raise RuntimeError("PipelineEngine fuses the step into train_batch()")

    # ------------------------------------------------------------------
    def _materialize_state(self, sample_inputs, sample_labels):
        if self._initialized:
            return
        if self._config.zero_config.offload_param_device().value != "none":
            raise NotImplementedError(
                "offload_param with the pipeline engine is not supported: the pipe "
                "shard_map schedule does not stream host-resident stage params — "
                "drop offload_param or use the non-pipeline engine")
        if self.params is None:
            params, act_struct = self.module.init(self._param_rng, sample_inputs)
            self.params = jax.tree.map(
                lambda x: x.astype(self.compute_dtype) if _is_float(x) else x, params)
            self._act_struct = act_struct
        else:
            _, self._act_struct = jax.eval_shape(
                lambda r: self.module.init(r, sample_inputs), self._param_rng)

        # Shardings: stacked body params carry their stage dim on 'pipe'
        # (each device materializes ONLY its own stage's layers — the
        # parameter-memory half of pipeline parallelism); prologue/
        # epilogue params are pipe-replicated. ZeRO placement over the
        # other axes composes on the inner dims via the sharding policy.
        self._param_shardings = self._pipe_tree_shardings(self.params, self.sharding_policy.param_spec)
        self._param_specs = self._pipe_tree_specs(self.params, self.sharding_policy.param_spec)
        self._opt_shardings = self._pipe_tree_shardings(self.params, self.sharding_policy.opt_spec)
        self._grad_specs = self._pipe_tree_specs(self.params, self.sharding_policy.grad_spec)
        self.params = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                   self.params, self._param_shardings)
        self._trainable_mask = self._build_trainable_mask()

        mixed = self.compute_dtype != jnp.float32
        if mixed or self.zero_stage >= 1:
            self.master_params = jax.jit(
                lambda p: jax.tree.map(lambda x: x.astype(jnp.float32) if _is_float(x) else x, p),
                out_shardings=self._opt_shardings)(self.params)
        else:
            self.master_params = self.params

        transform = self.optimizer.transform()
        self._opt_init, self._opt_update = transform.init, transform.update
        abstract_state = jax.eval_shape(self._opt_init, self.master_params)
        state_shardings = self._opt_state_shardings(abstract_state)
        self.opt_state = jax.jit(self._opt_init, out_shardings=state_shardings)(self.master_params)
        self._opt_state_shards = state_shardings
        self._initialized = True

        pending = getattr(self, "_pending_optim_state", None)
        if pending is not None:
            self._restore_optim_state(pending)
            self._pending_optim_state = None
        pending_u = getattr(self, "_pending_universal", None)
        if pending_u is not None:
            self._apply_universal(pending_u)
            self._pending_universal = None

    # ------------------------------------------------------------------
    # Sharding-spec composition for the stacked layout
    # ------------------------------------------------------------------
    def _pipe_spec(self, path, leaf_shape, base_fn):
        """P('pipe', None, *policy-spec-of-inner-dims) for stacked body
        leaves; the plain policy spec (pipe-replicated) otherwise."""
        if self.module.is_stacked and path.startswith("blocks/"):
            inner = tuple(leaf_shape[2:])
            base = tuple(base_fn(path, inner))
            return P("pipe", None, *base)
        return base_fn(path, leaf_shape)

    def _pipe_tree_specs(self, params, base_fn):
        return path_tree_map(lambda path, x: self._pipe_spec(path, x.shape, base_fn), params)

    def _pipe_tree_shardings(self, params, base_fn):
        return path_tree_map(
            lambda path, x: NamedSharding(self.mesh, self._pipe_spec(path, x.shape, base_fn)), params)

    # ------------------------------------------------------------------
    # The fused pipeline program
    # ------------------------------------------------------------------
    def _pipeline_loss_fn(self, for_eval=False):
        """Build ``loss(params, inputs, labels, scale) -> scalar`` where
        inputs/labels have a leading micro-batch dim [M, mb, ...].

        For training, ``params`` are the fp32 MASTER params: the cast to
        the compute dtype happens inside the shard_map so parameter
        cotangents cross the 'pipe' axis (shard_map transpose psum) in
        fp32 — higher-precision grad accumulation, and it sidesteps an
        XLA-CPU crash on bf16 psum of replicated-input cotangents."""
        module = self.module
        mesh = self.mesh
        n_stages = self.num_stages
        M = self.micro_batches
        act_struct = self._act_struct
        compute_dtype = self.compute_dtype

        def inner(params, inputs, labels, scale):
            # Declare the manual 'pipe' axis while tracing so Pallas
            # call sites inside the stages fall back to XLA instead of
            # opening a nested full-mesh shard_map.
            from deepspeed_tpu.ops.pallas import manual_axes
            with manual_axes({"pipe"}):
                return _inner_body(params, inputs, labels, scale)

        def _inner_body(params, inputs, labels, scale):
            params = jax.tree.map(
                lambda x: x.astype(compute_dtype) if _is_float(x) else x, params)
            p = jax.lax.axis_index("pipe") if n_stages > 1 else jnp.zeros((), jnp.int32)
            T = M + n_stages - 1
            h0 = jnp.zeros(act_struct.shape, compute_dtype) if act_struct is not None \
                else jnp.zeros((), compute_dtype)

            stacked = module.is_stacked and n_stages > 1
            if stacked:
                # local view of the stage dim is size 1 (split over 'pipe')
                blocks_local = jax.tree.map(lambda x: x[0], params["blocks"])
                other = {k: v for k, v in params.items() if k != "blocks"}

            def tick(h, t):
                mb = jnp.clip(t - p, 0, M - 1)
                valid = jnp.logical_and(t - p >= 0, t - p < M)
                x_mb = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, mb, 0, False), inputs)
                l_mb = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, mb, 0, False), labels)
                if stacked:
                    # Stage 0 embeds its micro-batch; later stages consume
                    # the permuted boundary activation. All pipe ranks then
                    # run the SAME scan over their local blocks, so GSPMD
                    # collectives over the auto axes stay uniform.
                    x = jax.lax.cond(p == 0,
                                     lambda op: module.prologue_apply(other, op[0]),
                                     lambda op: op[1], (x_mb, h))

                    def body(c, bp):
                        return module.block_apply(bp, c), None

                    x, _ = jax.lax.scan(body, x, blocks_local)
                    loss_c = jax.lax.cond(
                        p == n_stages - 1,
                        lambda xx: module.epilogue_loss(other, xx, l_mb),
                        lambda xx: jnp.zeros((), jnp.float32), x)
                    h_out = x
                else:
                    h_out, loss_c = module.stage_step(params, p, x_mb, l_mb, h)
                loss_c = jnp.where(valid, loss_c, 0.0)
                if n_stages > 1:
                    h_next = jax.lax.ppermute(h_out, "pipe",
                                              [(i, i + 1) for i in range(n_stages - 1)])
                else:
                    h_next = h_out
                return h_next, loss_c

            if not for_eval:
                tick = jax.checkpoint(tick, prevent_cse=False)
            _, losses = jax.lax.scan(tick, h0, jnp.arange(T))
            total = (jnp.sum(losses) / M) * scale
            if n_stages > 1:
                total = jax.lax.psum(total, "pipe")
            return total

        if n_stages > 1:
            param_specs = path_tree_map(
                lambda path, _: P("pipe") if (module.is_stacked and path.startswith("blocks/")) else P(),
                self.master_params)
            return shard_map(inner, mesh=mesh,
                                 in_specs=(param_specs, P(), P(), P()),
                                 out_specs=P(), axis_names={"pipe"}, check_vma=False)
        return inner

    def _pipe_train_fn(self):
        key = "pipe_train"
        if key in self._jit_cache:
            return self._jit_cache[key]
        loss_fn = self._pipeline_loss_fn()
        tied = self.master_params is self.params

        param_shardings = self._param_shardings

        def gathered_loss(master, inputs, labels, scale):
            # Re-place the (ZeRO-sharded) fp32 master onto the PARAM
            # shardings before the pipeline shard_map: GSPMD emits the
            # ZeRO-1 pre-forward all-gather in auto mode, and the manual
            # 'pipe' boundary sees operands already in its layout (a
            # mismatched reshard at that boundary aborts XLA's SPMD
            # partitioner: spmd_partitioner_util.cc CHECK).
            master = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s), master, param_shardings)
            return loss_fn(master, inputs, labels, scale)

        def body(params, master, opt_state, scaler_st, lr, inputs, labels):
            scale = scaler_st["cur_scale"]
            # Differentiate w.r.t. the fp32 master copy (see _pipeline_loss_fn)
            scaled_loss, grads = jax.value_and_grad(gathered_loss)(master, inputs, labels, scale)
            new_params, new_master, new_opt, new_scaler, gnorm, overflow = self._update_math(
                params, master, opt_state, grads, scaler_st, lr)
            mean_loss = scaled_loss / scale
            return new_params, new_master, new_opt, new_scaler, mean_loss, gnorm, overflow

        if tied:
            def fn(params, opt_state, scaler_st, lr, inputs, labels):
                new_params, _, new_opt, new_scaler, mloss, gnorm, overflow = body(
                    params, params, opt_state, scaler_st, lr, inputs, labels)
                return new_params, new_opt, new_scaler, mloss, gnorm, overflow

            jitted = jax.jit(fn, donate_argnums=(0, 1, 2))
        else:
            jitted = jax.jit(body, donate_argnums=(0, 1, 2, 3))
        self._jit_cache[key] = (jitted, tied)
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    def _stack_micro_batches(self, data_iter=None, batch=None):
        """→ (inputs [M, mb, ...], labels [M, mb, ...])."""
        M = self.micro_batches
        if batch is None:
            assert data_iter is not None, "provide data_iter or batch"
            micro = [next(data_iter) for _ in range(M)]
            batch = jax.tree.map(lambda *xs: np.stack(xs), *micro)
            inputs, labels = batch
        else:
            inputs, labels = batch
            lead = jax.tree.leaves(inputs)[0].shape[0]
            flat = M * self.micro_batch_size
            if lead == flat:
                # Flat [M*mb, ...] batch (the dataloader layout). When
                # mb == 1 this is indistinguishable from an already
                # stacked [M, ...] batch; flat wins — callers with
                # pre-stacked micro-batches at mb == 1 must add the
                # explicit batch dim themselves.
                reshape = lambda x: x.reshape((M, self.micro_batch_size) + x.shape[1:])
                inputs = jax.tree.map(reshape, inputs)
                labels = jax.tree.map(reshape, labels)
            elif lead != M:
                raise ValueError(
                    f"batch leading dim {lead} is neither micro_batches*micro_batch_size"
                    f"={flat} (flat) nor micro_batches={M} (stacked)")
        return inputs, labels

    def _place_batch(self, tree):
        def place(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            spec = batch_spec(self.mesh, extra_leading=1, shard_sequence=(x.ndim - 1 >= 2))
            spec = P(*list(spec)[:x.ndim])
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree.map(place, tree)

    def train_batch(self, data_iter=None, batch=None):
        """One full pipelined batch: M micro-batches through all stages,
        backward, and the optimizer step — a single XLA program
        (reference train_batch, pipe/engine.py:326)."""
        inputs, labels = self._stack_micro_batches(data_iter, batch)
        sample = jax.tree.map(lambda x: x[0], inputs)
        self._materialize_state(sample, jax.tree.map(lambda x: x[0], labels))
        inputs = self._place_batch(inputs)
        labels = self._place_batch(labels)

        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        fn, tied = self._pipe_train_fn()
        if tied:
            out = fn(self.params, self.opt_state, self.scaler_state, lr, inputs, labels)
            self.params, self.opt_state, self.scaler_state, mean_loss, gnorm, overflow = out
            self.master_params = self.params
        else:
            out = fn(self.params, self.master_params, self.opt_state, self.scaler_state, lr,
                     inputs, labels)
            (self.params, self.master_params, self.opt_state, self.scaler_state,
             mean_loss, gnorm, overflow) = out
        self.global_steps += 1
        self.micro_steps += self.micro_batches
        self.global_samples += self.train_batch_size()
        self.overflow = bool(overflow) if self.fp16_enabled() else False
        self.global_grad_norm = float(gnorm)
        if not self.overflow and self.lr_scheduler is not None:
            self.lr_scheduler.step()
        elif self.overflow:
            self.skipped_steps += 1
        self.timers(TRAIN_BATCH_TIMER).stop()
        self.tput_timer.stop(global_step=True)
        self.losses = mean_loss
        self._write_monitor(loss=mean_loss)
        return mean_loss

    def eval_batch(self, data_iter=None, batch=None, return_logits=False,
                   compute_loss=True, reduce_output="avg"):
        """Forward-only pipelined evaluation (reference eval_batch,
        pipe/engine.py:438). Returns the psum'd mean loss."""
        if return_logits or not compute_loss or reduce_output != "avg":
            raise NotImplementedError(
                "eval_batch currently returns only the mean loss "
                "(return_logits/compute_loss/reduce_output not yet supported)")
        inputs, labels = self._stack_micro_batches(data_iter, batch)
        self._materialize_state(jax.tree.map(lambda x: x[0], inputs),
                                jax.tree.map(lambda x: x[0], labels))
        inputs = self._place_batch(inputs)
        labels = self._place_batch(labels)
        key = "pipe_eval"
        if key not in self._jit_cache:
            loss_fn = self._pipeline_loss_fn(for_eval=True)
            self._jit_cache[key] = jax.jit(
                lambda params, i, l: loss_fn(params, i, l, jnp.ones((), jnp.float32)))
        return self._jit_cache[key](self.params, inputs, labels)

    # ------------------------------------------------------------------
    # Schedule inspection (parity surface; execution is fused)
    # ------------------------------------------------------------------
    def train_schedule(self, stage_id=None):
        stage_id = groups.get_pipeline_parallel_rank() if stage_id is None else stage_id
        return TrainSchedule(micro_batches=self.micro_batches, stages=self.num_stages,
                             stage_id=stage_id)

    def inference_schedule(self, stage_id=None):
        stage_id = groups.get_pipeline_parallel_rank() if stage_id is None else stage_id
        return InferenceSchedule(micro_batches=self.micro_batches, stages=self.num_stages,
                                 stage_id=stage_id)

    def is_first_stage(self):
        return groups.get_pipeline_parallel_rank() == 0

    def is_last_stage(self):
        return groups.get_pipeline_parallel_rank() == self.num_stages - 1

    def set_dataiterator(self, iterator):
        self.data_iterator = iterator

    def module_state_dict(self, exclude_frozen_parameters=False):
        return super().module_state_dict(exclude_frozen_parameters)

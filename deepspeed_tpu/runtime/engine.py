"""The DeepSpeed training engine, TPU-native.

Analogue of the reference's ``deepspeed/runtime/engine.py``
(``DeepSpeedEngine`` at engine.py:180: ``forward`` 1785, ``backward``
1924, ``step`` 2123, ``save_checkpoint`` 3056, ``load_checkpoint``
2710), re-designed for XLA:

- Model state is a pytree of globally-sharded jax.Arrays over one
  ``jax.sharding.Mesh``; ZeRO stages are sharding policies
  (see ``runtime/zero/partitioning.py``), not buffer partitioning.
- ``forward`` computes loss *and* gradients in one fused
  ``value_and_grad`` dispatch (async — the host does not block);
  ``backward`` accumulates them; ``step`` runs the jitted
  unscale/clip/update/re-cast with buffer donation. This preserves the
  reference's imperative ``forward/backward/step`` surface on a purely
  functional core.
- ``train_batch`` additionally offers the fully-fused hot path: one jit
  containing a ``lax.scan`` over gradient-accumulation micro-batches
  plus the optimizer update.
- fp16 loss scaling, bf16 + fp32 master weights, gradient clipping,
  LR schedules, monitors, timers, and DeepSpeed-layout checkpoints are
  all wired as in the reference.
"""

import math
import os
import re
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.monitor.monitor import MonitorMaster
from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.ops.adagrad.cpu_adagrad import DeepSpeedCPUAdagrad
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb
from deepspeed_tpu.ops.lion.fused_lion import FusedLion
from deepspeed_tpu.ops.op_base import DeepSpeedOptimizer
from deepspeed_tpu.ops.sgd import SGD
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime import lr_schedules
from deepspeed_tpu.runtime.checkpoint_engine import ArrayCheckpointEngine, ShardedCheckpointEngine
from deepspeed_tpu.runtime.checkpoint_engine.sharded_checkpoint_engine import flatten_named, match_named_tree
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.constants import (ADAGRAD_OPTIMIZER, ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM_OPTIMIZER,
                                             LAMB_OPTIMIZER, LION_OPTIMIZER, SGD_OPTIMIZER)
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import DynamicLossScaler, has_overflow, scaler_state, update_scale
from deepspeed_tpu.runtime.zero import overlap
from deepspeed_tpu.runtime.zero.partitioning import ZeroShardingPolicy, batch_spec, path_tree_map
from deepspeed_tpu.utils import tracing
from deepspeed_tpu.utils.env_registry import env_bool, env_int, env_raw
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER,
                                       TRAIN_BATCH_TIMER, NoopTimer, SynchronizedWallClockTimer,
                                       ThroughputTimer)

MEMORY_OPT_ALLREDUCE_SIZE = 500000000

DeepSpeedOptimizerCallable = object
DeepSpeedSchedulerCallable = object


def _reduce_count(name, values):
    """A step count over its layers (and micro-batches): a name with ``_max`` in it is the
    largest, any other the sum."""
    return jnp.max(values) if "_max" in name else jnp.sum(values)


class DeepSpeedEngine:
    """DeepSpeed engine: wraps a model to expose forward/backward/step."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 mesh=None,
                 loss_fn=None,
                 dont_change_device=False):
        """The constructor is one step record of kind ``setup`` (utils/tracing.py):
        the mesh and the ZeRO rules (``ds.setup.partition``), the optimizer and
        its schedule (``ds.setup.optimizer``), monitors, checkpointing and the
        data loader (``ds.setup.services``). The state itself is made at the
        first forward or ``train_batch`` (:meth:`_materialize_state`, a ``setup``
        record of its own)."""
        self.trace_id = tracing.engine_id()  # this engine's number in the step records
        with tracing.setup(self.trace_id) as setup:
            self._construct(setup, model, optimizer, model_parameters, training_data,
                            lr_scheduler, mpu, dist_init_required, collate_fn, config,
                            config_class, mesh, loss_fn)
        self._report_config()

    def _construct(self, setup, model, optimizer, model_parameters, training_data, lr_scheduler,
                   mpu, dist_init_required, collate_fn, config, config_class, mesh, loss_fn):
        setup.phase("setup.partition")
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.loss_fn = loss_fn
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.gradient_average = True
        self.warn_unscaled_loss = True
        self.loaded_checkpoint_mp_world_size = None
        self.loaded_checkpoint_dp_world_size = None
        self.losses = None
        self._is_training = True

        if config_class is None:
            config_class = DeepSpeedConfig(config, mpu=mpu, mesh_device=mesh)
        self._config = config_class

        if dist_init_required is None or dist_init_required:
            if not dist.is_initialized():
                dist.init_distributed()

        # Mesh: explicit > config['mesh'] > all-data default
        if mesh is not None:
            groups.set_mesh(mesh)
        elif not groups.mesh_is_initialized():
            groups.initialize_mesh(self._config.mesh_shape)
        self.mesh = groups.get_mesh()
        groups.mpu = mpu

        self.module = model
        self.params = model_parameters if _is_pytree_of_arrays(model_parameters) else None
        self.master_params = None
        self.opt_state = None
        self._initialized = False
        self._param_rng = jax.random.PRNGKey(env_int("DS_SEED"))
        self._dropout_rng = jax.random.PRNGKey(env_int("DS_SEED") + 1)

        # Precision
        if self.bfloat16_enabled():
            self.compute_dtype = jnp.bfloat16
        elif self.fp16_enabled():
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32

        self._grad_accum_dtype = {
            None: jnp.float32,
            "fp32": jnp.float32,
            "fp16": jnp.float16,
            "bf16": jnp.bfloat16,
        }.get(self._config.grad_accum_dtype, jnp.float32)

        # ZeRO sharding policy
        zc = self._config.zero_config
        self.zero_stage = zc.stage
        self.sharding_policy = ZeroShardingPolicy(
            mesh=self.mesh,
            stage=zc.stage,
            tp_rule=getattr(model, "tp_rule", None),
            param_persistence_threshold=int(zc.param_persistence_threshold),
            offload_optimizer=zc.offload_optimizer_device().value != "none",
            offload_param=zc.offload_param_device().value != "none",
            mics_shard_size=max(0, int(zc.mics_shard_size)),
        )

        # overlap_comm at stage 3: a layer scan traced by the gradient core takes
        # runtime/zero/overlap.py's backward; None leaves every scan as it is
        self._layer_overlap = (overlap.LayerOverlap(self.sharding_policy)
                               if zc.stage == 3 and zc.overlap_comm else None)

        setup.phase("setup.optimizer")
        # Loss scaler (host mirror; device state lives in self.scaler_state)
        self._build_loss_scaler()

        # Optimizer object (DeepSpeed-shaped; jitted transform drives updates)
        self.optimizer = self._configure_optimizer()
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)

        setup.phase("setup.services")
        # Monitors / timers
        self.monitor = MonitorMaster(self._config.monitor_config)
        self.wall_clock_breakdown_enabled = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown_enabled else NoopTimer()
        self.tput_timer = ThroughputTimer(
            config=self._config.timers_config,
            batch_size=self.train_batch_size(),
            steps_per_output=self.steps_per_print(),
        )

        # Sharded (chunk-indexed, mesh-resizable) checkpoints by default;
        # `"checkpoint": {"sharded": false}` selects consolidated msgpack.
        if self._config.checkpoint_config.get("sharded", True):
            self.checkpoint_engine = ShardedCheckpointEngine()
        else:
            self.checkpoint_engine = ArrayCheckpointEngine()

        # Nebula async checkpoint service: snapshot-to-host + background
        # write with atomic commit ("nebula": {"enabled": true}).
        self._checkpoint_service = None
        if getattr(self._config, "nebula_config", None) is not None and self._config.nebula_config.enabled:
            from deepspeed_tpu.nebula.service import NebulaCheckpointService
            self._checkpoint_service = NebulaCheckpointService(self._config.nebula_config,
                                                               self.checkpoint_engine,
                                                               monitor=self.monitor)

        # Data loader
        self.training_dataloader = self.deepspeed_io(training_data) if training_data is not None else None

        # Preemption tolerance: a SIGTERM (TPU maintenance / elastic agent
        # forward) flips a flag; the step boundary finishes the in-flight
        # step, emergency-saves, and exits PREEMPT_RC. The heartbeat is
        # the agent-side hang watchdog's signal (no-op unless the agent
        # exported DS_HEARTBEAT_FILE).
        from deepspeed_tpu.elasticity.preemption import HeartbeatWriter, PreemptionGuard
        self._heartbeat = HeartbeatWriter()
        self._preemption_guard = None
        self._last_ckpt_dir = None  # latest save/load dir — emergency-save fallback
        if env_bool("DS_EMERGENCY_CKPT") and env_bool("DS_ELASTIC_ENABLED"):
            self._preemption_guard = PreemptionGuard().install()

        # Legacy curriculum learning: the engine truncates each batch's
        # sequence dim to the scheduled difficulty (reference engine
        # exposes curriculum_scheduler; megatron consumes curriculum_seqlen)
        self.curriculum_scheduler_legacy = None
        if getattr(self._config, "curriculum_enabled_legacy", False):
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler_legacy = CurriculumScheduler(
                self._config.curriculum_params_legacy)

        # caches for jitted callables and last-forward microbatch
        self._jit_cache = {}
        self._grads_acc = None
        self._host_offload = None  # set by _materialize_state when offloading
        self._param_swapper = None  # set when offload_param.device == nvme
        self._trainable_mask = None  # set by _materialize_state (frozen_parameters)
        self._pending = None  # (loss, grads) from the last forward
        self.global_grad_norm = 0.0
        self.overflow = False

    # ------------------------------------------------------------------
    # Config accessors (parity with reference engine surface)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def steps_per_print(self):
        return self._config.steps_per_print

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bfloat16_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def dynamic_loss_scale(self):
        return self._config.loss_scale == 0

    def initial_dynamic_scale(self):
        return self._config.initial_dynamic_scale

    def dynamic_loss_scale_args(self):
        return self._config.dynamic_loss_scale_args

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def train(self, mode=True):
        self._is_training = mode

    def eval(self):
        self._is_training = False

    def dp_world_size(self):
        return groups.get_data_parallel_world_size()

    @property
    def checkpoint_tag_validation_enabled(self):
        return self._config.checkpoint_tag_validation_enabled

    def _report_config(self):
        log_dist(
            f"DeepSpeedTPU engine: zero_stage={self.zero_stage} dtype={self.compute_dtype.__name__} "
            f"micro_batch={self.train_micro_batch_size_per_gpu()} gas={self.gradient_accumulation_steps()} "
            f"train_batch={self.train_batch_size()} mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}",
            ranks=[0])

    # ------------------------------------------------------------------
    # Optimizer / scheduler configuration (reference engine.py:1219/899)
    # ------------------------------------------------------------------
    def _configure_optimizer(self):
        if self.client_optimizer is not None:
            if isinstance(self.client_optimizer, DeepSpeedOptimizer):
                return self.client_optimizer
            if callable(self.client_optimizer):
                opt = self.client_optimizer(None)
                assert isinstance(opt, DeepSpeedOptimizer), \
                    "optimizer callable must return a deepspeed_tpu optimizer"
                return opt
            raise ValueError("Unsupported client optimizer type; pass a deepspeed_tpu.ops optimizer "
                             "or configure one via the 'optimizer' config section")
        name = self._config.optimizer_name
        params = dict(self._config.optimizer_params or {})
        params.pop("torch_adam", None)
        adam_w_mode = params.pop("adam_w_mode", None)
        if name is None:
            # default: Adam
            return FusedAdam()
        name = name.lower()
        offload = self._config.zero_config.offload_optimizer_device().value != "none"
        if name in (ADAM_OPTIMIZER, FUSED_ADAM_OPTIMIZER):
            if offload:
                return DeepSpeedCPUAdam(adamw_mode=adam_w_mode if adam_w_mode is not None else True, **params)
            return FusedAdam(adam_w_mode=adam_w_mode if adam_w_mode is not None else True, **params)
        if name == ADAMW_OPTIMIZER:
            if offload:
                return DeepSpeedCPUAdam(adamw_mode=True, **params)
            return FusedAdam(adam_w_mode=True, **params)
        if name == LAMB_OPTIMIZER:
            return FusedLamb(**params)
        if name == LION_OPTIMIZER:
            return FusedLion(**params)
        if name == ADAGRAD_OPTIMIZER:
            return DeepSpeedCPUAdagrad(**params)
        if name == SGD_OPTIMIZER:
            return SGD(**params)
        if name == "onebitadam":
            from deepspeed_tpu.ops.adam.onebit_adam import OnebitAdam
            return OnebitAdam(**params)
        if name == "zerooneadam":
            from deepspeed_tpu.ops.adam.zoadam import ZeroOneAdam
            return ZeroOneAdam(**params)
        if name == "onebitlamb":
            from deepspeed_tpu.ops.lamb.onebit_lamb import OnebitLamb
            return OnebitLamb(**params)
        raise ValueError(f"Unknown optimizer {name}")

    def _configure_lr_scheduler(self, client_lr_scheduler):
        if client_lr_scheduler is not None:
            if callable(client_lr_scheduler):
                return client_lr_scheduler(self.optimizer)
            return client_lr_scheduler
        if self._config.scheduler_name is not None:
            sched_cls = getattr(lr_schedules, self._config.scheduler_name, None)
            if sched_cls is None:
                raise ValueError(f"Unknown lr schedule {self._config.scheduler_name}")
            return sched_cls(self.optimizer, **(self._config.scheduler_params or {}))
        return None

    def _build_loss_scaler(self):
        if self.fp16_enabled():
            if self.dynamic_loss_scale():
                args = self.dynamic_loss_scale_args() or {}
                self.loss_scaler = DynamicLossScaler(init_scale=args.get("init_scale",
                                                                         self.initial_dynamic_scale()),
                                                     scale_window=args.get("scale_window", 1000),
                                                     min_scale=args.get("min_scale", 1),
                                                     delayed_shift=args.get("delayed_shift", 2),
                                                     consecutive_hysteresis=args.get("consecutive_hysteresis", False),
                                                     raise_error_at_min_scale=False)
                self.scaler_state = self.loss_scaler.device_state()
                self._scaler_kwargs = dict(scale_window=self.loss_scaler.scale_window,
                                           min_scale=self.loss_scaler.min_scale,
                                           delayed_shift=self.loss_scaler.delayed_shift,
                                           consecutive_hysteresis=self.loss_scaler.consecutive_hysteresis,
                                           dynamic=True)
            else:
                self.loss_scaler = None
                self.scaler_state = scaler_state(init_scale=self._config.loss_scale)
                self._scaler_kwargs = dict(dynamic=False)
        else:
            self.loss_scaler = None
            self.scaler_state = scaler_state(init_scale=1.0)
            self._scaler_kwargs = dict(dynamic=False)

    # ------------------------------------------------------------------
    # Parameter/optimizer state materialization
    # ------------------------------------------------------------------
    def _apply_module(self, params, *args, rngs=None, **kwargs):
        """Run the wrapped model. Supports flax modules ({'params': p}) and
        plain callables f(params, *args)."""
        if getattr(self, "_generic_param_offload", False) and getattr(
                self, "_param_offload_enabled", False):
            # generic offload_param: upload the host-resident tree to its
            # device compute layout inside the step program (XLA sinks
            # each copy to first use and frees after last use). Inside a
            # manual shard_map region (quantized/1-bit comm cores) the
            # hop already happened before the region — a mesh-sharding
            # device_put is illegal in here, so skip.
            from deepspeed_tpu.ops.pallas import current_manual_axes
            if not current_manual_axes():
                params = jax.tree.map(jax.device_put, params, self._param_device_shardings)
        if hasattr(self.module, "apply"):
            try:
                return self.module.apply({"params": params}, *args, rngs=rngs, **kwargs)
            except TypeError:
                return self.module.apply({"params": params}, *args, **kwargs)
        return self.module(params, *args, **kwargs)

    def _step_count_names(self):
        """What the model counts on the device in a training step under this
        mesh (``module.step_count_names(mesh)``; () for a model that counts
        nothing, whose step program is then what it was)."""
        names = getattr(self.module, "step_count_names", None)
        return tuple(names(self.mesh)) if callable(names) else ()

    def _apply_counting(self, params, *args, rngs=None, **kwargs):
        """:meth:`_apply_module` with the model's ``step_counts`` collection
        mutable → (the model's output, {name: the sum over the layers that
        sowed it})."""
        from deepspeed_tpu.moe.sharded_moe import STEP_COUNTS
        out, state = self.module.apply({"params": params}, *args, rngs=rngs,
                                       mutable=[STEP_COUNTS], **kwargs)
        found = {name: [] for name in self._step_count_names()}
        for path, leaf in jax.tree_util.tree_leaves_with_path(state.get(STEP_COUNTS, {})):
            found[next(k.key for k in reversed(path) if hasattr(k, "key"))].append(leaf.reshape(-1))
        return out, {name: _reduce_count(name, jnp.concatenate(leaves)) if leaves
                     else jnp.zeros((), jnp.int32) for name, leaves in found.items()}

    def _init_params(self, *fwd_args, **fwd_kwargs):
        assert hasattr(self.module, "init"), (
            "model has no .init(); pass model_parameters (a pytree of arrays) to initialize()")
        rng = self._param_rng

        def init_fn(rng):
            variables = self.module.init(rng, *fwd_args, **fwd_kwargs)
            return variables["params"]

        abstract = jax.eval_shape(init_fn, rng)
        shardings = path_tree_map(
            lambda path, x: NamedSharding(self.mesh, self.sharding_policy.param_spec(path, x.shape)), abstract)
        params = jax.jit(init_fn, out_shardings=shardings)(rng)
        return jax.tree.map(lambda x: x.astype(self.compute_dtype) if _is_float(x) else x, params)

    def _configure_param_offload(self):
        """Validate + arm ZeRO-Infinity param offload (offload_param).

        Reference semantics (``deepspeed/runtime/zero/stage3.py`` offload
        branches; ``partition_parameters.py:808`` works on any module):
        params may be offloaded only under ZeRO-3. deepspeed_tpu models
        stream per-layer slices inside their scan
        (``param_stream_prefix`` + ``config.offload_params``); any other
        flax module takes the generic path — whole tree in pinned_host,
        uploaded by the step program itself.
        """
        zc = self._config.zero_config
        device = zc.offload_param_device().value
        self._param_offload_enabled = device != "none"
        if not self._param_offload_enabled:
            return
        if self.zero_stage < 3:
            raise ValueError(
                f"zero_optimization.offload_param requires stage 3 (got stage {self.zero_stage})")
        self._param_nvme_path = None
        if device == "nvme":
            # Full ZeRO-Infinity: the scanned-layer leaves live in NVMe
            # files between steps (swap_tensor/param_swapper.py) and are
            # restored into pinned_host ahead of each dispatch, where the
            # per-layer scan streaming takes over. Reference:
            # swap_tensor/partitioned_param_swapper.py:36.
            self._param_nvme_path = self._config.zero_config.offload_param.nvme_path
            assert self._param_nvme_path, "offload_param.device=nvme requires nvme_path"
        cfg = getattr(self.module, "config", None)
        prefix = getattr(self.module, "param_stream_prefix", None)
        if cfg is not None and prefix is not None and hasattr(cfg, "offload_params"):
            # deepspeed_tpu model: the scanned blocks stream their own
            # layer slices host→HBM inside the scan (param_stream.py) —
            # O(1 layer) of params resident at a time.
            self._param_stream_prefix = prefix
            self._generic_param_offload = False
            if not cfg.offload_params:
                import dataclasses as _dc
                self.module = self.module.clone(config=_dc.replace(cfg, offload_params=True))
        else:
            # Arbitrary module (reference parity:
            # zero/partition_parameters.py:808 wraps any nn.Module): the
            # WHOLE param tree lives in pinned_host between steps and the
            # jitted step device_puts it to HBM. The copies are graph ops,
            # so XLA's latency-hiding scheduler sinks each upload to just
            # before its first use and frees it after its last — for a
            # sequential model that recovers a streaming working set
            # without knowing the module's structure.
            self._param_stream_prefix = ""
            self._generic_param_offload = True

    def destroy(self):
        """Release engine resources (reference engine.destroy): jit
        caches, accumulated grads, the NVMe param swap files, AND the
        device state (params / fp32 master / optimizer moments) — a
        destroyed engine's HBM must be reclaimable for a back-to-back
        engine build (the bench runs several ~0.5-2.5B engines in one
        process)."""
        if self._checkpoint_service is not None:
            # drain: an in-flight background checkpoint must commit (or
            # surface its failure) before the state it snapshots dies
            self._checkpoint_service.shutdown(wait=True)
        if self._preemption_guard is not None:
            self._preemption_guard.uninstall()
            self._preemption_guard = None
        self._jit_cache.clear()
        self._grads_acc = None
        self._pending = None
        self.params = None
        self.master_params = None
        self.opt_state = None
        if getattr(self, "_host_offload", None) is not None:
            self._host_offload.close()
        self._host_offload = None
        self._initialized = False
        if self._param_swapper is not None:
            self._param_swapper.close()
            self._param_swapper = None

    def _nvme_offload_params(self):
        """End-of-step half of NVMe param offload: write the streamed
        subtree's leaves to their swap files (async) and replace them
        with handles — between steps no array storage backs them."""
        if self._param_swapper is None:
            return
        from deepspeed_tpu.runtime.swap_tensor.param_swapper import NVMeParamHandle
        prefix = self._param_stream_prefix
        swapper = self._param_swapper

        def off(path, leaf):
            if path.startswith(prefix) and not isinstance(leaf, NVMeParamHandle):
                return swapper.offload(path, leaf)
            return leaf

        self.params = path_tree_map(off, self.params)

    def _ensure_params_resident(self):
        """Pre-dispatch half of NVMe param offload: stream swapped leaves
        NVMe→host→pinned_host (concurrent preads) so the jitted step's
        per-layer scan streaming finds them where the cpu-offload path
        keeps them."""
        if self._param_swapper is None:
            return
        from deepspeed_tpu.runtime.swap_tensor.param_swapper import NVMeParamHandle
        flat_params, treedef = jax.tree_util.tree_flatten_with_path(
            self.params, is_leaf=lambda x: isinstance(x, NVMeParamHandle))
        flat_shard = jax.tree.leaves(self._param_shardings)
        handles = [(leaf, flat_shard[i]) for i, (kp, leaf) in enumerate(flat_params)
                   if isinstance(leaf, NVMeParamHandle)]
        if not handles:
            return
        restored = self._param_swapper.restore(handles)
        new_leaves = [restored.get(leaf.path, leaf) if isinstance(leaf, NVMeParamHandle)
                      else leaf for kp, leaf in flat_params]
        self.params = jax.tree_util.tree_unflatten(treedef, new_leaves)

    def _enforce_param_memory_kinds(self):
        """Param-offload contract: offloaded leaves live in pinned_host
        between steps. The update writes them back in-program where the
        backend supports host-placed outputs (TPU); where it silently
        leaves them in device memory (CPU SPMD), re-place here."""
        if not getattr(self, "_param_offload_enabled", False):
            return
        self.params = jax.tree.map(
            lambda x, s: x if x.sharding.memory_kind == s.memory_kind else jax.device_put(x, s),
            self.params, self._param_shardings)

    def _materialize_state(self, *fwd_args, **fwd_kwargs):
        if self._initialized:
            return
        # a setup record of its own (program "state") inside the first step's: the
        # parameters made or taken and cast (ds.setup.params), the ZeRO shardings and the
        # placement under them (ds.setup.partition), master copy and optimizer state
        # (ds.setup.optimizer). Each phase launches device work and waits for none of it.
        with tracing.setup(self.trace_id, program="state") as setup:
            self._make_state(setup, fwd_args, fwd_kwargs)

    def _make_state(self, setup, fwd_args, fwd_kwargs):
        setup.phase("setup.params")
        self._configure_param_offload()
        if self.params is None:
            self.params = self._init_params(*fwd_args, **fwd_kwargs)
        else:
            # Re-place user-provided params with policy shardings + dtype
            shardings = self.sharding_policy.tree_param_shardings(self.params)
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(
                    x.astype(self.compute_dtype) if _is_float(x) else x, s), self.params, shardings)

        setup.phase("setup.partition")
        self._param_shardings = self.sharding_policy.tree_param_shardings(self.params)
        self._param_specs = self.sharding_policy.tree_param_specs(self.params)
        self._opt_shardings = self.sharding_policy.tree_opt_shardings(self.params)
        self._opt_specs = self.sharding_policy.tree_opt_specs(self.params)
        self._grad_specs = self.sharding_policy.tree_grad_specs(self.params)
        self._grad_shardings = self.sharding_policy.tree_grad_shardings(self.params)
        self._trainable_mask = self._build_trainable_mask()

        if self._param_offload_enabled:
            # ZeRO-Infinity param offload: the offloaded subtree (scanned
            # layers for streaming models, everything for the generic
            # path) lives in the device's pinned_host memory space.
            prefix = self._param_stream_prefix
            self._param_device_shardings = self._param_shardings
            self._param_shardings = path_tree_map(
                lambda path, s: s.with_memory_kind("pinned_host")
                if path.startswith(prefix) else s, self._param_shardings)
            self.params = jax.tree.map(jax.device_put, self.params, self._param_shardings)
            if self._param_nvme_path:
                from deepspeed_tpu.runtime.swap_tensor.param_swapper import AsyncParamSwapper
                self._param_swapper = AsyncParamSwapper(
                    self._param_nvme_path,
                    aio_threads=int(self._config.zero_config.offload_param.buffer_count or 4))

        setup.phase("setup.optimizer")
        offload_device = self._config.zero_config.offload_optimizer_device().value
        if offload_device != "none":
            # ZeRO-Offload: fp32 master + moments on host (RAM or NVMe),
            # update on host SIMD (runtime/zero/offload.py). The device
            # keeps only compute-dtype params.
            from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
            nvme_path = None
            if offload_device == "nvme":
                nvme_path = self._config.zero_config.offload_optimizer.nvme_path
                assert nvme_path, "offload_optimizer.device=nvme requires nvme_path"
            self._host_offload = HostOffloadOptimizer(
                self.optimizer, self.params, self._param_shardings, self.compute_dtype,
                nvme_path=nvme_path,
                aio_threads=int(self._config.zero_config.offload_optimizer.buffer_count or 4),
                trainable_mask=(jax.tree.leaves(self._trainable_mask)
                                if self._trainable_mask is not None else None))
            self.master_params = None
            self.opt_state = None
        else:
            self._host_offload = None
            # fp32 master copy sharded like optimizer state (ZeRO-1 partitioning)
            mixed = self.compute_dtype != jnp.float32
            if mixed or self.zero_stage >= 1:
                src = self.params
                if self._param_offload_enabled:
                    # computing on pinned_host operands is illegal inside
                    # a partitioned program — hop to HBM first (init-only)
                    src = jax.device_put(src, self._opt_shardings)
                self.master_params = jax.jit(
                    lambda p: jax.tree.map(lambda x: x.astype(jnp.float32) if _is_float(x) else x, p),
                    out_shardings=self._opt_shardings)(src)
            else:
                self.master_params = self.params

            # Optimizer state: mirror master sharding for params-shaped subtrees
            transform = self.optimizer.transform()
            self._opt_init, self._opt_update = transform.init, transform.update
            abstract_state = jax.eval_shape(self._opt_init, self.master_params)
            state_shardings = self._opt_state_shardings(abstract_state)
            self.opt_state = jax.jit(self._opt_init, out_shardings=state_shardings)(self.master_params)
            self._opt_state_shards = state_shardings

        self._commit_scaler_state()

        self._initialized = True

        # A load_checkpoint() that ran before materialization stashed the
        # optimizer/master/scaler state; apply it now.
        pending = getattr(self, "_pending_optim_state", None)
        if pending is not None:
            self._restore_optim_state(pending)
            self._pending_optim_state = None
        pending_u = getattr(self, "_pending_universal", None)
        if pending_u is not None:
            self._apply_universal(pending_u)
            self._pending_universal = None

    def _opt_state_shardings(self, abstract_state):
        params_treedef = jax.tree.structure(self.params)

        def map_entry(entry):
            if jax.tree.structure(entry) == params_treedef:
                return self._opt_shardings
            return jax.tree.map(lambda x: NamedSharding(self.mesh, P()), entry)

        if isinstance(abstract_state, dict):
            return {k: map_entry(v) for k, v in abstract_state.items()}
        return jax.tree.map(lambda x: NamedSharding(self.mesh, P()), abstract_state)

    # ------------------------------------------------------------------
    # Batch placement
    # ------------------------------------------------------------------
    def _shard_batch(self, tree, extra_leading=0):
        """Place batch arrays with batch (+sequence) sharding."""
        def place(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            nd = x.ndim - extra_leading
            spec = batch_spec(self.mesh, extra_leading=extra_leading,
                              shard_sequence=(nd >= 2))
            spec = P(*list(spec)[:x.ndim])
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree.map(place, tree)

    # ------------------------------------------------------------------
    # forward / backward / step (reference engine.py:1785/1924/2123)
    # ------------------------------------------------------------------
    def _quantized_comm_enabled(self):
        zc = self._config.zero_config
        # the nontrainable-only flag quantizes frozen-leaf gathers, so it
        # has an effect (and is worth the manual-DP region) only when a
        # frozen_parameters mask exists
        qnw_active = (zc.zero_quantized_nontrainable_weights
                      and self._config._param_dict.get("frozen_parameters"))
        if not (zc.zero_quantized_gradients or zc.zero_quantized_weights or qnw_active):
            return False
        return dict(self.mesh.shape).get("data", 1) > 1

    def _onebit_enabled(self):
        return getattr(self.optimizer, "freeze_step", None) is not None and \
            dict(self.mesh.shape).get("data", 1) > 1

    def _use_compressed_now(self):
        """Should the NEXT step use the 1-bit gradient core? Optimizers
        with a per-step schedule (0/1 Adam's variance-refresh steps use
        exact exchange) expose ``wants_compressed``; the 1-bit Adam/LAMB
        warmup follows ``freeze_step``."""
        if not self._onebit_enabled():
            return False
        opt = self.optimizer
        if hasattr(opt, "wants_compressed"):
            # key on APPLIED optimizer steps: overflow-skipped steps advance
            # global_steps but not the in-state variance machine, and the
            # host mirror must stay in lockstep with it
            return opt.wants_compressed(self.global_steps - self.skipped_steps)
        return self.global_steps >= opt.freeze_step

    def _manual_data_specs(self):
        """Shared spec derivation for manual-'data' shard_map regions
        (quantized + 1-bit gradient cores): per-leaf manual in-specs for
        params (the data-sharded dim when divisible), the matching dim
        maps, and the batch-leaf heuristic."""
        axis = "data"
        n = dict(self.mesh.shape)[axis]

        def axis_dim(spec):
            # -1 = axis absent (None would collapse the pytree)
            for d, entry in enumerate(spec):
                entries = entry if isinstance(entry, (tuple, list)) else (entry,)
                if axis in entries:
                    return d
            return -1

        # manual in/out specs require exact divisibility (GSPMD pads,
        # shard_map does not): non-divisible dims stay replicated
        divisible = lambda leaf, dim: dim if (dim >= 0 and leaf.shape[dim] % n == 0) else -1
        param_dims = jax.tree.map(axis_dim, self._param_specs,
                                  is_leaf=lambda x: isinstance(x, P))
        param_dims = jax.tree.map(divisible, self.params, param_dims)
        grad_dims = jax.tree.map(axis_dim, self._grad_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        grad_dims = jax.tree.map(divisible, self.params, grad_dims)
        manual_spec = lambda dim, ndim: P(*[axis if d == dim else None for d in range(ndim)])
        to_specs = lambda dims: jax.tree.map(
            lambda leaf, dim: manual_spec(dim, leaf.ndim) if dim >= 0 else P(),
            self.params, dims)
        # Only true batch leaves (leading dim == the micro-batch size) are
        # split over 'data' in manual mode; anything else (position ids,
        # shared masks, scalars) stays replicated — splitting a non-batch
        # input would silently change the loss.
        mb = self.train_micro_batch_size_per_gpu()
        batch_spec_of = lambda leaf: P(axis) if (
            getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == mb and mb % n == 0) else P()
        return axis, n, param_dims, grad_dims, to_specs, batch_spec_of

    def _onebit_core(self):
        """Compressed-stage gradient core for 1-bit Adam: per-shard grads
        exchanged as sign bits + scale with persistent error feedback
        (reference onebit/adam.py compressed stage over
        comm/nccl.py:compressed_allreduce)."""
        from deepspeed_tpu.ops.pallas import manual_axes
        from deepspeed_tpu.runtime.comm.onebit import onebit_allreduce
        gas = self.gradient_accumulation_steps()

        def loss_of(params, scale, rng, args, kwargs):
            out = self._apply_module(params, *args, rngs={"dropout": rng}, **kwargs)
            loss = out[0] if isinstance(out, (tuple, list)) else out
            return (loss.astype(jnp.float32) * scale) / gas, loss

        axis, n, param_dims, _, to_specs, batch_spec_of = self._manual_data_specs()
        param_in_specs = to_specs(param_dims)
        efb_specs = jax.tree.map(lambda leaf: P(axis), self.params)

        def body(params, scale, rng, args, kwargs, efb):
            with manual_axes({axis}):
                def gather(leaf, dim):
                    if dim < 0:
                        return leaf
                    return jax.lax.all_gather(leaf, axis, axis=dim, tiled=True)

                full = jax.tree.map(gather, params, param_dims)
                (_, loss), grads = jax.value_and_grad(loss_of, has_aux=True)(
                    full, scale, rng, args, kwargs)

                def red(g, e):
                    # compress in the UNSCALED domain: the efb residual
                    # persists across steps, and a dynamic loss-scale
                    # change between steps would otherwise mis-weight it
                    gu = g.astype(jnp.float32) / scale
                    mean, e_new = onebit_allreduce(gu, axis, e[0])
                    return (mean * scale).astype(g.dtype), e_new[None].astype(e.dtype)

                pairs = jax.tree.map(red, grads, efb)
                treedef = jax.tree.structure(grads)
                leaves = treedef.flatten_up_to(pairs)
                grads = treedef.unflatten([x[0] for x in leaves])
                efb_new = treedef.unflatten([x[1] for x in leaves])
                loss = jax.lax.pmean(loss, axis)
            return loss, grads, efb_new

        def core(params, scale, rng, args, kwargs, efb):
            params = self._hop_offloaded_to_device(params)
            mapped = shard_map(
                body, mesh=self.mesh,
                in_specs=(param_in_specs, P(), P(),
                          jax.tree.map(batch_spec_of, args),
                          jax.tree.map(batch_spec_of, kwargs),
                          efb_specs),
                out_specs=(P(), jax.tree.map(lambda _: P(), self.params), efb_specs),
                axis_names={axis}, check_vma=False)
            return mapped(params, scale, rng, args, kwargs, efb)

        return core

    def _hop_offloaded_to_device(self, params):
        """offload_param × manual shard_map comm cores: pinned_host
        operands are illegal inside a manual region, so the step hops the
        host-resident tree to its device layout BEFORE entering shard_map
        (reference stage3 composes offload with the quantized collectives
        the same way — gather from host, then exchange). Outside the
        offload configs this is a no-op."""
        if not getattr(self, "_param_offload_enabled", False):
            return params
        return jax.tree.map(jax.device_put, params, self._param_device_shardings)

    def _init_onebit_efb(self):
        n = dict(self.mesh.shape)["data"]
        return jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((n,) + p.shape, jnp.float32),
                NamedSharding(self.mesh, P("data"))), self.params)

    def _loss_and_grads_core(self):
        """:meth:`_vag_core` for the paths that write no counts on a step
        record (forward/backward by hand, the offloaded update): the loss
        alone beside the gradients."""
        core = self._vag_core()
        if not self._step_count_names():
            return core

        def loss_only(*args):
            (loss, _), grads = core(*args)
            return loss, grads
        return loss_only

    def _vag_core(self):
        """(params, scale, rng, args, kwargs) -> (loss, raw_grads); for a
        model that counts its steps (:meth:`_step_count_names`), ``loss`` is
        ``(loss, {name: count})``.

        Default: one auto-sharded value_and_grad — GSPMD inserts the DP
        grad reduction. With ZeRO++ flags (zero_quantized_gradients /
        zero_quantized_weights), the 'data' axis runs MANUALLY instead:
        params are all-gathered (int8 when qwZ, two-hop when hpZ),
        per-shard grads are reduced with the int8 all-to-all
        reduce-scatter (qgZ) — reference coalesced_collectives.py:31 —
        while TP/SP/EP axes stay under GSPMD inside the region."""
        gas = self.gradient_accumulation_steps()
        counting = bool(self._step_count_names())

        def loss_of(params, scale, rng, args, kwargs):
            if counting:
                out, counts = self._apply_counting(params, *args, rngs={"dropout": rng}, **kwargs)
            else:
                out = self._apply_module(params, *args, rngs={"dropout": rng}, **kwargs)
            loss = out[0] if isinstance(out, (tuple, list)) else out
            scaled = (loss.astype(jnp.float32) * scale) / gas
            return scaled, ((loss, counts) if counting else loss)

        if not self._quantized_comm_enabled():
            def core(params, scale, rng, args, kwargs):
                # counting: ``loss`` is (loss, {name: count}), and the fused step
                # (_train_batch_fn) takes the pair apart
                with overlap.overlapping(self._layer_overlap):
                    (_, loss), grads = jax.value_and_grad(loss_of, has_aux=True)(
                        params, scale, rng, args, kwargs)
                return loss, grads
            return core
        if counting:
            raise NotImplementedError("a model that counts its steps (an expert exchange) "
                                      "under quantized ZeRO++ communication")

        from deepspeed_tpu.ops.pallas import manual_axes
        from deepspeed_tpu.runtime.comm.compressed import (quant_all_gather, quant_all_reduce,
                                                           quant_reduce_scatter)
        zc = self._config.zero_config
        qg = zc.zero_quantized_gradients
        qw = zc.zero_quantized_weights
        # nontrainable-only variant: quantize the gather of FROZEN leaves
        # (reference semantics — trainable weights stay full precision)
        qnw = zc.zero_quantized_nontrainable_weights
        if qnw and not qw and self._trainable_mask is None:
            logger.warning("zero_quantized_nontrainable_weights set but no "
                           "frozen_parameters configured — nothing to quantize")
        trainable = (self._trainable_mask if self._trainable_mask is not None
                     else jax.tree.map(lambda _: True, self.params))
        hpz = int(getattr(zc, "zero_hpz_partition_size", 1) or 1)
        axis, n, param_dims, grad_dims, to_specs, batch_spec_of = self._manual_data_specs()
        param_in_specs = to_specs(param_dims)
        grad_out_specs = to_specs(grad_dims)

        def body(params, scale, rng, args, kwargs):
            with manual_axes({axis}):
                # step- and leaf-varying quantization seeds: a constant
                # seed would repeat the same stochastic-rounding pattern
                # every step, turning zero-mean noise into a fixed bias
                seed_base = jax.random.randint(jax.random.fold_in(rng, 0x5eed), (),
                                               0, jnp.iinfo(jnp.int32).max)

                trainable_leaves = jax.tree.structure(params).flatten_up_to(trainable)

                def gather(i, leaf, dim):
                    if dim < 0:
                        return leaf
                    if qw or (qnw and not trainable_leaves[i]):
                        return quant_all_gather(leaf, axis, gather_dim=dim,
                                                hpz_size=hpz, dtype=leaf.dtype,
                                                seed=seed_base + 2 * i)
                    return jax.lax.all_gather(leaf, axis, axis=dim, tiled=True)

                full = _tree_map_indexed(gather, params, param_dims)
                (_, loss), grads = jax.value_and_grad(loss_of, has_aux=True)(
                    full, scale, rng, args, kwargs)

                def reduce(i, g, dim):
                    # fp32 for the exact collectives: bf16 psum/psum_scatter
                    # aborts XLA's CPU backend inside manual shard_map
                    seed = seed_base + 2 * i + 1
                    g32 = g.astype(jnp.float32)
                    if dim >= 0:
                        if qg:
                            return quant_reduce_scatter(g, axis, scatter_dim=dim, seed=seed) / n
                        return (jax.lax.psum_scatter(g32, axis, scatter_dimension=dim,
                                                     tiled=True) / n).astype(g.dtype)
                    if qg:
                        return quant_all_reduce(g, axis, seed=seed) / n
                    return (jax.lax.psum(g32, axis) / n).astype(g.dtype)

                grads = _tree_map_indexed(reduce, grads, grad_dims)
                loss = jax.lax.pmean(loss, axis)
            return loss, grads

        def core(params, scale, rng, args, kwargs):
            params = self._hop_offloaded_to_device(params)
            mapped = shard_map(
                body, mesh=self.mesh,
                in_specs=(param_in_specs, P(), P(),
                          jax.tree.map(batch_spec_of, args),
                          jax.tree.map(batch_spec_of, kwargs)),
                out_specs=(P(), grad_out_specs),
                axis_names={axis}, check_vma=False)
            return mapped(params, scale, rng, args, kwargs)

        return core

    def _value_and_grad_onebit_fn(self):
        key = "vag_onebit"
        if key in self._jit_cache:
            return self._jit_cache[key]
        acc_dtype = self._grad_accum_dtype
        grad_specs = self._grad_specs
        core = self._onebit_core()

        def fn(params, scale, rng, args, kwargs, efb):
            loss, grads, efb_new = core(params, scale, rng, args, kwargs, efb)
            grads = jax.tree.map(
                lambda g, spec: jax.lax.with_sharding_constraint(
                    g.astype(acc_dtype), NamedSharding(self.mesh, spec)), grads, grad_specs)
            return loss, grads, efb_new

        self._jit_cache[key] = jax.jit(fn, donate_argnums=(5,))
        return self._jit_cache[key]

    def _value_and_grad_fn(self):
        key = "vag"
        if key in self._jit_cache:
            return self._jit_cache[key]
        acc_dtype = self._grad_accum_dtype
        grad_specs = self._grad_specs
        core = self._loss_and_grads_core()

        def fn(params, scale, rng, args, kwargs):
            loss, grads = core(params, scale, rng, args, kwargs)
            grads = jax.tree.map(
                lambda g, spec: jax.lax.with_sharding_constraint(g.astype(acc_dtype), NamedSharding(self.mesh, spec)),
                grads, grad_specs)
            return loss, grads

        jitted = jax.jit(fn, static_argnames=())
        self._jit_cache[key] = jitted
        return jitted

    def _maybe_flops_profile(self, args, kwargs):
        """Print the flops profile once, at flops_profiler.profile_step
        (reference profiler hooks in engine forward; here one jaxpr walk)."""
        fc = self._config.flops_profiler_config
        if not fc.enabled or getattr(self, "_flops_profiled", False):
            return
        if self.global_steps + 1 < fc.profile_step:
            return
        self._flops_profiled = True
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
        prof = FlopsProfiler(model=self.module, ds_engine=self)
        rng = jax.random.PRNGKey(0)

        def fwd(params):
            out = self._apply_module(params, *args, rngs={"dropout": rng}, **kwargs)
            return out[0] if isinstance(out, (tuple, list)) else out

        prof.profile(fwd, self.params, time_it=False)
        prof.total_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self.params))
        prof.print_model_profile(profile_step=fc.profile_step, module_depth=fc.module_depth,
                                 top_modules=fc.top_modules, detailed=fc.detailed,
                                 output_file=fc.output_file)

    def forward(self, *args, **kwargs):
        """Compute loss (and, when training, gradients in the same fused
        dispatch). Returns the unscaled loss."""
        self._materialize_state(*args, **kwargs)
        self._ensure_params_resident()
        args = self._shard_batch(args)
        kwargs = self._shard_batch(kwargs)
        if self._is_training:
            self._maybe_flops_profile(args, kwargs)
        if not self._is_training:
            if "eval" not in self._jit_cache:
                self._jit_cache["eval"] = jax.jit(lambda p, a, k: self._apply_module(p, *a, **k))
            return self._jit_cache["eval"](self.params, args, kwargs)

        self.timers(FORWARD_GLOBAL_TIMER).start()
        self._dropout_rng, sub = jax.random.split(self._dropout_rng)
        scale = self.scaler_state["cur_scale"]
        if self._use_compressed_now():
            # compressed stage: 1-bit grad exchange with error feedback
            if getattr(self, "_onebit_efb", None) is None:
                self._onebit_efb = self._init_onebit_efb()
            loss, grads, self._onebit_efb = self._value_and_grad_onebit_fn()(
                self.params, scale, sub, args, kwargs, self._onebit_efb)
        else:
            loss, grads = self._value_and_grad_fn()(self.params, scale, sub, args, kwargs)
        self._pending = (loss, grads)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    def backward(self, loss=None, retain_graph=False, scale_wrt_gas=True):
        """Accumulate the gradients computed by the matching forward()."""
        assert self._pending is not None, "backward() called without a prior forward()"
        _, grads = self._pending
        self._pending = None
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        if self._grads_acc is None:
            self._grads_acc = grads
        else:
            key = "acc"
            if key not in self._jit_cache:
                self._jit_cache[key] = jax.jit(
                    lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=(0,))
            self._grads_acc = self._jit_cache[key](self._grads_acc, grads)
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps % self.gradient_accumulation_steps()) == 0

    def zero_grad(self):
        self._grads_acc = None

    def allreduce_gradients(self, bucket_size=MEMORY_OPT_ALLREDUCE_SIZE):
        # Gradient reduction is fused into the sharded update by XLA.
        pass

    def _build_trainable_mask(self):
        """Static per-leaf bools from the `frozen_parameters` config list
        (regex over leaf paths) — the analogue of requires_grad=False
        (reference stage3 frozen-param handling). None = all trainable."""
        patterns = self._config._param_dict.get("frozen_parameters", [])
        if not patterns:
            return None
        compiled = [re.compile(p) for p in patterns]
        return path_tree_map(
            lambda path, x: not any(c.search(path) for c in compiled), self.params)

    def _apply_trainable_mask(self, new_tree, old_tree):
        """Keep frozen leaves at their old values (static select: no
        runtime cost for the trainable ones)."""
        if self._trainable_mask is None:
            return new_tree
        params_treedef = jax.tree.structure(self.params)

        def mask_like(new, old):
            if jax.tree.structure(new) == params_treedef:
                return jax.tree.map(lambda keep, n, o: n if keep else o,
                                    self._trainable_mask, new, old)
            return new

        if isinstance(new_tree, dict) and jax.tree.structure(new_tree) != params_treedef:
            return {k: mask_like(v, old_tree[k]) for k, v in new_tree.items()}
        return mask_like(new_tree, old_tree)

    def _update_math(self, params, master, opt_state, grads, scaler_st, lr):
        """Shared traced update body: unscale, overflow check, clip,
        optimizer update, skip-on-overflow select, compute-dtype re-cast,
        loss-scale update. ``grads`` still carry the loss scale."""
        clip = float(self.gradient_clipping() or 0.0)
        fp16 = self.fp16_enabled()
        scale = scaler_st["cur_scale"]
        grads32 = jax.tree.map(lambda g: g.astype(jnp.float32) / scale, grads)
        if self._trainable_mask is not None:
            # requires_grad=False semantics: frozen leaves contribute
            # nothing to the grad norm, clipping, or overflow detection
            grads32 = jax.tree.map(
                lambda keep, g: g if keep else jnp.zeros_like(g),
                self._trainable_mask, grads32)
        overflow = has_overflow(grads32) if fp16 else jnp.zeros((), bool)

        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads32)))
        if clip > 0.0:
            factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads32 = jax.tree.map(lambda g: g * factor, grads32)

        new_master, new_opt = self._opt_update(grads32, opt_state, master, lr)
        new_master = self._apply_trainable_mask(new_master, master)
        new_opt = self._apply_trainable_mask(new_opt, opt_state)

        # skip the update on overflow
        def sel(new, old):
            return jax.tree.map(lambda n, o: jnp.where(overflow, o, n), new, old)

        new_master = sel(new_master, master)
        new_opt = sel(new_opt, opt_state)
        if getattr(self, "_param_offload_enabled", False):
            # device_put (not a constraint): offloaded leaves must land
            # back in pinned_host so the next step streams them again
            new_params = jax.tree.map(
                lambda m, s: jax.device_put(
                    m.astype(self.compute_dtype) if _is_float(m) else m, s),
                new_master, self._param_shardings)
        else:
            new_params = jax.tree.map(
                lambda m, spec: jax.lax.with_sharding_constraint(
                    m.astype(self.compute_dtype) if _is_float(m) else m, NamedSharding(self.mesh, spec)),
                new_master, self._param_specs)
        new_scaler = update_scale(scaler_st, overflow, **dict(self._scaler_kwargs))
        return new_params, new_master, new_opt, new_scaler, gnorm, overflow

    def _unscale_clip_math(self, grads, scaler_st):
        """Device half of the offload step: unscale, overflow check, clip.
        The optimizer update itself runs on host SIMD."""
        clip = float(self.gradient_clipping() or 0.0)
        scale = scaler_st["cur_scale"]
        grads32 = jax.tree.map(lambda g: g.astype(jnp.float32) / scale, grads)
        overflow = has_overflow(grads32) if self.fp16_enabled() else jnp.zeros((), bool)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads32)))
        if clip > 0.0:
            factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads32 = jax.tree.map(lambda g: g * factor, grads32)
        return grads32, gnorm, overflow

    def _offload_prep_fn(self):
        key = "offload_prep"
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(self._unscale_clip_math, donate_argnums=(0,))
        return self._jit_cache[key]

    def _offload_apply(self, grads32, gnorm, overflow):
        """Host half of the offload step + shared bookkeeping."""
        self.overflow = bool(overflow) if self.fp16_enabled() else False
        if not self.overflow:
            self.params = self._host_offload.step(grads32, prev_params=self.params)
        self.scaler_state = update_scale(self.scaler_state, overflow, **dict(self._scaler_kwargs))
        self.global_grad_norm = float(gnorm)

    def _apply_update_fn(self):
        key = "apply"
        if key in self._jit_cache:
            return self._jit_cache[key]
        tied = self.master_params is self.params
        body = self._update_math

        if tied:
            # master IS params: a single donated buffer (donating it at two
            # argument positions would be a deleted-array error).
            def fn(params, opt_state, grads, scaler_st, lr):
                new_params, _, new_opt, new_scaler, gnorm, overflow = body(
                    params, params, opt_state, grads, scaler_st, lr)
                return new_params, new_opt, new_scaler, gnorm, overflow

            jitted = jax.jit(fn, donate_argnums=(0, 1, 2, 3))
        else:
            # pinned_host param buffers can't alias device outputs — skip
            # donating params under param offload
            donate = (1, 2, 3, 4) if self._param_offload_enabled else (0, 1, 2, 3, 4)
            jitted = jax.jit(body, donate_argnums=donate)
        self._jit_cache[key] = (jitted, tied)
        return self._jit_cache[key]

    def step(self, lr_kwargs=None):
        """Optimizer step at gradient-accumulation boundaries."""
        assert self._grads_acc is not None, "step() called with no accumulated gradients"
        if not self.is_gradient_accumulation_boundary():
            return
        self.timers(STEP_GLOBAL_TIMER).start()
        if self._host_offload is not None:
            grads32, gnorm, overflow = self._offload_prep_fn()(self._grads_acc, self.scaler_state)
            self._offload_apply(grads32, gnorm, overflow)
        else:
            self._ensure_params_resident()
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            fn, tied = self._apply_update_fn()
            if tied:
                out = fn(self.params, self.opt_state, self._grads_acc, self.scaler_state, lr)
                self.params, self.opt_state, self.scaler_state, gnorm, overflow = out
                self.master_params = self.params
            else:
                out = fn(self.params, self.master_params, self.opt_state, self._grads_acc, self.scaler_state, lr)
                self.params, self.master_params, self.opt_state, self.scaler_state, gnorm, overflow = out
            self._enforce_param_memory_kinds()
            self.overflow = bool(overflow) if self.fp16_enabled() else False
            self.global_grad_norm = float(gnorm)
        self._nvme_offload_params()
        self._grads_acc = None
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        if self.overflow:
            self.skipped_steps += 1
            log_dist(f"[deepspeed_tpu] OVERFLOW! Skipping step; loss scale -> "
                     f"{float(self.scaler_state['cur_scale'])}", ranks=[0])
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step(**(lr_kwargs or {}))
        self.timers(STEP_GLOBAL_TIMER).stop()
        self._write_monitor()
        if self.wall_clock_breakdown_enabled and self.global_steps % self.steps_per_print() == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER])
        self._heartbeat.beat(self.global_steps)
        self._maybe_handle_preemption()

    # ------------------------------------------------------------------
    # Fused train_batch hot path
    # ------------------------------------------------------------------
    def _train_batch_fn(self):
        key = "train_batch"
        if key in self._jit_cache:
            return self._jit_cache[key]
        gas = self.gradient_accumulation_steps()
        acc_dtype = self._grad_accum_dtype
        grad_specs = self._grad_specs
        mesh = self.mesh

        core = self._vag_core()
        tied = self.master_params is self.params
        counting = bool(self._step_count_names())

        def body(params, master, opt_state, scaler_st, lr, rng, batches):
            scale = scaler_st["cur_scale"]

            def micro(carry, batch_rng):
                acc = carry
                batch, r = batch_rng
                args, kwargs = batch
                loss, grads = core(params, scale, r, args, kwargs)
                grads = jax.tree.map(
                    lambda g, spec: jax.lax.with_sharding_constraint(
                        g.astype(acc_dtype), NamedSharding(mesh, spec)), grads, grad_specs)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss

            zeros = jax.tree.map(
                lambda p, spec: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, acc_dtype), NamedSharding(mesh, spec)), params, grad_specs)
            rngs = jax.random.split(rng, gas)
            acc, losses = jax.lax.scan(micro, zeros, (batches, rngs))

            new_params, new_master, new_opt, new_scaler, gnorm, overflow = self._update_math(
                params, master, opt_state, acc, scaler_st, lr)
            if counting:
                # the model's counts ride the gradient norm: one vector, read at its sync
                losses, counts = losses
                gnorm = (gnorm, jnp.stack([_reduce_count(n, counts[n])
                                           for n in self._step_count_names()]))
            return new_params, new_master, new_opt, new_scaler, losses.mean(), gnorm, overflow

        if tied:
            # single donated buffer when master IS params (fp32 stage 0)
            def fn(params, opt_state, scaler_st, lr, rng, batches):
                new_params, _, new_opt, new_scaler, mloss, gnorm, overflow = body(
                    params, params, opt_state, scaler_st, lr, rng, batches)
                return new_params, new_opt, new_scaler, mloss, gnorm, overflow

            jitted = jax.jit(fn, donate_argnums=(0, 1, 2))
        else:
            donate = (1, 2, 3) if self._param_offload_enabled else (0, 1, 2, 3)
            jitted = jax.jit(body, donate_argnums=donate)
        self._jit_cache[key] = (jitted, tied)
        return self._jit_cache[key]

    def _train_batch_grads_fn(self):
        """Offload variant of the fused step: scan over micro-batches and
        return clipped fp32 grads for the host-side optimizer update."""
        key = "train_batch_grads"
        if key in self._jit_cache:
            return self._jit_cache[key]
        gas = self.gradient_accumulation_steps()
        acc_dtype = self._grad_accum_dtype
        grad_specs = self._grad_specs
        mesh = self.mesh

        core = self._loss_and_grads_core()

        def fn(params, scaler_st, rng, batches):
            scale = scaler_st["cur_scale"]

            def micro(carry, batch_rng):
                acc = carry
                batch, r = batch_rng
                args, kwargs = batch
                loss, grads = core(params, scale, r, args, kwargs)
                grads = jax.tree.map(
                    lambda g, spec: jax.lax.with_sharding_constraint(
                        g.astype(acc_dtype), NamedSharding(mesh, spec)), grads, grad_specs)
                return jax.tree.map(jnp.add, acc, grads), loss

            zeros = jax.tree.map(
                lambda p, spec: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, acc_dtype), NamedSharding(mesh, spec)), params, grad_specs)
            rngs = jax.random.split(rng, gas)
            acc, losses = jax.lax.scan(micro, zeros, (batches, rngs))
            grads32, gnorm, overflow = self._unscale_clip_math(acc, scaler_st)
            return grads32, losses.mean(), gnorm, overflow

        self._jit_cache[key] = jax.jit(fn)
        return self._jit_cache[key]

    def train_batch(self, data_iter=None, batch=None):
        """Run one full training step (gas micro-batches + update) as a
        single jitted program (reference PipelineEngine.train_batch:326
        surface, here for the data-parallel engine)."""
        gas = self.gradient_accumulation_steps()
        with tracing.step("train", engine=self.trace_id, program="train_batch", k=gas,
                          n_seqs=self.train_batch_size()) as rec:
            with tracing.phase("train.prepare"):
                if batch is None:
                    assert data_iter is not None, "provide data_iter or batch"
                    micro = [next(data_iter) for _ in range(gas)]
                    batch = jax.tree.map(lambda *xs: np.stack(xs), *micro)
                else:
                    lead = jax.tree.leaves(batch)[0].shape[0]
                    if lead != gas:
                        assert lead == gas * self.train_micro_batch_size_per_gpu(), (
                            f"batch leading dim {lead} != gas*micro")
                        batch = jax.tree.map(
                            lambda x: x.reshape((gas, self.train_micro_batch_size_per_gpu()) + x.shape[1:]), batch)
                if not (isinstance(batch, tuple) and len(batch) == 2 and isinstance(batch[1], dict)):
                    batch = ((batch,) if not isinstance(batch, (tuple, list)) else tuple(batch), {})
                if self.curriculum_scheduler_legacy is not None:
                    seqlen = self.curriculum_scheduler_legacy.update_difficulty(self.global_steps + 1)
                    # truncate only integer [gas, mbs, S] token-id/label leaves;
                    # float features, attention masks [.., S, S], images pass
                    # through — models with such inputs consume the scheduler
                    # directly (engine.curriculum_scheduler_legacy)
                    trunc = lambda x: x[:, :, :seqlen] if (
                        getattr(x, "ndim", 0) == 3 and
                        jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer)) else x
                    batch = (tuple(jax.tree.map(trunc, a) for a in batch[0]),
                             jax.tree.map(trunc, batch[1]))
                self._materialize_state(*jax.tree.map(lambda x: x[0], batch[0]),
                                        **jax.tree.map(lambda x: x[0], batch[1]))
                self._ensure_params_resident()
                batch = self._shard_batch(batch, extra_leading=1)
                self._maybe_flops_profile(jax.tree.map(lambda x: x[0], batch[0]),
                                          jax.tree.map(lambda x: x[0], batch[1]))
                lead = jax.tree.leaves(batch)[0]   # [gas, rows, tokens, ...]
                rec.n_tokens = math.prod(lead.shape[:3])
            with tracing.phase("train.timer_sync"):
                self.tput_timer.start()
                self.timers(TRAIN_BATCH_TIMER).start()
            self._dropout_rng, sub = jax.random.split(self._dropout_rng)
            if self._use_compressed_now():
                # compressed stage threads error feedback through each micro
                # step: run the unfused forward/backward loop + one step()
                with tracing.phase("train.dispatch"):
                    micro_losses = []
                    for g in range(gas):
                        micro = jax.tree.map(lambda x: x[g], batch)
                        loss = self.forward(*micro[0], **micro[1])
                        self.backward(loss)
                        micro_losses.append(loss)
                    self.step()
                    mean_loss = jnp.mean(jnp.stack([jnp.asarray(l) for l in micro_losses]))
                self.losses = mean_loss
                with tracing.phase("train.timer_sync"):
                    self.timers(TRAIN_BATCH_TIMER).stop()
                    self.tput_timer.stop(global_step=True)
                with tracing.phase("train.post"):
                    self._write_monitor(loss=mean_loss)
                return mean_loss
            with tracing.phase("train.dispatch"):
                if self._host_offload is not None:
                    grads32, mean_loss, gnorm, overflow = self._train_batch_grads_fn()(
                        self.params, self.scaler_state, sub, batch)
                    self._offload_apply(grads32, gnorm, overflow)
                else:
                    lr = jnp.asarray(self.get_lr()[0], jnp.float32)
                    fn, tied = self._train_batch_fn()
                    if tied:
                        out = fn(self.params, self.opt_state, self.scaler_state, lr, sub, batch)
                        self.params, self.opt_state, self.scaler_state, mean_loss, gnorm, overflow = out
                        self.master_params = self.params
                    else:
                        out = fn(self.params, self.master_params, self.opt_state, self.scaler_state, lr, sub, batch)
                        self.params, self.master_params, self.opt_state, self.scaler_state, mean_loss, gnorm, overflow = out
                    self._enforce_param_memory_kinds()
                self._nvme_offload_params()
                if self._layer_overlap is not None:  # known once the program is traced
                    rec.n_layers_prefetched = gas * self._layer_overlap.n_layers_prefetched
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += self.train_batch_size()
            with tracing.phase("train.sync"):
                self.overflow = bool(overflow) if self.fp16_enabled() else False
                if isinstance(gnorm, tuple):
                    gnorm, counts = gnorm
                    rec.counts = dict(zip(self._step_count_names(),
                                          (int(c) for c in np.asarray(counts))))
                self.global_grad_norm = float(gnorm)
            if not self.overflow and self.lr_scheduler is not None:
                self.lr_scheduler.step()
            elif self.overflow:
                self.skipped_steps += 1
            with tracing.phase("train.timer_sync"):
                self.timers(TRAIN_BATCH_TIMER).stop()
                self.tput_timer.stop(global_step=True)
            with tracing.phase("train.post"):
                self.losses = mean_loss
                self._write_monitor(loss=mean_loss)
                self._heartbeat.beat(self.global_steps)
                self._maybe_handle_preemption()
            return mean_loss

    # ------------------------------------------------------------------
    # Preemption (checked between steps; never inside a signal handler)
    # ------------------------------------------------------------------
    def _maybe_handle_preemption(self):
        """Step-boundary preemption check: emergency-save, write the
        resume marker, and exit :data:`PREEMPT_RC` so the elastic agent
        relaunches outside the failure budget. A failed save still exits
        — the grace budget is real and the last periodic checkpoint plus
        its resume validation already cover the no-save case."""
        guard = self._preemption_guard
        if guard is None or not guard.preempted:
            return
        from deepspeed_tpu.elasticity.preemption import PREEMPT_RC, write_resume_marker
        tag = f"preempt-{self.global_steps}"
        deadline = guard.deadline_remaining()
        save_dir = self._resolve_emergency_dir()
        elapsed = None
        if save_dir is None:
            logger.error("[preempt] no checkpoint directory known (no nebula "
                         "persistent_storage_path and no prior save) — exiting "
                         "without an emergency checkpoint")
        else:
            try:
                t0 = time.perf_counter()
                self.save_checkpoint(save_dir, tag=tag, async_save=False,
                                     _emergency_deadline_s=deadline)
                elapsed = time.perf_counter() - t0
                write_resume_marker(save_dir, tag, self.global_steps)
                logger.warning(f"[preempt] emergency checkpoint '{tag}' committed "
                               f"in {elapsed:.2f}s; exiting rc={PREEMPT_RC}")
            except BaseException as e:
                logger.error(f"[preempt] emergency checkpoint failed "
                             f"({type(e).__name__}: {e}); exiting anyway — resume "
                             f"falls back to the last periodic checkpoint")
        if self.monitor.enabled:
            events = [("Train/Elastic/preempt_step", self.global_steps, self.global_steps)]
            if elapsed is not None:
                events.append(("Train/Elastic/emergency_save_s", float(elapsed), self.global_steps))
            try:
                self.monitor.write_events(events)
            except Exception:
                pass
        raise SystemExit(PREEMPT_RC)

    def _resolve_emergency_dir(self):
        ncfg = getattr(self._config, "nebula_config", None)
        if ncfg is not None and ncfg.enabled and ncfg.persistent_storage_path:
            return ncfg.persistent_storage_path
        return self._last_ckpt_dir

    def _write_monitor(self, loss=None):
        if self.monitor.enabled and self.global_steps % self.steps_per_print() == 0:
            events = [("Train/Samples/lr", self.get_lr()[0], self.global_samples)]
            if loss is not None:
                events.append(("Train/Samples/train_loss", float(loss), self.global_samples))
            if self.fp16_enabled():
                events.append(("Train/Samples/loss_scale", float(self.scaler_state["cur_scale"]),
                               self.global_samples))
            self.monitor.write_events(events)

    # ------------------------------------------------------------------
    # LR / loss-scale accessors
    # ------------------------------------------------------------------
    def get_lr(self):
        return [g["lr"] for g in self.optimizer.param_groups]

    def get_type(self):
        return type(self.optimizer).__name__

    def get_mom(self):
        return [g.get("betas", (0.0, 0.0))[0] for g in self.optimizer.param_groups]

    def get_loss_scale(self):
        return float(self.scaler_state["cur_scale"])

    @property
    def cur_scale(self):
        return self.get_loss_scale()

    def get_global_grad_norm(self):
        return self.global_grad_norm

    # ------------------------------------------------------------------
    # Data loading (reference engine.py:1690)
    # ------------------------------------------------------------------
    def deepspeed_io(self,
                     dataset,
                     batch_size=None,
                     route="train",
                     pin_memory=True,
                     data_sampler=None,
                     collate_fn=None,
                     num_local_io_workers=None):
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu()
        return DeepSpeedDataLoader(dataset=dataset,
                                   batch_size=batch_size,
                                   collate_fn=collate_fn or self.collate_fn,
                                   data_parallel_world_size=1,  # one process addresses the full mesh
                                   data_parallel_rank=0,
                                   data_sampler=data_sampler)

    # ------------------------------------------------------------------
    # Checkpointing (reference engine.py:3056/2710)
    # ------------------------------------------------------------------
    def _get_ckpt_name(self, checkpoints_path, tag, mp_placeholder=None):
        if mp_placeholder is not None:
            mp_rank_str = mp_placeholder
        else:
            mp_rank_str = f"{groups.get_model_parallel_rank():02d}"
        return os.path.join(checkpoints_path, str(tag), f"mp_rank_{mp_rank_str}_model_states.pt")

    def _get_optimizer_ckpt_name(self, checkpoints_path, tag, dp_rank=None):
        dp_rank = dp_rank if dp_rank is not None else dist.get_rank()
        mp = groups.get_model_parallel_rank()
        return os.path.join(checkpoints_path, str(tag),
                            f"zero_pp_rank_{dp_rank}_mp_rank_{mp:02d}_optim_states.pt")

    def _get_optimizer_ckpt_name_sharded(self, checkpoints_path, tag):
        # canonical rank-0 name: the chunk store spans all dp/mp ranks
        return os.path.join(checkpoints_path, str(tag),
                            "zero_pp_rank_0_mp_rank_00_optim_states.pt")

    def save_checkpoint(self,
                        save_dir=None,
                        tag=None,
                        client_state={},
                        save_latest=True,
                        exclude_frozen_parameters=False,
                        async_save=None,
                        _emergency_deadline_s=None):
        assert self._initialized, "cannot save before the first forward/train_batch"
        emergency = _emergency_deadline_s is not None
        nebula = self._checkpoint_service
        if nebula is not None and not emergency:
            # a failed background write surfaces here, never silently (an
            # emergency save must not die on an unrelated earlier failure)
            nebula.raise_pending_failure()
        if save_dir is None:
            if nebula is not None and self._config.nebula_config.persistent_storage_path:
                save_dir = self._config.nebula_config.persistent_storage_path
            else:
                raise ValueError("save_checkpoint requires save_dir "
                                 "(or nebula.persistent_storage_path in the config)")
        self._last_ckpt_dir = save_dir
        if emergency:
            async_save = False
        elif async_save is None:
            async_save = nebula is not None
        elif async_save and nebula is None:
            raise ValueError("async_save=True requires the nebula checkpoint service: "
                             'set "nebula": {"enabled": true} in the config')
        self._ensure_params_resident()  # NVMe-swapped leaves back for serialization
        auto_tag = tag is None
        if auto_tag:
            tag = f"global_step{self.global_steps}"
        tag = str(tag)
        if nebula is not None and auto_tag and not nebula.persist_due():
            log_dist(f"[nebula] skipping auto-tagged save '{tag}': persistent_time_interval "
                     f"({self._config.nebula_config.persistent_time_interval}s) not yet elapsed",
                     ranks=[0])
            return False
        self._validate_checkpoint_tag(tag)
        self.checkpoint_engine.create(tag)
        sharded = isinstance(self.checkpoint_engine, ShardedCheckpointEngine)
        # sharded save: leave leaves on device, every process writes its
        # own shards; consolidated save: host-ify on rank 0 only. Under
        # nebula, device state is snapshotted to host up front (the step
        # stalls for the copy only) and the write happens off-thread.
        snapshot_t0 = time.perf_counter()
        if nebula is not None:
            from deepspeed_tpu.nebula.service import snapshot_tree
            ser = snapshot_tree
        else:
            ser = (lambda t: t) if sharded else _to_serializable

        model_state = {
            "module": ser(self.params),
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            "dp_world_size": self.dp_world_size(),
            "mp_world_size": groups.get_model_parallel_world_size(),
            "ds_config": self._config._param_dict,
            "ds_version": _version(),
            "client_state": client_state,
        }
        if self.lr_scheduler is not None:
            model_state["lr_scheduler"] = self.lr_scheduler.state_dict()
        if self.training_dataloader is not None and hasattr(self.training_dataloader, "state_dict"):
            # consumed-samples + sampler RNG: resume at ANY dp width
            # neither repeats nor skips samples (global sample order is
            # world-size independent — see runtime/dataloader.py)
            model_state["dataloader_state"] = self.training_dataloader.state_dict()
        # A sharded save is ONE logical chunk store for the whole mesh:
        # every process must target the same path (global coordinates make
        # per-mp-rank files meaningless), so pin the mp placeholder.
        ckpt_name = (self._get_ckpt_name(save_dir, tag, mp_placeholder="00") if sharded
                     else self._get_ckpt_name(save_dir, tag))

        if self._host_offload is not None:
            opt_sd = self._host_offload.export_state()
            master_sd = self._host_offload.export_master()
        else:
            opt_sd = ser(self.opt_state)
            master_sd = (ser(self.master_params)
                         if self.master_params is not self.params else None)
        optim_state = {
            "optimizer_state_dict": opt_sd,
            "fp32_master_params": master_sd,
            "scaler_state": ser(self.scaler_state),
            "optimizer_param_groups": [{k: v for k, v in g.items() if k != "params"}
                                       for g in self.optimizer.param_groups],
        }
        optim_name = (self._get_optimizer_ckpt_name_sharded(save_dir, tag) if sharded
                      else self._get_optimizer_ckpt_name(save_dir, tag, dp_rank=0))

        if nebula is not None:
            snapshot_s = time.perf_counter() - snapshot_t0
            tag_dir = os.path.join(save_dir, tag)
            parts = []
            if sharded or dist.get_process_rank() == 0:
                parts = [(model_state, os.path.relpath(ckpt_name, tag_dir)),
                         (optim_state, os.path.relpath(optim_name, tag_dir))]
            if emergency:
                nebula.emergency_save(save_dir, tag, parts,
                                      deadline_s=_emergency_deadline_s,
                                      save_latest=save_latest,
                                      snapshot_s=snapshot_s, step=self.global_steps)
            else:
                submit = nebula.save_async if async_save else nebula.save_sync
                submit(save_dir, tag, parts, save_latest=save_latest,
                       snapshot_s=snapshot_s, step=self.global_steps)
            return True

        if sharded or dist.get_process_rank() == 0:
            self.checkpoint_engine.save(model_state, ckpt_name)
            self.checkpoint_engine.save(optim_state, optim_name)

        self.checkpoint_engine.commit(tag)
        # `latest` rotates only after commit, via tmp + os.replace: a
        # crash anywhere leaves the pointer naming a finished checkpoint
        if save_latest and dist.get_process_rank() == 0:
            from deepspeed_tpu.nebula.service import write_latest
            write_latest(save_dir, tag)
        return True

    def _validate_checkpoint_tag(self, tag):
        if not self.checkpoint_tag_validation_enabled:
            return
        # all control-plane ranks must agree on the tag
        digest = np.frombuffer(tag.encode().ljust(64, b"\0")[:64], dtype=np.uint8)
        gathered = dist.host_all_gather(digest)
        ok = bool((gathered == gathered[0]).all())
        msg = f"checkpoint tag '{tag}' differs across ranks"
        if not ok:
            if self._config.checkpoint_tag_validation_fail:
                raise ValueError(msg)
            logger.warning(msg)

    def load_checkpoint(self,
                        load_dir=None,
                        tag=None,
                        load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_module_only=False,
                        custom_load_fn=None):
        if self._checkpoint_service is not None:
            # barrier: never read a tag whose background write is in flight
            self._checkpoint_service.wait()
        if load_dir is None:
            ncfg = getattr(self._config, "nebula_config", None)
            if ncfg is not None and (ncfg.load_path or ncfg.persistent_storage_path):
                load_dir = ncfg.load_path or ncfg.persistent_storage_path
            else:
                raise ValueError("load_checkpoint requires load_dir "
                                 "(or nebula.load_path/persistent_storage_path in the config)")
        if self._config.load_universal_checkpoint:
            return self.load_universal_checkpoint(load_dir, tag)
        if tag is None:
            from deepspeed_tpu.elasticity import is_elastic_restart
            validated_resume = ((self._checkpoint_service is not None
                                 and self._config.nebula_config.enable_nebula_load)
                                or is_elastic_restart())
            if validated_resume:
                # manifest-validated resolution: newest *intact* tag, even
                # if `latest` names a torn or uncommitted one
                from deepspeed_tpu.nebula.service import resolve_load_tag
                tag = resolve_load_tag(load_dir)
                if tag is None:
                    logger.warning(f"No intact checkpoint found under {load_dir}; "
                                   f"starting fresh")
                    return None, {}
                latest_path = None
            else:
                latest_path = os.path.join(load_dir, "latest")
            if tag is None and latest_path is not None:
                if os.path.isfile(latest_path):
                    with open(latest_path, "r") as fd:
                        tag = fd.read().strip()
                else:
                    logger.warning(f"Unable to find latest file at {latest_path}, "
                                   f"if trying to load latest checkpoint please pass `tag`")
                    return None, {}

        ckpt_name = self._get_ckpt_name(load_dir, tag)
        if not os.path.isfile(ckpt_name):
            # sharded saves are written once under the canonical mp rank
            canonical = self._get_ckpt_name(load_dir, tag, mp_placeholder="00")
            if os.path.isfile(canonical):
                ckpt_name = canonical
            else:
                logger.warning(f"Client provided checkpoint load path: {ckpt_name} does not exist")
                return None, {}
        reader = self._reader_engine(ckpt_name)
        if isinstance(reader, ShardedCheckpointEngine) and self._initialized:
            # place each leaf straight onto its current sharding: reads
            # only this process's slices, reshards across mesh changes
            model_state = reader.load_onto(ckpt_name, {"module": self.params})
            self.params = match_named_tree(model_state["module"], self.params,
                                           strict=load_module_strict)
        else:
            model_state = reader.load(ckpt_name)
            loaded_params = match_named_tree(model_state["module"], self.params,
                                            strict=load_module_strict) \
                if self.params is not None else model_state["module"]
            if self._initialized:
                self.params = jax.tree.map(
                    lambda cur, new, sh: _place_leaf(new, cur.dtype, sh),
                    self.params, loaded_params, self._param_shardings)
            else:
                self.params = jax.tree.map(lambda x: np.asarray(x), loaded_params)

        self.global_steps = int(model_state.get("global_steps", 0))
        self.global_samples = int(model_state.get("global_samples", 0))
        self.skipped_steps = int(model_state.get("skipped_steps", 0))
        self.micro_steps = int(model_state.get("micro_steps", 0))
        # a checkpoint never captures mid-accumulation gradients; any
        # half-accumulated micro-grads from before the load would
        # contaminate the first post-resume optimizer update
        self._grads_acc = None
        self._pending = None
        self.loaded_checkpoint_dp_world_size = model_state.get("dp_world_size")
        self.loaded_checkpoint_mp_world_size = model_state.get("mp_world_size")
        client_state = model_state.get("client_state", {})

        if load_lr_scheduler_states and self.lr_scheduler is not None and "lr_scheduler" in model_state:
            self.lr_scheduler.load_state_dict(model_state["lr_scheduler"])

        self._last_ckpt_dir = load_dir
        if (model_state.get("dataloader_state") is not None
                and self.training_dataloader is not None
                and hasattr(self.training_dataloader, "load_state_dict")):
            self.training_dataloader.load_state_dict(model_state["dataloader_state"])

        if load_module_only or not load_optimizer_states:
            self._finish_elastic_resume(load_dir, tag, model_state)
            return load_dir, client_state

        optim_name = self._get_optimizer_ckpt_name(load_dir, tag, dp_rank=0)
        if not os.path.isfile(optim_name):
            optim_name = self._get_optimizer_ckpt_name_sharded(load_dir, tag)
        if os.path.isfile(optim_name):
            if self._initialized:
                self._restore_optim_state(self._load_optim_state(optim_name))
            else:
                # defer to _materialize_state: shardings don't exist yet,
                # so a sharded read can't place leaves (and an eager read
                # would gather the world) — stash the path instead
                self._pending_optim_state = ("__ckpt_path__", optim_name)
        self._finish_elastic_resume(load_dir, tag, model_state)
        return load_dir, client_state

    def _finish_elastic_resume(self, load_dir, tag, model_state):
        """Post-load elastic bookkeeping: log the re-mesh (checkpoint dp
        width N → current width M — the sharded engine already resharded
        every leaf onto the current mesh; the global batch is invariant
        because ``compute_elastic_config`` picked a divisor-rich batch,
        so only gradient-accumulation changed), emit ``Train/Elastic/*``
        recovery events, and clear the preemption resume marker."""
        ckpt_dp = self.loaded_checkpoint_dp_world_size
        cur_dp = self.dp_world_size()
        if ckpt_dp is not None and int(ckpt_dp) != int(cur_dp):
            ckpt_cfg = model_state.get("ds_config") or {}
            ckpt_gbs = ckpt_cfg.get("train_batch_size")
            cur_gbs = self.train_batch_size()
            if ckpt_gbs is not None and int(ckpt_gbs) != int(cur_gbs):
                logger.warning(
                    f"[elastic] re-mesh resume dp {ckpt_dp}→{cur_dp} changes the "
                    f"global batch ({ckpt_gbs}→{cur_gbs}): the loss curve will "
                    f"diverge from the uninterrupted run. Enable elasticity so "
                    f"compute_elastic_config keeps the global batch invariant.")
            else:
                logger.info(f"[elastic] re-mesh resume: checkpoint dp width {ckpt_dp} → "
                            f"current {cur_dp} (global batch {cur_gbs} unchanged, "
                            f"gas={self.gradient_accumulation_steps()})")
        from deepspeed_tpu.elasticity import is_elastic_restart
        from deepspeed_tpu.elasticity.preemption import clear_resume_marker, read_resume_marker
        if read_resume_marker(load_dir) is not None:
            clear_resume_marker(load_dir)
        if is_elastic_restart() and self.monitor.enabled:
            events = [("Train/Elastic/restart_count",
                       env_int("DS_ELASTIC_RESTART_COUNT"), self.global_steps),
                      ("Train/Elastic/resume_step", self.global_steps, self.global_steps),
                      ("Train/Elastic/dp_world_size", int(cur_dp), self.global_steps)]
            down_since = env_raw("DS_ELASTIC_DOWN_SINCE")
            if down_since:
                try:
                    events.append(("Train/Elastic/recovery_s",
                                   max(0.0, time.time() - float(down_since)),
                                   self.global_steps))
                except ValueError:
                    pass
            try:
                self.monitor.write_events(events)
            except Exception:
                pass

    def _reader_engine(self, path):
        """Pick the engine matching the on-disk format (a sharded write is
        readable regardless of the configured save engine, and vice versa)."""
        if ShardedCheckpointEngine.is_sharded(path):
            return self.checkpoint_engine if isinstance(self.checkpoint_engine, ShardedCheckpointEngine) \
                else ShardedCheckpointEngine()
        return self.checkpoint_engine if isinstance(self.checkpoint_engine, ArrayCheckpointEngine) \
            else ArrayCheckpointEngine()

    def _load_optim_state(self, optim_name):
        reader = self._reader_engine(optim_name)
        if isinstance(reader, ShardedCheckpointEngine) and self._initialized and self._host_offload is None:
            # scaler_state is deliberately absent from the sharded-load
            # target: its tiny scalar leaves load eagerly via the
            # skeleton fallback, then _commit_scaler_state re-places them
            target = {
                "optimizer_state_dict": self.opt_state,
                "fp32_master_params": (self.master_params
                                       if self.master_params is not self.params else None),
            }
            return reader.load_onto(optim_name, target)
        return reader.load(optim_name)

    def _commit_scaler_state(self):
        """Commit the scaler scalars to their replicated device sharding:
        freshly-(re)built scaler leaves are uncommitted jnp.asarray
        scalars, but the fused train program returns them committed — an
        aval change that would retrace and RECOMPILE the whole program on
        the next call. Invoked at materialize AND after every checkpoint
        restore that reassigns scaler_state."""
        if getattr(self, "mesh", None) is not None and self.scaler_state is not None:
            self.scaler_state = jax.device_put(
                self.scaler_state, NamedSharding(self.mesh, P()))

    def _restore_optim_state(self, optim_state):
        if isinstance(optim_state, tuple) and optim_state and optim_state[0] == "__ckpt_path__":
            optim_state = self._load_optim_state(optim_state[1])
        if self._host_offload is not None:
            self._host_offload.load_state(optim_state["optimizer_state_dict"])
            if optim_state.get("fp32_master_params") is not None:
                self._host_offload.load_master(optim_state["fp32_master_params"])
                self.params = self._host_offload.current_params()
            if optim_state.get("scaler_state") is not None:
                self.scaler_state = jax.tree.map(jnp.asarray, match_named_tree(optim_state["scaler_state"],
                                                                               self.scaler_state))
                self._commit_scaler_state()
            for g, g_new in zip(self.optimizer.param_groups, optim_state.get("optimizer_param_groups", [])):
                g.update(g_new)
            return
        loaded_opt = match_named_tree(optim_state["optimizer_state_dict"], self.opt_state)
        self.opt_state = jax.tree.map(
            lambda cur, new: _place_leaf(new, cur.dtype, cur.sharding),
            self.opt_state, loaded_opt)
        if optim_state.get("fp32_master_params") is not None and self.master_params is not self.params:
            loaded_m = match_named_tree(optim_state["fp32_master_params"], self.master_params)
            self.master_params = jax.tree.map(
                lambda cur, new: _place_leaf(new, cur.dtype, cur.sharding),
                self.master_params, loaded_m)
        if "scaler_state" in optim_state and optim_state["scaler_state"] is not None:
            self.scaler_state = jax.tree.map(jnp.asarray, match_named_tree(optim_state["scaler_state"],
                                                                           self.scaler_state))
            self._commit_scaler_state()
        for g, g_new in zip(self.optimizer.param_groups, optim_state.get("optimizer_param_groups", [])):
            g.update(g_new)

    # ------------------------------------------------------------------
    # Universal checkpoint load (reference universal_checkpoint.py:
    # load_hp_checkpoint_state re-slices consolidated fp32 per rank)
    # ------------------------------------------------------------------
    def load_universal_checkpoint(self, load_dir, tag=None):
        from deepspeed_tpu.checkpoint.universal import is_universal_dir, load_universal_metadata
        udir = load_dir
        if not is_universal_dir(udir) and tag is not None:
            cand = os.path.join(load_dir, str(tag))
            if is_universal_dir(cand):
                udir = cand
        if not is_universal_dir(udir):
            raise FileNotFoundError(f"{load_dir} is not a universal checkpoint "
                                    f"(run deepspeed_tpu.checkpoint.ds_to_universal first)")
        meta = load_universal_metadata(udir)
        if self._initialized:
            self._apply_universal(udir)
        else:
            self._apply_universal_metadata(meta)
            self._pending_universal = udir
        return udir, meta.get("client_state", {})

    def _apply_universal_metadata(self, meta):
        self.global_steps = int(meta.get("global_steps", 0))
        self.global_samples = int(meta.get("global_samples", 0))
        self.skipped_steps = int(meta.get("skipped_steps", 0))
        self.micro_steps = int(meta.get("micro_steps", 0))
        if self.lr_scheduler is not None and meta.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        for g, g_new in zip(self.optimizer.param_groups, meta.get("optimizer_param_groups") or []):
            g.update(g_new)
        if meta.get("scaler_state"):
            for k, v in meta["scaler_state"].items():
                if k in self.scaler_state:
                    cur = self.scaler_state[k]
                    self.scaler_state[k] = jnp.asarray(v, getattr(cur, "dtype", jnp.float32))
            self._commit_scaler_state()

    def _load_universal_index(self, udir):
        """Shared universal-load prologue: read + apply metadata, then
        validate the param index covers the model with matching shapes."""
        from deepspeed_tpu.checkpoint.universal import load_universal_metadata
        meta = load_universal_metadata(udir)
        self._apply_universal_metadata(meta)
        index = meta.get("params", {})
        named = dict(flatten_named(self.params))
        missing = [p for p in named if p not in index]
        if missing:
            raise KeyError(f"universal checkpoint missing {len(missing)} params (e.g. {missing[:5]})")
        for p, cur in named.items():
            if tuple(index[p]["shape"]) != tuple(cur.shape):
                raise ValueError(f"universal param {p}: checkpoint shape {index[p]['shape']} "
                                 f"!= model shape {tuple(cur.shape)}")
        return meta, index, named

    def _apply_universal(self, udir):
        from deepspeed_tpu.checkpoint.universal import read_universal_param
        if self._host_offload is not None:
            return self._apply_universal_offload(udir)
        meta, index, named = self._load_universal_index(udir)

        mixed = self.master_params is not self.params
        params_treedef = jax.tree.structure(self.params)
        moment_keys = [k for k, v in self.opt_state.items()
                       if jax.tree.structure(v) == params_treedef] if isinstance(self.opt_state, dict) else []

        named_master = dict(flatten_named(self.master_params)) if mixed else {}
        named_moments = {mk: dict(flatten_named(self.opt_state[mk])) for mk in moment_keys}
        new_params, new_master = {}, {}
        new_moments = {k: {} for k in moment_keys}
        for p, cur in named.items():
            fp32 = read_universal_param(udir, p)  # mmap'd; sliced per shard
            shape = tuple(fp32.shape)
            new_params[p] = _place_np(fp32, cur.dtype, cur.sharding, shape)
            if mixed:
                mleaf = named_master[p]
                new_master[p] = _place_np(fp32, mleaf.dtype, mleaf.sharding, shape)
            for mk in moment_keys:
                oleaf = named_moments[mk][p]
                if mk in index[p].get("moments", []):
                    mom = read_universal_param(udir, p, name=mk)
                    new_moments[mk][p] = _place_np(mom, oleaf.dtype, oleaf.sharding, shape)
                else:
                    new_moments[mk][p] = jnp.zeros_like(oleaf)

        self.params = match_named_tree(new_params, self.params)
        if mixed:
            self.master_params = match_named_tree(new_master, self.master_params)
        scalars = meta.get("optimizer_scalars", {})
        if isinstance(self.opt_state, dict):
            for k in list(self.opt_state.keys()):
                if k in moment_keys:
                    self.opt_state[k] = match_named_tree(new_moments[k], self.opt_state[k])
                elif k in scalars:
                    cur = self.opt_state[k]
                    self.opt_state[k] = jax.device_put(
                        np.asarray(scalars[k]).astype(cur.dtype), cur.sharding)

    def _apply_universal_offload(self, udir):
        """Universal checkpoint → host-offload optimizer state: the fp32
        consolidated params become the host master copy, moments refill
        the flat host (or NVMe-swapped) state regions, and compute-dtype
        device params are rebuilt from the master (reference loads
        universal hp state into stage_1_and_2's CPU partitions the same
        way, universal_checkpoint.py:22 load_hp_checkpoint_state). State
        streams into the flat host regions one parameter at a time — no
        second full-model host copy for exactly the engines sized to
        need offloading."""
        from deepspeed_tpu.checkpoint.universal import read_universal_param, ZERO_FP32
        ho = self._host_offload
        meta, index, named = self._load_universal_index(udir)
        unmapped = [p for p in named if p not in set(ho.paths)]
        if unmapped:
            raise KeyError(f"universal load: {len(unmapped)} params have no offload "
                           f"region (e.g. {unmapped[:3]})")
        ho.load_from_reader(
            read=lambda p, mk: read_universal_param(udir, p, name=mk or ZERO_FP32),
            moments_of=lambda p: index[p].get("moments", []),
            step=meta.get("optimizer_scalars", {}).get("step"))
        self.params = ho.current_params()

    def compile(self, backend=None, compile_kwargs=None):
        """torch.compile parity (reference engine.py:3612 ``compile``):
        on this engine every hot path is ALREADY a jitted XLA program —
        forward/backward, the fused train_batch scan, and the optimizer
        update compile on first use — so this records the request and
        returns the engine. ``backend`` other than 'xla' raises."""
        if backend not in (None, "xla"):
            raise ValueError(f"compile backend {backend!r} unsupported (XLA is built in)")
        if compile_kwargs:
            logger.warning(f"engine.compile: ignoring torch.compile kwargs {list(compile_kwargs)} "
                           f"— XLA jit has no equivalents")
        self._is_compiled = True
        return self

    @property
    def is_compiled(self):
        # jit compilation is unconditional; the flag only records that
        # compile() was requested (reference semantics)
        return getattr(self, "_is_compiled", False)

    # module state dict parity
    def module_state_dict(self, exclude_frozen_parameters=False):
        if exclude_frozen_parameters and getattr(self, "_trainable_mask", None) is not None:
            named = flatten_named(self.params)
            mask = dict(flatten_named(self._trainable_mask))
            from deepspeed_tpu.utils.zero_to_fp32 import _nest
            return _nest({p: np.asarray(jax.device_get(x))
                          for p, x in named if mask.get(p, True)})
        return _to_serializable(self.params)

    def load_module_state_dict(self, state_dict, strict=True, custom_load_fn=None):
        if self._initialized:
            self.params = jax.tree.map(
                lambda cur, new, sh: _place_leaf(new, cur.dtype, sh),
                self.params, match_named_tree(state_dict, self.params, strict=strict),
                self._param_shardings)
        else:
            self.params = state_dict

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin", exclude_frozen_parameters=False):
        """Consolidated compute-dtype weights (reference engine.py:3436)."""
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, save_filename.replace(".bin", ".msgpack"))
        self.checkpoint_engine.save(_to_serializable(self.params), path)
        return True


def _is_float(x):
    return jnp.issubdtype(jnp.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype, jnp.floating)


def _is_pytree_of_arrays(x):
    if x is None:
        return False
    leaves = jax.tree.leaves(x)
    return len(leaves) > 0 and all(hasattr(l, "shape") for l in leaves)


def _to_serializable(tree):
    if tree is None:
        return None
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)) if hasattr(x, "shape") else x, tree)


def _tree_map_indexed(fn, tree, *rest):
    """tree.map with a leaf index as the first argument."""
    leaves, treedef = jax.tree.flatten(tree)
    rest_leaves = [treedef.flatten_up_to(r) for r in rest]
    out = [fn(i, leaf, *(r[i] for r in rest_leaves)) for i, leaf in enumerate(leaves)]
    return treedef.unflatten(out)


def _place_np(arr, dtype, sharding, shape):
    """Place a host (possibly mem-mapped) array onto ``sharding``,
    reading only the slices the addressable devices need."""
    idx_map = sharding.addressable_devices_indices_map(tuple(shape))
    cache = {}
    bufs = []
    for dev, idx in idx_map.items():
        key = tuple(sl.indices(d)[:2] for sl, d in zip(idx, shape))
        if key not in cache:
            cache[key] = np.ascontiguousarray(np.asarray(arr[idx])).astype(dtype)
        bufs.append(jax.device_put(cache[key], dev))
    return jax.make_array_from_single_device_arrays(tuple(shape), sharding, bufs)


def _place_leaf(new, dtype, sharding):
    """Place a loaded leaf on ``sharding`` without a host round-trip when
    it is already a correctly-placed jax.Array (the sharded-read path)."""
    if isinstance(new, jax.Array) and getattr(new, "sharding", None) == sharding and new.dtype == dtype:
        return new
    if isinstance(new, jax.Array):
        return jax.device_put(new.astype(dtype), sharding)
    return jax.device_put(np.asarray(new).astype(dtype), sharding)


def _version():
    from deepspeed_tpu import __version__
    return __version__

"""TPU accelerator (the primary backend).

Plays the role of the reference's ``accelerator/cuda_accelerator.py``:
device queries, memory stats (via PJRT ``memory_stats``), dtype support,
synchronization, and op-builder dispatch for the ``op_builder/tpu``
registry.
"""

import os

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"
        self._compile_backend = "xla"
        self._seed = 0

    def _jax(self):
        import jax
        return jax

    def _devices(self):
        return self._jax().devices()

    # Device APIs
    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index=None):
        devs = self._devices()
        return devs[device_index or 0]

    def set_device(self, device_index):
        # JAX addresses all local devices from one process; no-op.
        pass

    def current_device(self):
        return 0

    def current_device_name(self):
        return "tpu:0"

    def device_count(self):
        return len(self._devices())

    def synchronize(self, device_index=None):
        import jax
        (jax.device_put(0.0) + 0).block_until_ready()

    # RNG APIs
    def random(self):
        import jax
        return jax.random

    def manual_seed(self, seed):
        self._seed = seed

    def initial_seed(self):
        return self._seed

    def default_generator(self, device_index):
        import jax
        return jax.random.PRNGKey(self._seed)

    # Memory management
    def empty_cache(self):
        pass

    def _mem_stats(self, device_index=None):
        return self.device(device_index).memory_stats() or {}

    def memory_allocated(self, device_index=None):
        return self._mem_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index=None):
        return self._mem_stats(device_index).get("peak_bytes_in_use", 0)

    def reset_max_memory_allocated(self, device_index=None):
        pass

    def memory_stats(self, device_index=None):
        return self._mem_stats(device_index)

    def available_memory(self, device_index=None):
        stats = self._mem_stats(device_index)
        limit = stats.get("bytes_limit", self.total_memory(device_index))
        return limit - stats.get("bytes_in_use", 0)

    def total_memory(self, device_index=None):
        stats = self._mem_stats(device_index)
        if "bytes_limit" not in stats:
            raise RuntimeError(
                f"{self.device(device_index)} reports no bytes_limit in memory_stats(); "
                f"its HBM size is not known and is not guessed")
        return stats["bytes_limit"]

    # Data type support
    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        # TPUs compute natively in bf16; fp16 storage is supported, matmul
        # accumulates via fp32, loss-scaling path is still honored.
        return True

    def supported_dtypes(self):
        import jax.numpy as jnp
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.float8_e4m3fn, jnp.float8_e5m2]

    # Misc
    def communication_backend_name(self):
        return self._communication_backend_name

    def is_available(self):
        return any(d.platform == "tpu" for d in self._devices())

    def range_push(self, msg):
        try:
            import jax.profiler
            self._trace_ctx = jax.profiler.TraceAnnotation(msg)
            self._trace_ctx.__enter__()
        except Exception:
            pass

    def range_pop(self):
        try:
            if getattr(self, "_trace_ctx", None) is not None:
                self._trace_ctx.__exit__(None, None, None)
                self._trace_ctx = None
        except Exception:
            pass

    def lazy_call(self, callback):
        callback()

    def is_triton_supported(self):
        return False

    def use_host_timers(self):
        return True

    def resolves_data_dependency(self):
        return True

    def handles_memory_backpressure(self):
        return True

    # Op builder dispatch
    def op_builder_dir(self):
        return "op_builder.tpu"

    def create_op_builder(self, class_name):
        builder_class = self.get_op_builder(class_name)
        return builder_class() if builder_class is not None else None

    def get_op_builder(self, class_name):
        from op_builder import tpu as tpu_builders
        return getattr(tpu_builders, class_name, None)

    def build_extension(self):
        return None

    def export_envs(self):
        return ["JAX_", "XLA_", "TPU_", "LIBTPU"]

    # ------------------------------------------------------------------
    # Extended surface (reference cuda_accelerator.py parity, TPU forms)
    # ------------------------------------------------------------------
    def set_rng_state(self, new_state, device_index=None):
        self._rng_state = new_state

    def get_rng_state(self, device_index=None):
        import jax
        state = getattr(self, "_rng_state", None)
        return state if state is not None else jax.random.PRNGKey(self._seed)

    # Streams/events: XLA owns scheduling — these are inert handles that
    # keep stream-structured caller code running unchanged.
    class _NullStream:
        def synchronize(self):
            pass

        def wait_stream(self, other):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    class _NullEvent:
        def record(self, stream=None):
            import time
            self._t = time.perf_counter()

        def synchronize(self):
            pass

        def elapsed_time(self, other):
            return abs(getattr(other, "_t", 0.0) - getattr(self, "_t", 0.0)) * 1e3

        def query(self):
            return True

    def Stream(self, device=None, priority=0, **kwargs):
        return TPU_Accelerator._NullStream()

    def stream(self, stream):
        return stream if hasattr(stream, "__enter__") else TPU_Accelerator._NullStream()

    def current_stream(self, device_index=None):
        return TPU_Accelerator._NullStream()

    def default_stream(self, device_index=None):
        return TPU_Accelerator._NullStream()

    def Event(self, **kwargs):
        return TPU_Accelerator._NullEvent()

    def amp(self):
        return None  # precision policy is the engine's dtype config

    # CUDA-graph parity: a jitted callable IS the captured graph
    def create_graph(self):
        return {"fn": None}

    def capture_to_graph(self, graph, pool=None, stream=None):
        import contextlib
        return contextlib.nullcontext(graph)

    def replay_graph(self, graph):
        fn = graph.get("fn")
        if fn is not None:
            return fn()

    @property
    def BFloat16Tensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.bfloat16)

    @property
    def ByteTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.uint8)

    @property
    def DoubleTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.float64)

    @property
    def FloatTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.float32)

    @property
    def HalfTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.float16)

    @property
    def IntTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.int32)

    @property
    def LongTensor(self):
        import functools
        import jax.numpy as jnp
        return functools.partial(jnp.asarray, dtype=jnp.int64)

    def pin_memory(self, tensor, align_bytes=1):
        return tensor  # host numpy feeds DMA directly under PJRT

    def is_pinned(self, tensor):
        return True

    def on_accelerator(self, tensor):
        import jax
        return isinstance(tensor, jax.Array) and any(
            d.platform == "tpu" for d in tensor.devices())

    def visible_devices_envs(self):
        return ["TPU_VISIBLE_DEVICES"]

    def set_visible_devices_envs(self, current_env, local_accelerator_ids):
        for env in self.visible_devices_envs():
            current_env[env] = ",".join(map(str, local_accelerator_ids))

    def get_compile_backend(self):
        return self._compile_backend

    def set_compile_backend(self, backend):
        self._compile_backend = backend

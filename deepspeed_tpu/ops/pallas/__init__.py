"""Pallas TPU kernels — the framework's native-kernel layer.

TPU-native replacement for the reference's CUDA device code under
``csrc/`` (training transformer kernels ``csrc/transformer/``, inference
kernels ``csrc/transformer/inference/csrc/``, quantization
``csrc/quantization/``): instead of hand-written CUDA bound via
pybind11, the hot ops are Pallas kernels launched from jitted XLA
programs. Everything else (bias-add, gelu chains, residual adds, …) is
left to the XLA fuser on purpose — re-implementing those would only
defeat the compiler.

Dispatch policy: each op has a reference XLA implementation and a
Pallas kernel; ``use_pallas()`` selects the kernel on TPU backends
(override with ``DS_PALLAS=0/1``). Kernels run COMPILED: interpreter
mode is what a caller asks for (``interpret=True``, ``FORCE_INTERPRET``,
or ``DS_PALLAS=1`` on a backend with no Mosaic, as the CPU tests do) —
see :func:`default_interpret`. A call site that pinned a kernel raises
when the kernel cannot run; only unpinned ("auto") sites fall back to
the XLA reference.
"""

import contextlib
import contextvars

import jax

# ``pallas_call`` has no GSPMD partitioning rule: inside a sharded jit,
# XLA treats it as an opaque custom call and at best fully replicates
# its operands. Kernels are therefore only dispatched when operands are
# provably shard-local: single-device meshes, or inside a
# ``shard_map_kernel`` wrapper that manualizes every mesh axis. The two
# context vars below track where a trace currently sits.
_local_kernel_ctx = contextvars.ContextVar("ds_pallas_local", default=False)
_manual_axes_ctx = contextvars.ContextVar("ds_pallas_manual_axes", default=frozenset())


@contextlib.contextmanager
def manual_axes(names):
    """Declare (while tracing) that ``names`` mesh axes are already under
    a manual ``shard_map`` (e.g. the pipeline engine's 'pipe' axis), so
    kernel call sites must not open a second full-mesh shard_map."""
    tok = _manual_axes_ctx.set(frozenset(names) | _manual_axes_ctx.get())
    try:
        yield
    finally:
        _manual_axes_ctx.reset(tok)


def current_manual_axes():
    return _manual_axes_ctx.get()


def _pallas_enabled() -> bool:
    from deepspeed_tpu.utils.env_registry import env_opt_bool
    forced = env_opt_bool("DS_PALLAS")
    if forced is not None:
        return forced
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """The ``interpret=None`` default of every kernel entry point.

    False — kernels compile through Mosaic — with one exception:
    ``DS_PALLAS=1`` forcing the kernel path on a backend that has no
    Mosaic (the virtual CPU mesh of the test suite), where interpreted
    is the only way the forced kernel can run. On a TPU it is never
    True, so nothing the chip runs is silently interpreted."""
    from deepspeed_tpu.utils.env_registry import env_opt_bool
    return env_opt_bool("DS_PALLAS") is True and jax.default_backend() != "tpu"


def use_pallas() -> bool:
    """Should an op take its Pallas kernel path *here*? True only when
    the kernel is enabled AND its operands are shard-local (no active
    multi-device mesh, or we are inside a ``shard_map_kernel`` body)."""
    if not _pallas_enabled():
        return False
    if _local_kernel_ctx.get():
        return True
    from deepspeed_tpu.parallel import groups
    mesh = groups.get_mesh(required=False)
    return mesh is None or mesh.size == 1


def kernel_dispatch(mesh=None) -> str:
    """How a Pallas-backed call site should execute given the active
    mesh: 'direct' (call the op, it will pick the kernel), 'shard_map'
    (wrap in :func:`shard_map_kernel` with the canonical layout), or
    'xla' (kernel unavailable/unsafe — op takes its XLA fallback)."""
    if not _pallas_enabled():
        return "xla"
    if mesh is None:
        from deepspeed_tpu.parallel import groups
        mesh = groups.get_mesh(required=False)
    if mesh is None or mesh.size == 1:
        return "direct"
    if current_manual_axes():
        # Already inside a partially-manual shard_map: the remaining
        # axes are still GSPMD-sharded and a nested full-mesh shard_map
        # is not expressible, so stay on the XLA path.
        return "xla"
    return "shard_map"


def spec_divides(mesh, spec, shape) -> bool:
    """True when every sharded dim of ``shape`` splits evenly over its
    spec's mesh axes (shard_map requires even splits); call before
    wrapping with :func:`shard_map_kernel`."""
    from deepspeed_tpu.sequence.layer import _mesh_axis_sizes
    sizes = _mesh_axis_sizes(mesh)
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        if n > 1 and dim % n != 0:
            return False
    return True


def shard_map_kernel(fn, mesh, in_specs, out_specs):
    """Wrap a Pallas-backed op so it runs per-shard under ``mesh``.

    ``in_specs``/``out_specs`` must be the canonical activation layout
    at the call site (the caller constrains to it). Inside the body the
    operands are shard-local, so ``use_pallas()`` is True there.
    """
    def body(*args):
        tok = _local_kernel_ctx.set(True)
        try:
            return fn(*args)
        finally:
            _local_kernel_ctx.reset(tok)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402,F401
from deepspeed_tpu.ops.pallas.fused_norms import fused_layer_norm, fused_rms_norm  # noqa: E402,F401
from deepspeed_tpu.ops.pallas.fused_quant_matmul import dequantize_grouped, quant_matmul  # noqa: E402,F401
from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, gmm_quant  # noqa: E402,F401
from deepspeed_tpu.ops.pallas.quantization import dequantize_int8, quantize_int8  # noqa: E402,F401

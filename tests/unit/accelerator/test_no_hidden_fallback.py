"""The chip is never quietly replaced: entry points that measure it fail
without one, pinned kernels raise instead of degrading, device facts are
not guessed, and the compile cache sits where it was placed.

(The kernel-pin half for the paged decode kernel lives with the registry
in ``tests/unit/inference/test_v2_heuristics.py``.)"""

import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _python(args, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.Popen([sys.executable, *args], cwd=REPO, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = _python([os.path.join(REPO, "chip_smoke.py")])
    out, err = proc.communicate(timeout=120)
    assert proc.returncode not in (0, None)
    assert "platform 'cpu'" in err and "nothing was run" in err
    assert '"ok"' not in out  # no result line


def test_compile_cache_is_one_fixed_in_checkout_path_across_processes(tmp_path):
    code = ("from deepspeed_tpu.utils.compile_cache import enable_compile_cache\n"
            "import jax\n"
            "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)")
    procs = [_python(["-c", code]), _python(["-c", code]),
             _python(["-c", code], JAX_COMPILATION_CACHE_DIR=str(tmp_path))]
    outs = []
    for proc in procs:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        outs.append(out.split())
    in_checkout = os.path.join(REPO, ".jax_cache")
    assert outs[0] == outs[1] == [in_checkout, in_checkout]
    # placed from outside: reported as placed, and JAX (not the helper) honours it
    assert outs[2] == [str(tmp_path), str(tmp_path)]


def test_compile_cache_helper_sets_nothing_when_placed(monkeypatch, tmp_path):
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_pinned_flash_attention_raises_where_the_kernel_cannot_run():
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="attention_impl='flash' is pinned.*backend='cpu'"):
        build_llama("debug", attention_impl="flash").init(jax.random.PRNGKey(0), ids)
    build_llama("debug", attention_impl="auto").init(jax.random.PRNGKey(0), ids)  # may choose
    q = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="bias path has no kernel"):
        flash_attention(q, q, q, bias=jnp.zeros((1, 1, 8, 8)), force_pallas=True)


def test_interpret_mode_is_asked_for_never_inferred_from_the_backend(monkeypatch):
    from deepspeed_tpu.ops.pallas import default_interpret
    monkeypatch.delenv("DS_PALLAS", raising=False)
    assert default_interpret() is False
    monkeypatch.setenv("DS_PALLAS", "1")  # the CPU tests' explicit request
    assert default_interpret() is True


def test_tpu_accelerator_does_not_guess():
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
    acc = TPU_Accelerator()
    assert acc.is_available() is False  # these are CPU devices
    with pytest.raises(RuntimeError, match="no bytes_limit"):
        acc.total_memory()


def test_autotuner_parent_stays_off_jax_until_it_runs_experiments_itself():
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    assert Autotuner(model_fn=None, batch_fn=None, base_config={}).world_size is None
    assert Autotuner(model_fn=None, batch_fn=None, base_config={}, world_size=4).world_size == 4

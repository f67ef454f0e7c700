"""Runtime accelerator selection.

Analogue of the reference's ``accelerator/real_accelerator.py``
(``get_accelerator()`` at real_accelerator.py:51): env override via
``DS_ACCELERATOR`` plus auto-detect (TPU if any non-CPU JAX device is
visible, else CPU).
"""


ds_accelerator = None

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu"]


def _validate_accelerator(accel_name):
    assert accel_name in SUPPORTED_ACCELERATOR_LIST, (
        f"accelerator name {accel_name} not supported; supported: {SUPPORTED_ACCELERATOR_LIST}")


def is_current_accelerator_supported():
    return get_accelerator().device_name() in SUPPORTED_ACCELERATOR_LIST


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    from deepspeed_tpu.utils.env_registry import env_raw

    accelerator_name = env_raw("DS_ACCELERATOR")
    if accelerator_name is not None:
        _validate_accelerator(accelerator_name)

    if accelerator_name is None:
        # a backend that fails to initialize raises here: a chip that is
        # there but unusable must not be mistaken for "this is a CPU host"
        import jax
        accelerator_name = "cpu" if all(d.platform == "cpu" for d in jax.devices()) else "tpu"

    set_accelerator_name(accelerator_name)
    return ds_accelerator


def set_accelerator_name(accelerator_name):
    global ds_accelerator
    if accelerator_name == "tpu":
        from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
    elif accelerator_name == "cpu":
        from deepspeed_tpu.accelerator.cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
    else:
        _validate_accelerator(accelerator_name)
    return ds_accelerator


def set_accelerator(accel_obj):
    global ds_accelerator
    ds_accelerator = accel_obj
    return ds_accelerator
